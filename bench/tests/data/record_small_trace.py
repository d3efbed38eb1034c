#!/usr/bin/env python3
"""Record ``small.xplane.pb``, the trace ``test_bench_trace.py`` reduces,
on the chip (one TPU, run from the checkout's root):

    python3 bench/tests/data/record_small_trace.py

Three rounds of a host span ``bench.compute`` around a matrix program and
the program's fused paged attention kernel, each followed by a host span
``bench.host`` that sleeps 20 ms, so the device idles under it.
"""
import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.paged_attention import (  # noqa: E402
    fused_paged_attention_pallas)


def main() -> None:
    out = os.path.join(ROOT, ".bench_trace", "small")
    shutil.rmtree(out, ignore_errors=True)
    R, H, Hkv, D, page, npages, MP = 8, 32, 8, 128, 32, 64, 8
    q = jnp.ones((R, H, D), jnp.float32)
    kv = jax.random.normal(jax.random.PRNGKey(0),
                           (npages, page, 2 * Hkv, D))
    tables = (jnp.arange(R * MP).reshape(R, MP) % (npages - 1)
              ).astype(jnp.int32) + 1
    lens = jnp.full((R,), 200, jnp.int32)
    attn = jax.jit(lambda q, kv, t, n: fused_paged_attention_pallas(
        q, kv, t, n, page_size=page))
    mm = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    a = jnp.ones((2048, 2048), jnp.float32)
    attn(q, kv, tables, lens).block_until_ready()
    mm(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.compute"):
            mm(a).block_until_ready()
            attn(q, kv, tables, lens).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.host"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)[0]
    shutil.copy(src, os.path.join(HERE, "small.xplane.pb"))
    shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()

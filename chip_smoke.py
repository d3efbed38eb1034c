#!/usr/bin/env python3
"""Bring-up smoke run of the TreePO main path on one TPU chip.

One process drives, through the public entry points, at qwen3-4b's
published widths cut to 2 layers, with random weights from a seed:

* RL phase: ``RLTrainer.train_step`` twice with the sequence-packed
  update — tree rollout (prefill, paged decode, fork/COW), advantage,
  packed PPO update;
* serve phase: ``Scheduler(mode="continuous", radix=True)`` over 4 seeded
  requests on a ``TreeEngine`` holding the trained params, as
  ``repro.launch.serve`` does.

Each phase prints compile and run seconds, tokens and peak device bytes;
the compiled update and decode programs are checked for Pallas kernels
(``tpu_custom_call``).  Every check raises on failure, and the last line
of standard output is one JSON object naming the device:

    python chip_smoke.py

It exits non-zero, printing no result, where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import math
import os
import random
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

SEED = 0
NUM_LAYERS = 2
QUERIES, WIDTH, DEPTH, SEGMENT = 2, 4, 3, 32
MAX_PROMPT = 256
RL_STEPS = 2
SERVE_REQUESTS, SERVE_MAX_NEW = 4, 64
# Step 1 trains on the rollout's own params, so the PPO ratio compares the
# paged-decode logprobs with the update's full-forward logprobs of the same
# tokens: 1 up to numerics.  TPU matmuls on f32 operands default to one
# bf16 pass, which leaves each logprob off by about 1e-2 in different ways
# on the two paths; their mean over hundreds of tokens stays far inside
# 2e-2, while a wrong kernel moves logprobs by whole nats.
RATIO_TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")
    print(f"  ok: {what}", flush=True)


class CompileClock:
    """Seconds JAX spends lowering and compiling programs (persistent-cache
    loads included; tracing, which nests, is left in the run time), and
    the persistent-cache hits, since construction."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def abstract(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def pages_needed(prompt_len: int, new_tokens: int, rows: int) -> int:
    """Worst-case pages for ``rows`` paths, doubled for COW tail pages and
    paths retained until their tree finishes, plus the garbage page."""
    per_path = -(-(prompt_len + new_tokens) // SEGMENT)
    return 2 * rows * per_path + 1


def run_timed(clock: CompileClock, fn):
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = clock.seconds - c0
    return out, wall, comp, clock.cache_hits - h0


def rl_phase(cfg, clock: CompileClock):
    from repro.configs.base import TrainConfig, TreeConfig
    from repro.rl.trainer import RLTrainer, TrainerMode

    tree_cfg = TreeConfig(max_depth=DEPTH, segment_len=SEGMENT,
                          max_width=WIDTH, branch_factor=2,
                          init_divergence_low=2, init_divergence_high=2,
                          temperature=1.0)
    train_cfg = TrainConfig(batch_size=QUERIES, group_size=WIDTH,
                            oversample_factor=1, max_resample_rounds=0,
                            dynamic_sampling=False, pack_sequences=True)
    num_pages = pages_needed(MAX_PROMPT, tree_cfg.max_response_len,
                             QUERIES * WIDTH)
    engine_kwargs = dict(num_pages=num_pages, page_size=SEGMENT,
                         max_slots=64, max_queries=QUERIES,
                         max_prompt_len=MAX_PROMPT)
    trainer = RLTrainer(cfg, train_cfg, tree_cfg, TrainerMode.TREEPO,
                        seed=SEED, engine_kwargs=engine_kwargs)
    print(f"rl: {cfg.name} params={cfg.num_params():,} pages={num_pages} "
          f"queries={QUERIES} width={WIDTH} depth={DEPTH} "
          f"segment={SEGMENT}", flush=True)
    steps = []
    for i in range(RL_STEPS):
        m, wall, comp, hits = run_timed(
            clock, lambda: trainer.train_step(num_queries=QUERIES))
        steps.append(m)
        print(f"rl step {i + 1}: wall_s={wall:.3f} compile_s={comp:.3f} "
              f"run_s={wall - comp:.3f} cache_hits={hits} "
              f"rollout_tokens={m['sample_model_tokens']:.0f} "
              f"trajectories={m['num_trajectories']:.0f} "
              f"loss={m.get('loss', float('nan')):.6g} "
              f"ratio_mean={m.get('ratio_mean', float('nan')):.6f} "
              f"grad_norm={m.get('grad_norm', float('nan')):.6g} "
              f"pad_fraction={m['padded_token_fraction']:.3f} "
              f"peak_bytes_in_use={peak_bytes()}", flush=True)
    for i, m in enumerate(steps, 1):
        check("skipped" not in m and "loss" in m,
              f"step {i}: the packed update ran")
        check(m.get("skipped_nonfinite", 1.0) == 0.0,
              f"step {i}: skipped_nonfinite == 0")
        check(math.isfinite(m["loss"]), f"step {i}: loss is finite")
    dev = abs(steps[0]["ratio_mean"] - 1.0)
    check(dev <= RATIO_TOL,
          f"step 1: |ratio_mean - 1| = {dev:.2e} <= {RATIO_TOL:g} "
          "(paged-decode vs full-forward logprobs)")
    return trainer, tree_cfg, engine_kwargs


def pallas_kernels(compiled) -> collections.Counter:
    """Pallas kernels in a compiled program, by the name of the jitted
    wrapper XLA gives each ``tpu_custom_call`` instruction."""
    names = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.match(r"\s*(?:ROOT )?%([A-Za-z_]\w*?)(?:\.\d+)* = ", line)
            # under autodiff the wrapper is named jvp_jit_<kernel>__
            name = re.sub(r"^(?:jvp_|jit_)+", "", m.group(1)).rstrip("_") \
                if m else "?"
            names[name] += 1
    return names


def kernel_evidence(trainer, engine) -> None:
    """Compile the update and tree-decode programs the RL phase ran, count
    the Pallas kernels left in each, and check the update's memory."""
    import numpy as np

    (Nb, L, S), update_fn = list(trainer._packed_update_fns.items())[-1]
    i32, f32 = np.int32, np.float32
    sds = jax.ShapeDtypeStruct
    update = update_fn.lower(
        abstract(trainer.params), abstract(trainer.opt_state),
        sds((Nb, L), i32), sds((Nb, L), f32), sds((Nb, S), i32),
        sds((Nb, S), i32), sds((Nb, S), f32), sds((), i32)).compile()
    Rb = QUERIES * WIDTH
    runner = engine.runner
    decode = runner.get_decode_fn(Rb, SEGMENT).lower(
        abstract(runner.params), abstract(runner.kv.kv_pools),
        abstract(runner.kv.rec_state), abstract(runner.cross_pool),
        sds((Rb,), i32), sds((Rb,), f32), sds((Rb,), i32),
        sds((Rb, runner.MP), i32), sds((Rb,), i32), sds((Rb,), i32),
        abstract(jax.random.PRNGKey(0))).compile()
    k_update, k_decode = pallas_kernels(update), pallas_kernels(decode)
    mem = update.memory_analysis()
    pool = sum(a.nbytes for a in jax.tree.leaves(runner.kv.kv_pools))
    limit = int((jax.devices()[0].memory_stats() or {}).get("bytes_limit", 0))
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes + pool)
    print(f"kernels: update(N={Nb}, L={L}, S={S}) {dict(k_update)}; "
          f"decode(R={Rb}, l={SEGMENT}) {dict(k_decode)}")
    print(f"memory: update args={mem.argument_size_in_bytes} "
          f"temp={mem.temp_size_in_bytes} kv_pool={pool} "
          f"bytes_limit={limit}", flush=True)
    check(k_update["flash_attention_pallas"] >= NUM_LAYERS,
          "flash attention kernels in the compiled update")
    check(k_decode["fused_paged_attention_pallas"] >= NUM_LAYERS,
          "paged attention kernels in the compiled decode")
    check(limit == 0 or need < limit,
          "update + KV pool fit the device's bytes_limit")


def serve_phase(engine, clock: CompileClock) -> None:
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch.serve import serve_requests

    args = argparse.Namespace(
        seed=SEED, requests=SERVE_REQUESTS, shared="on", rate=2.0,
        max_new=SERVE_MAX_NEW, mode="continuous", max_running=SERVE_REQUESTS,
        radix="on")
    (reqs, report), wall, comp, hits = run_timed(
        clock, lambda: serve_requests(args, engine, ByteTokenizer(),
                                      random.Random(SEED)))
    print(f"serve: wall_s={wall:.3f} compile_s={comp:.3f} "
          f"run_s={wall - comp:.3f} cache_hits={hits} "
          f"rounds={report.rounds} gen_tokens={report.gen_tokens} "
          f"model_tokens={report.model_tokens} "
          f"radix_hit_tokens={report.radix_hit_tokens} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    check(report.finished == SERVE_REQUESTS,
          f"{report.finished}/{SERVE_REQUESTS} requests finished")
    for r in reqs:
        check(r.finish_reason == "length"
              and len(r.out_tokens) == SERVE_MAX_NEW,
              f"request {r.rid}: {len(r.out_tokens)} tokens "
              f"({r.finish_reason})")


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    from repro.configs import get_config
    from repro.core.engine import TreeEngine
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"jax {jax.__version__}", flush=True)
    clock = CompileClock()
    cfg = dataclasses.replace(get_config("qwen3-4b"), num_layers=NUM_LAYERS)
    trainer, tree_cfg, engine_kwargs = rl_phase(cfg, clock)
    engine = TreeEngine(trainer.params, cfg, tree_cfg, seed=SEED,
                        **dict(engine_kwargs, max_queries=SERVE_REQUESTS))
    kernel_evidence(trainer, engine)
    serve_phase(engine, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

"""Compile rehearsal without the chip: the main-path Pallas kernels, and
the gradient path through them, compiled for a described TPU v5e at the
qwen3-4b widths and page size ``chip_smoke.py`` runs.  The TPU compiler
refuses here what interpret mode accepts (unaligned blocks, layouts that
Mosaic cannot tile), so these guard every later change at no chip time.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import fused_paged_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

CFG = dataclasses.replace(get_config("qwen3-4b"), num_layers=2)
HQ, HKV, D = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
PAGE = chip_smoke.SEGMENT
ROWS = chip_smoke.QUERIES * chip_smoke.WIDTH
B, S = 2, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args):
    """Compile ``fn`` for the described chip; the Pallas kernels left."""
    return chip_smoke.pallas_kernels(jax.jit(fn).lower(*args).compile())


@pytest.mark.parametrize("rows,d", [(B * S, CFG.d_model),
                                    (B * S * HQ, D), (5, CFG.d_model)])
def test_rmsnorm_compiles(one_chip, rows, d):
    got = _kernels(lambda x, s: rmsnorm_pallas(x, s),
                   _sds(one_chip, (rows, d)), _sds(one_chip, (d,)))
    assert got["rmsnorm_pallas"] == 1


@pytest.mark.parametrize("form", ["causal", "segment_ids", "q_offset_chunk",
                                  "bias", "ragged_bf16"])
def test_flash_attention_compiles(one_chip, form):
    sq, dtype, kw, extra = S, jnp.float32, {}, []
    if form == "segment_ids":
        extra = [_sds(one_chip, (B, S), jnp.int32)]
        kw = lambda seg: dict(segment_ids=seg)
    elif form == "q_offset_chunk":
        sq = S // 2
        extra = [_sds(one_chip, (B, S), jnp.int32)]
        kw = lambda seg: dict(segment_ids=seg, q_offset=S - sq)
    elif form == "bias":
        extra = [_sds(one_chip, (B, HQ, S, S))]
        kw = lambda b: dict(bias=b)
    elif form == "ragged_bf16":
        sq, dtype = 37, jnp.bfloat16
    skv = S if form in ("segment_ids", "q_offset_chunk", "bias") else sq
    args = [_sds(one_chip, (B, sq, HQ, D), dtype),
            _sds(one_chip, (B, skv, HKV, D), dtype),
            _sds(one_chip, (B, skv, HKV, D), dtype)] + extra

    def fn(q, k, v, *rest):
        return flash_attention_pallas(q, k, v, **(kw(*rest) if rest else {}))

    assert _kernels(fn, *args)["flash_attention_pallas"] == 1


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_fused_paged_attention_compiles(one_chip, depth):
    max_pages = -(-(chip_smoke.MAX_PROMPT + 3 * PAGE) // PAGE) + 1
    got = _kernels(
        lambda q, kv, t, n: fused_paged_attention_pallas(
            q, kv, t, n, page_size=PAGE, num_buffers=depth),
        _sds(one_chip, (ROWS, HQ, D)),
        _sds(one_chip, (8 * ROWS, PAGE, 2 * HKV, D)),
        _sds(one_chip, (ROWS, max_pages), jnp.int32),
        _sds(one_chip, (ROWS,), jnp.int32))
    assert got["fused_paged_attention_pallas"] == 1


def test_update_gradient_path_compiles(one_chip, monkeypatch):
    """The PPO update differentiates through ``ops``: on the chip that is
    the kernel forward plus the reference VJP (``pallas_call`` itself has
    no transpose rule).  Steer ``ops`` onto its TPU branch and compile a
    packed-attention + qk-norm loss and its gradient."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def loss(q, k, v, scale, seg):
        q = ops.rmsnorm(q, scale)
        o = ops.flash_attention(q, k, v, segment_ids=seg)
        return (o * o).sum()

    args = [_sds(one_chip, (B, S, HQ, D)), _sds(one_chip, (B, S, HKV, D)),
            _sds(one_chip, (B, S, HKV, D)), _sds(one_chip, (D,)),
            _sds(one_chip, (B, S), jnp.int32)]
    got = _kernels(jax.grad(loss, argnums=(0, 1, 2, 3)), *args)
    assert got["flash_attention_pallas"] == 1
    assert got["rmsnorm_pallas"] == 1

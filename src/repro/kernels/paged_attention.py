"""Paged (tree-decode) attention Pallas kernels.

The TPU adaptation of vLLM-style PagedAttention for TreePO's shared-prefix
tree: every search path holds a *block table* of page ids into a global KV
pool; branching copies the table, never the KV data.  GPU PagedAttention
gathers pages with per-warp loads; the TPU version resolves page
indirection with **scalar prefetch** — the block table is a scalar-prefetch
operand, read on the scalar core, so the gather costs no vector compute.

Two generations of the pattern live here:

* **Legacy split-pool kernels** (:func:`paged_attention_pallas`,
  :func:`mla_paged_attention_pallas`) — grid ``(B, max_pages)``, one page
  tile per grid step chosen by the BlockSpec ``index_map``.  The Pallas
  pipeline double-buffers grid-step inputs for free, but K and V live in
  separate pools so every page costs two serialized DMAs, and the grid is
  padded to ``max_pages`` (invalid steps are masked, not skipped).  Kept as
  the parity oracle behind ``fused_kv=False``.

* **Pipelined fused-pool kernels** (:func:`fused_paged_attention_pallas`,
  :func:`mla_fused_paged_attention_pallas`) — grid ``(B,)``, the pool stays
  HBM-resident (``ANY`` memory space) and the kernel issues its own
  multi-buffered ``pltpu.make_async_copy`` ring over ``num_buffers`` VMEM
  slots: the copy of page *i+1* is in flight while page *i* is scored.
  K/V are fused into one head-interleaved pool (``[K0,V0,K1,V1,...]``;
  MLA: ``[ckv|k_rope]`` feature-concat — ``repro.kv.layout``), so one DMA
  ships both halves of a page.  The per-path loop runs only over the
  ``ceil(lengths[b]/page)`` *valid* pages — padding rows (``lengths==0``)
  issue **zero** DMAs and emit zeros.  The page-visit order and the online
  softmax are independent of ``num_buffers``, so outputs are bitwise
  identical across buffer depths (only DMA timing changes).

Both generations guard the fully-masked-row case: when every position of a
row is masked (a padding row in the fixed-shape serve dispatch), the
masked probabilities are zeroed *before* accumulation, so ``l == 0`` and
the flush emits exact zeros — not the mean of page 0's stale contents
(which is what ``exp(s - m) == 1`` under an all ``-1e30`` score row used
to produce).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _paged_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float, page_size: int,
                  group: int, window: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32)                    # (Hq, D)
    k = k_ref[...].astype(jnp.float32)                  # (page, Hkv, D)
    v = v_ref[...].astype(jnp.float32)

    Hq, D = q.shape
    page, Hkv, _ = k.shape
    # (Hkv, group, D) x (page, Hkv, D) -> (Hkv, group, page)
    qg = q.reshape(Hkv, group, D)
    s = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale     # (Hkv, group, page)

    # table pages are consecutive per path, so `lengths` alone masks both
    # the tail of the last page and the -1 (clamped-to-0) padding pages.
    pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (Hkv, group, page), 2)
    valid = pos < lengths_ref[b]
    if window > 0:
        valid &= pos >= lengths_ref[b] - window
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...]                                 # (Hkv, group)
    m_cur = jnp.maximum(m_prev, s.max(axis=2))
    alpha = jnp.exp(m_prev - m_cur)
    # masked positions contribute exactly 0: on a fully-masked row m_cur
    # stays -1e30 and exp(s - m_cur) would be 1 everywhere, aggregating
    # page garbage into the flush
    p = jnp.where(valid, jnp.exp(s - m_cur[..., None]), 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=2)
    # (Hkv, group, page) x (page, Hkv, D) -> (Hkv, group, D)
    pv = jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_cur

    @pl.when(i == np_ - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = (acc_ref[...] / denom).reshape(Hq, D).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "window",
                                    "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, block_tables, lengths, *,
                           page_size: int, scale=None, window: int = 0,
                           interpret: bool = False):
    """q: (B, Hq, D); pools: (P, page, Hkv, D);
    block_tables: (B, max_pages) int32 (-1 pad); lengths: (B,)."""
    B, Hq, D = q.shape
    P, page, Hkv, _ = k_pool.shape
    assert page == page_size
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    max_pages = block_tables.shape[1]
    safe_tables = jnp.maximum(block_tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, i, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((None, page, Hkv, D),
                         lambda b, i, tbl, ln: (tbl[b, i], 0, 0, 0)),
            pl.BlockSpec((None, page, Hkv, D),
                         lambda b, i, tbl, ln: (tbl[b, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, i, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group), jnp.float32),
            pltpu.VMEM((Hkv, group), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, scale=float(scale),
                          page_size=page_size, group=group, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(safe_tables, lengths, q, k_pool, v_pool)


# ---------------------------------------------------------------------------
# MLA (absorbed-latent) paged decode — legacy split pools
# ---------------------------------------------------------------------------

def _mla_paged_kernel(tables_ref, lengths_ref, q_lat_ref, q_rope_ref,
                      ckv_ref, kr_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      scale: float, page_size: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    ql = q_lat_ref[0].astype(jnp.float32)               # (H, r)
    qr = q_rope_ref[0].astype(jnp.float32)              # (H, rd)
    ckv = ckv_ref[...].astype(jnp.float32)              # (page, r)
    kr = kr_ref[...].astype(jnp.float32)                # (page, rd)

    H, _ = ql.shape
    page = ckv.shape[0]
    # absorbed scores: q_lat.ckv^T + q_rope.k_rope^T -> (H, page)
    s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
         + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)) * scale

    # pages are consecutive per path, so `lengths` alone masks the tail of
    # the last valid page and every -1 (clamped-to-0) padding page.
    pos = i * page_size + jax.lax.broadcasted_iota(jnp.int32, (H, page), 1)
    valid = pos < lengths_ref[b]
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[...]                                 # (H, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    # zero the masked probabilities so a fully-masked (padding) row keeps
    # l == 0 and flushes to zeros instead of page-0 garbage
    p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)       # (H, page)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    # (H, page) x (page, r) -> (H, r) latent aggregate
    pv = jax.lax.dot_general(p, ckv, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_cur

    @pl.when(i == np_ - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "interpret"))
def mla_paged_attention_pallas(q_lat, q_rope, ckv_pool, kr_pool,
                               block_tables, lengths, *, page_size: int,
                               scale: float, interpret: bool = False):
    """Absorbed MLA tree-decode over latent pages.

    q_lat: (B, H, r) query pre-multiplied by W_uk (latent space);
    q_rope: (B, H, rd) decoupled-rope query; ckv_pool: (P, page, r);
    kr_pool: (P, page, rd); block_tables: (B, max_pages) int32 (-1 pad);
    lengths: (B,).  Returns the latent output (B, H, r) — the caller
    up-projects with W_uv and mixes with W_o.
    """
    B, H, r = q_lat.shape
    P, page, rd = kr_pool.shape
    assert page == page_size and ckv_pool.shape[:2] == (P, page)
    max_pages = block_tables.shape[1]
    safe_tables = jnp.maximum(block_tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, i, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, rd), lambda b, i, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((None, page, r),
                         lambda b, i, tbl, ln: (tbl[b, i], 0, 0)),
            pl.BlockSpec((None, page, rd),
                         lambda b, i, tbl, ln: (tbl[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda b, i, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, r), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_paged_kernel, scale=float(scale),
                          page_size=page_size),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(safe_tables, lengths, q_lat, q_rope, ckv_pool, kr_pool)


# ---------------------------------------------------------------------------
# Pipelined fused-pool kernels: manual multi-buffered page DMA
# ---------------------------------------------------------------------------
#
# Ring-buffer schedule over ``depth`` VMEM slots (slot = page_index % depth):
#
#   warm-up:       start pages 0 .. depth-2           (slots 0 .. depth-2)
#   iteration i:   start page  i+depth-1 -> slot (i+depth-1) % depth
#                  wait  page  i         at  slot  i % depth
#                  score page  i
#
# Page i+depth-1 lands in the slot consumed at iteration i-1 — never the
# slot iteration i is about to read — so compute on page i overlaps the
# copies of pages i+1 .. i+depth-1.  depth=1 degenerates to the serial
# start-then-wait schedule.  Every started page p < n_valid is waited at
# iteration p, so no DMA is left dangling when the loop exits — including
# the n_valid == 0 (padding-row) case, which starts nothing and returns
# the zero-initialized accumulator.


def _fused_paged_kernel(tables_ref, lengths_ref, q_ref, kv_ref, o_ref,
                        buf, sem, *, scale: float, page_size: int,
                        group: int, window: int, depth: int):
    b = pl.program_id(0)
    max_pages = tables_ref.shape[1]
    n_valid = jnp.minimum(
        (lengths_ref[b] + page_size - 1) // page_size, max_pages)

    def start(j):
        pltpu.make_async_copy(kv_ref.at[tables_ref[b, j]],
                              buf.at[j % depth], sem.at[j % depth]).start()

    def wait(j):
        pltpu.make_async_copy(kv_ref.at[tables_ref[b, j]],
                              buf.at[j % depth], sem.at[j % depth]).wait()

    def warm(j, carry):
        @pl.when(j < n_valid)
        def _():
            start(j)
        return carry
    jax.lax.fori_loop(0, depth - 1, warm, 0)

    q = q_ref[0].astype(jnp.float32)                    # (Hq, D)
    Hq, D = q.shape
    Hkv = kv_ref.shape[2] // 2
    qg = q.reshape(Hkv, group, D)

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        @pl.when(i + depth - 1 < n_valid)
        def _():
            start(i + depth - 1)
        wait(i)
        tile = buf[i % depth].astype(jnp.float32)       # (page, 2*Hkv, D)
        kv = tile.reshape(page_size, Hkv, 2, D)
        k = kv[:, :, 0, :]                              # (page, Hkv, D)
        v = kv[:, :, 1, :]
        # (Hkv, group, D) x (page, Hkv, D) -> (Hkv, group, page)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32) * scale
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (Hkv, group, page_size), 2)
        valid = pos < lengths_ref[b]
        if window > 0:
            valid &= pos >= lengths_ref[b] - window
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=2))
        alpha = jnp.exp(m_prev - m_cur)
        # masked positions contribute 0 even when the whole tile is masked
        # (m_cur still -1e30): no page-garbage aggregation
        p = jnp.where(valid, jnp.exp(s - m_cur[..., None]), 0.0)
        l_cur = l_prev * alpha + p.sum(axis=2)
        pv = jax.lax.dot_general(
            p, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        acc_cur = acc_prev * alpha[..., None] + pv
        return m_cur, l_cur, acc_cur

    m0 = jnp.full((Hkv, group), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, group), jnp.float32)
    acc0 = jnp.zeros((Hkv, group, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_valid, body, (m0, l0, acc0))
    denom = jnp.maximum(l, 1e-30)[..., None]
    o_ref[0] = (acc / denom).reshape(Hq, D).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "window",
                                    "num_buffers", "interpret"))
def fused_paged_attention_pallas(q, kv_pool, block_tables, lengths, *,
                                 page_size: int, scale=None,
                                 window: int = 0, num_buffers: int = 2,
                                 interpret: bool = False):
    """Pipelined tree-decode over a fused head-interleaved KV pool.

    q: (B, Hq, D); kv_pool: (P, page, 2*Hkv, D) with heads
    ``[K0,V0,K1,V1,...]`` (``repro.kv.layout.interleave_kv``);
    block_tables: (B, max_pages) int32 (-1 pad); lengths: (B,).
    ``num_buffers`` is the DMA ring depth (1 = serial copy/compute; 2/4 =
    double/quad buffering) — a pure scheduling knob, outputs are bitwise
    identical across depths.
    """
    B, Hq, D = q.shape
    P, page, Hkv2, _ = kv_pool.shape
    assert page == page_size and Hkv2 % 2 == 0
    Hkv = Hkv2 // 2
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    depth = max(1, int(num_buffers))
    safe_tables = jnp.maximum(block_tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, tbl, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, page, Hkv2, D), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_fused_paged_kernel, scale=float(scale),
                          page_size=page_size, group=group, window=window,
                          depth=depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # the manual DMA ring (buf/sem scratch) is shared state across
        # grid steps: the batch dim must not be megacore-parallelized
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(safe_tables, lengths, q, kv_pool)


def _mla_fused_paged_kernel(tables_ref, lengths_ref, q_lat_ref, q_rope_ref,
                            kv_ref, o_ref, buf, sem, *, scale: float,
                            page_size: int, rank: int, depth: int):
    b = pl.program_id(0)
    max_pages = tables_ref.shape[1]
    n_valid = jnp.minimum(
        (lengths_ref[b] + page_size - 1) // page_size, max_pages)

    def start(j):
        pltpu.make_async_copy(kv_ref.at[tables_ref[b, j]],
                              buf.at[j % depth], sem.at[j % depth]).start()

    def wait(j):
        pltpu.make_async_copy(kv_ref.at[tables_ref[b, j]],
                              buf.at[j % depth], sem.at[j % depth]).wait()

    def warm(j, carry):
        @pl.when(j < n_valid)
        def _():
            start(j)
        return carry
    jax.lax.fori_loop(0, depth - 1, warm, 0)

    ql = q_lat_ref[0].astype(jnp.float32)               # (H, r)
    qr = q_rope_ref[0].astype(jnp.float32)              # (H, rd)
    H, r = ql.shape

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        @pl.when(i + depth - 1 < n_valid)
        def _():
            start(i + depth - 1)
        wait(i)
        tile = buf[i % depth].astype(jnp.float32)       # (page, r + rd)
        ckv = tile[:, :rank]                            # (page, r)
        kr = tile[:, rank:]                             # (page, rd)
        s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
             ) * scale                                  # (H, page)
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (H, page_size), 1)
        valid = pos < lengths_ref[b]
        s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(valid, jnp.exp(s - m_cur), 0.0)   # (H, page)
        l_cur = l_prev * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot_general(p, ckv, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_cur = acc_prev * alpha + pv
        return m_cur, l_cur, acc_cur

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, r), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_valid, body, (m0, l0, acc0))
    denom = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("page_size", "scale", "num_buffers",
                                    "interpret"))
def mla_fused_paged_attention_pallas(q_lat, q_rope, kv_pool, block_tables,
                                     lengths, *, page_size: int,
                                     scale: float, num_buffers: int = 2,
                                     interpret: bool = False):
    """Pipelined absorbed-MLA tree-decode over a fused latent pool.

    q_lat: (B, H, r); q_rope: (B, H, rd); kv_pool: (P, page, r + rd) with
    ``[ckv | k_rope]`` on the feature axis (``repro.kv.layout.fuse_mla``);
    block_tables: (B, max_pages) int32 (-1 pad); lengths: (B,).  Returns
    the latent aggregate (B, H, r).  The rope split point is derived from
    the shapes: ``rd = kv_pool.shape[-1] - q_lat.shape[-1]``.
    """
    B, H, r = q_lat.shape
    P, page, feat = kv_pool.shape
    assert page == page_size and feat > r
    assert q_rope.shape == (B, H, feat - r)
    depth = max(1, int(num_buffers))
    safe_tables = jnp.maximum(block_tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, r), lambda b, tbl, ln: (b, 0, 0)),
            pl.BlockSpec((1, H, feat - r), lambda b, tbl, ln: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, r), lambda b, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((depth, page, feat), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_fused_paged_kernel, scale=float(scale),
                          page_size=page_size, rank=r, depth=depth),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(safe_tables, lengths, q_lat, q_rope, kv_pool)

"""Public kernel entry points.

Each op dispatches between the Pallas TPU kernel and the pure-jnp reference
depending on the backend.  On a TPU every op runs its Pallas kernel; on
the CPU the jnp reference runs, or the Pallas interpreter in tests
(``REPRO_PALLAS_INTERPRET=1``).  ``REPRO_FORCE_REF=1`` forces the
references on the CPU.  Both switches raise on a TPU backend, so a chip
run can never quietly measure the interpreter or the reference.

``pallas_call`` has no transpose rule, so reverse-mode autodiff cannot
pass through a kernel: the ops a training forward uses run the kernel
forward and take their gradient from the reference's VJP
(:func:`_with_ref_grad`).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _cpu_switch(name: str) -> bool:
    on = os.environ.get(name, "0") == "1"
    if on and _on_tpu():
        raise RuntimeError(
            f"{name}=1 is a CPU-only switch; on a TPU backend every op "
            "runs its compiled Pallas kernel")
    return on


def _interpret() -> bool:
    """REPRO_PALLAS_INTERPRET=1 routes ops through the Pallas interpreter on
    CPU — used by tests to exercise the real kernel bodies end-to-end."""
    return _cpu_switch("REPRO_PALLAS_INTERPRET")


def _use_pallas() -> bool:
    if _cpu_switch("REPRO_FORCE_REF"):
        return False
    return _on_tpu() or _interpret()


def _with_ref_grad(kernel, reference):
    """``kernel``'s forward with ``reference``'s gradient.  The backward
    recomputes the reference from the saved inputs and applies its VJP;
    integer inputs (segment ids) get the usual float0 cotangents.

    Callers pass lambdas that *call* both functions: ``tools/analyze``
    R4 pairs a dispatch's kernel and reference by their call sites."""
    @jax.custom_vjp
    def f(*args):
        return kernel(*args)

    def fwd(*args):
        return kernel(*args), args

    def bwd(args, g):
        return jax.vjp(reference, *args)[1](g)

    f.defvjp(fwd, bwd)
    return f


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps: float = 1e-6):
    from repro.kernels.ref import rmsnorm_ref

    if _use_pallas():
        from repro.kernels.rmsnorm import rmsnorm_pallas

        return _with_ref_grad(
            lambda x, scale: rmsnorm_pallas(x, scale, eps=eps,
                                            interpret=_interpret()),
            lambda x, scale: rmsnorm_ref(x, scale, eps=eps))(x, scale)
    return rmsnorm_ref(x, scale, eps=eps)


# ---------------------------------------------------------------------------
# flash attention (prefill / train)
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int = 0,
                    segment_ids=None, bias=None):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D). Returns (B, Sq, Hq, D).

    ``window``: sliding-window size (0 = full). ``q_offset``: absolute
    position of q[0] relative to k[0] (for chunked prefill).
    ``segment_ids``: optional (B, Skv) int32 per-token segment labels
    over the key axis for sequence-packed rows — attention is restricted
    to same-segment pairs.  With Sq < Skv (chunked prefill) the q chunk's
    labels are the slice at ``q_offset``; kv labels equal to
    ``SHARED_SEGMENT_ID`` (a per-row modality prefix) are visible to all.
    ``bias``: optional additive attention bias broadcastable to
    (B, Hq, Sq, Skv), added to the masked logits (ALiBi, relative
    position, soft prompt masks); supported by both backends.
    """
    from repro.kernels.ref import attention_ref

    static = dict(causal=causal, window=window, scale=scale,
                  q_offset=q_offset)
    # the Pallas kernel tiles one head dim for q/k/v; MLA prefill attends
    # with qk_head_dim != v_head_dim, which only the reference supports.
    if _use_pallas() and q.shape[-1] == v.shape[-1]:
        from repro.kernels.flash_attention import flash_attention_pallas

        def kernel(q, k, v, segment_ids, bias):
            return flash_attention_pallas(
                q, k, v, segment_ids=segment_ids, bias=bias,
                interpret=_interpret(), **static)

        def reference(q, k, v, segment_ids, bias):
            return attention_ref(q, k, v, segment_ids=segment_ids,
                                 bias=bias, **static)

        return _with_ref_grad(kernel, reference)(q, k, v, segment_ids, bias)
    return attention_ref(q, k, v, segment_ids=segment_ids, bias=bias,
                         **static)


# ---------------------------------------------------------------------------
# dense-cache decode attention
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, lengths, *, window: int = 0,
                     scale: float | None = None):
    """Single-token decode. q: (B, Hq, D); caches: (B, S, Hkv, D);
    lengths: (B,) valid cache lengths (the new token is at lengths-1)."""
    from repro.kernels.ref import decode_attention_ref

    return decode_attention_ref(q, k_cache, v_cache, lengths, window=window,
                                scale=scale)


# ---------------------------------------------------------------------------
# paged (tree) decode attention
# ---------------------------------------------------------------------------

def paged_attention(q, k_pool, v_pool, block_tables, lengths, *,
                    page_size: int, scale: float | None = None,
                    window: int = 0):
    """Tree-decode attention over a shared paged KV pool.

    q: (B, Hq, D); pools: (num_pages, page, Hkv, D);
    block_tables: (B, max_pages) int32 page ids (-1 pad);
    lengths: (B,) total valid tokens per path.
    ``window`` > 0: sliding-window layers attend the last `window` keys.
    """
    if _use_pallas():
        from repro.kernels.paged_attention import paged_attention_pallas

        return paged_attention_pallas(q, k_pool, v_pool, block_tables,
                                      lengths, page_size=page_size,
                                      scale=scale, window=window,
                                      interpret=_interpret())
    from repro.kernels.ref import paged_attention_ref

    return paged_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               page_size=page_size, scale=scale,
                               window=window)


def fused_paged_attention(q, kv_pool, block_tables, lengths, *,
                          page_size: int, scale: float | None = None,
                          window: int = 0, num_buffers: int = 2):
    """Pipelined tree-decode over a fused head-interleaved KV pool.

    q: (B, Hq, D); kv_pool: (num_pages, page, 2*Hkv, D) with heads
    ``[K0,V0,K1,V1,...]`` (``repro.kv.layout.interleave_kv``);
    block_tables: (B, max_pages) int32 page ids (-1 pad); lengths: (B,).
    ``num_buffers``: DMA ring depth (Pallas-only scheduling knob — the
    kernel overlaps the copy of page i+1 with the scoring of page i;
    outputs are bitwise identical across depths).
    """
    if _use_pallas():
        from repro.kernels.paged_attention import fused_paged_attention_pallas

        return fused_paged_attention_pallas(q, kv_pool, block_tables,
                                            lengths, page_size=page_size,
                                            scale=scale, window=window,
                                            num_buffers=num_buffers,
                                            interpret=_interpret())
    from repro.kernels.ref import fused_paged_attention_ref

    return fused_paged_attention_ref(q, kv_pool, block_tables, lengths,
                                     page_size=page_size, scale=scale,
                                     window=window)


# ---------------------------------------------------------------------------
# MLA (absorbed-latent) paged decode attention
# ---------------------------------------------------------------------------

def mla_paged_attention(q_lat, q_rope, ckv_pool, kr_pool, block_tables,
                        lengths, *, page_size: int, scale: float):
    """Absorbed DeepSeek-MLA tree-decode over a shared latent page pool.

    q_lat: (B, H, r) query pre-multiplied by W_uk; q_rope: (B, H, rd);
    ckv_pool: (num_pages, page, r); kr_pool: (num_pages, page, rd);
    block_tables: (B, max_pages) int32 page ids (-1 pad); lengths: (B,).
    Returns the latent aggregate (B, H, r) — W_uv/W_o applied by the caller.
    """
    if _use_pallas():
        from repro.kernels.paged_attention import mla_paged_attention_pallas

        return mla_paged_attention_pallas(q_lat, q_rope, ckv_pool, kr_pool,
                                          block_tables, lengths,
                                          page_size=page_size, scale=scale,
                                          interpret=_interpret())
    from repro.kernels.ref import mla_paged_attention_ref

    return mla_paged_attention_ref(q_lat, q_rope, ckv_pool, kr_pool,
                                   block_tables, lengths,
                                   page_size=page_size, scale=scale)


def mla_fused_paged_attention(q_lat, q_rope, kv_pool, block_tables,
                              lengths, *, page_size: int, scale: float,
                              num_buffers: int = 2):
    """Pipelined absorbed-MLA tree-decode over a fused latent pool.

    q_lat: (B, H, r) query pre-multiplied by W_uk; q_rope: (B, H, rd);
    kv_pool: (num_pages, page, r + rd) with ``[ckv | k_rope]`` on the
    feature axis (``repro.kv.layout.fuse_mla``); block_tables:
    (B, max_pages) int32 page ids (-1 pad); lengths: (B,).  Returns the
    latent aggregate (B, H, r).  ``num_buffers``: DMA ring depth
    (Pallas-only scheduling knob; bitwise-invariant).
    """
    if _use_pallas():
        from repro.kernels.paged_attention import (
            mla_fused_paged_attention_pallas)

        return mla_fused_paged_attention_pallas(
            q_lat, q_rope, kv_pool, block_tables, lengths,
            page_size=page_size, scale=scale, num_buffers=num_buffers,
            interpret=_interpret())
    from repro.kernels.ref import mla_fused_paged_attention_ref

    return mla_fused_paged_attention_ref(q_lat, q_rope, kv_pool,
                                         block_tables, lengths,
                                         page_size=page_size, scale=scale)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

def mamba_scan(u, dt, B_, C_, A, D, h0, segment_ids=None):
    """Selective scan: u,dt (B,T,d_in); B_,C_ (B,T,N); A (d_in,N); D
    (d_in,); h0 (B,d_in,N) -> (y, h_final).  Pallas keeps the state in
    VMEM across the time loop (vs. an HBM round-trip per step in the XLA
    scan lowering — §Perf).

    ``segment_ids``: optional (B, T) packed-row labels — the carried
    state is zeroed at every segment start, so packed segments scan
    exactly as they would in their own rows."""
    from repro.kernels.ref import mamba_scan_ref

    if _use_pallas():
        from repro.kernels.mamba_scan import mamba_scan_pallas

        return _with_ref_grad(
            lambda *a: mamba_scan_pallas(*a, interpret=_interpret()),
            lambda *a: mamba_scan_ref(*a))(u, dt, B_, C_, A, D, h0,
                                           segment_ids)
    return mamba_scan_ref(u, dt, B_, C_, A, D, h0,
                          segment_ids=segment_ids)


# ---------------------------------------------------------------------------
# rwkv6 wkv recurrence
# ---------------------------------------------------------------------------

def wkv6(r, k, v, w, u, state, segment_ids=None):
    """RWKV6 time-mix recurrence.

    r,k,v: (B, T, H, D); w: (B, T, H, D) decay in (0,1); u: (H, D) bonus;
    state: (B, H, D, D). Returns (out (B,T,H,D), new_state).

    ``segment_ids``: optional (B, T) packed-row labels — the carried
    state is zeroed at every segment start (no cross-segment wkv leak)."""
    from repro.kernels.ref import wkv6_ref

    if _use_pallas():
        from repro.kernels.wkv6 import wkv6_pallas

        return _with_ref_grad(
            lambda *a: wkv6_pallas(*a, interpret=_interpret()),
            lambda *a: wkv6_ref(*a))(r, k, v, w, u, state, segment_ids)
    return wkv6_ref(r, k, v, w, u, state, segment_ids=segment_ids)

"""Flash attention (prefill / train) Pallas kernel.

TPU mapping: grid ``(B, Hq, num_q_blocks, num_kv_blocks)`` with the kv-block
axis innermost; a ``(blk_q, D)`` query tile stays resident in VMEM while
``(blk_k, D)`` key/value tiles stream through, maintaining the online-softmax
running max/denominator in f32 VMEM scratch.  Q/K tiles are MXU-shaped
(blk_q, blk_k multiples of 128 when the sequence allows).  GQA is handled in
the index map: the kv-head coordinate is ``q_head // group`` — no
materialized head repetition (saves Hq/Hkv × KV bandwidth).

Causal masking, sliding windows and the chunked-prefill ``q_offset`` are all
position masks computed from grid coordinates (no mask tensors in HBM).
Sequence-packed rows add one more mask term: per-token ``segment_ids``
(B, Skv) int32 over the key axis stream in alongside q and k (q labels
as a lane-broadcast (blk_q, 128) tile, kv labels as a sublane-broadcast
(8, blk_k) tile), and the score mask requires ``seg[q] == seg[kv]`` —
packed segments never attend across their boundary, at the cost of two
int32 tiles (no (S, S) mask in HBM).  q/k/v/o are passed head-major
(B, H, S, D) so every block is a (rows, D) tile of one head.  The q chunk's labels are the kv labels sliced at
``q_offset`` (chunked prefill packs too), and kv labels equal to
``SHARED_SEGMENT_ID`` (-2; a per-row modality prefix) are attendable by
every query.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import SHARED_SEGMENT_ID

_NEG_INF = -1e30
# Mosaic's native (sublane, lane) tile
_SUBLANES, _LANES = 8, 128


def _flash_kernel(q_ref, k_ref, v_ref, *refs,
                  scale: float, causal: bool, window: int, q_offset: int,
                  blk_q: int, blk_k: int, sq: int, skv: int,
                  has_seg: bool, has_bias: bool):
    refs = list(refs)
    if has_seg:
        qseg_ref, kseg_ref = refs[:2]
        refs = refs[2:]
    if has_bias:
        bias_ref = refs[0]
        refs = refs[1:]
    o_ref, acc_ref, m_ref, l_ref = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32)                  # (blk_q, D)
    k = k_ref[...].astype(jnp.float32)                  # (blk_k, D)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) \
        + q_offset
    kpos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = kpos < skv                                   # kv padding
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    if has_seg:
        qseg = qseg_ref[:, :1]                          # (blk_q, 1)
        kseg = kseg_ref[:1, :]                          # (1, blk_k)
        mask &= (qseg == kseg) | (kseg == SHARED_SEGMENT_ID)
    s = jnp.where(mask, s, _NEG_INF)
    if has_bias:
        # same order as attention_ref: bias lands on the already-masked
        # logits, so a masked score stays ~-1e30 for any finite bias
        s = s + bias_ref[...].astype(jnp.float32)

    m_prev = m_ref[...]                                 # (blk_q, 1)
    m_cur = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_offset", "blk_q",
                     "blk_k", "interpret"),
)
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale=None, q_offset: int = 0, blk_q: int = 128,
                           blk_k: int = 128, interpret: bool = False,
                           segment_ids=None, bias=None):
    """q: (B, Sq, Hq, D); k/v: (B, Skv, Hkv, D) -> (B, Sq, Hq, D).

    ``segment_ids``: optional (B, Skv) int32 labels over the key axis:
    restrict attention to same-segment pairs (sequence-packed rows).
    When Sq < Skv (chunked prefill) the q chunk's labels are the slice at
    ``q_offset``; ``SHARED_SEGMENT_ID`` kv tokens are visible to all.

    ``bias``: optional additive attention bias broadcastable to
    (B, Hq, Sq, Skv) (ALiBi slopes, relative-position buckets, soft
    prompt masks); added to the masked logits exactly as in
    ``attention_ref``, streamed as (blk_q, blk_k) tiles."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    has_seg = segment_ids is not None
    has_bias = bias is not None
    if has_seg and (segment_ids.shape[1] != Skv or q_offset + Sq > Skv):
        raise ValueError("segment_ids labels the kv axis (B, Skv); the q "
                         "chunk is its slice at q_offset")

    blk_q = min(blk_q, max(Sq, 1))
    blk_k = min(blk_k, max(Skv, 1))
    pad_q = (-Sq) % blk_q
    pad_k = (-Skv) % blk_k
    # head-major operands: every block is a (rows, D) tile of one head,
    # the layout Mosaic tiles natively on its last two dims
    qh = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kh = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vh = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    nq = qh.shape[2] // blk_q
    nk = kh.shape[2] // blk_k

    in_specs = [
        pl.BlockSpec((None, None, blk_q, D), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((None, None, blk_k, D),
                     lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((None, None, blk_k, D),
                     lambda b, h, i, j: (b, h // group, j, 0)),
    ]
    inputs = [qh, kh, vh]
    if has_seg:
        # -1 on the kv pad tail can never equal a real q segment id of a
        # surviving (un-sliced) row; the kpos < skv term masks it anyway.
        # q labels run down the sublanes and kv labels along the lanes,
        # each broadcast to a full (8, 128) tile so the kernel reads a
        # (blk_q, 1) column and a (1, blk_k) row without a relayout.
        seg = segment_ids.astype(jnp.int32)
        qseg = jnp.pad(seg[:, q_offset: q_offset + Sq],
                       ((0, 0), (0, pad_q)), constant_values=-1)
        kseg = jnp.pad(seg, ((0, 0), (0, pad_k)), constant_values=-1)
        qseg = jnp.broadcast_to(qseg[:, :, None], qseg.shape + (_LANES,))
        kseg = jnp.broadcast_to(kseg[:, None, :],
                                (B, _SUBLANES, kseg.shape[1]))
        in_specs += [
            pl.BlockSpec((None, blk_q, _LANES), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((None, _SUBLANES, blk_k),
                         lambda b, h, i, j: (b, 0, j)),
        ]
        inputs += [qseg, kseg]
    if has_bias:
        bias_full = jnp.broadcast_to(jnp.asarray(bias, jnp.float32),
                                     (B, Hq, Sq, Skv))
        # zero on the pad tail: padded scores are already masked to
        # _NEG_INF, the bias must not resurrect them
        bias_full = jnp.pad(bias_full,
                            ((0, 0), (0, 0), (0, pad_q), (0, pad_k)))
        in_specs += [
            pl.BlockSpec((None, None, blk_q, blk_k),
                         lambda b, h, i, j: (b, h, i, j)),
        ]
        inputs += [bias_full]

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=float(scale), causal=causal, window=window,
            q_offset=q_offset, blk_q=blk_q, blk_k=blk_k, sq=Sq, skv=Skv,
            has_seg=has_seg, has_bias=has_bias),
        grid=(B, Hq, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, blk_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, D), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
            pltpu.VMEM((blk_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
    )(*inputs)
    return out.transpose(0, 2, 1, 3)[:, :Sq]

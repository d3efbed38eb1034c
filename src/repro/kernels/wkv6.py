"""RWKV-6 (Finch) wkv recurrence Pallas kernel.

The recurrence  S_t = diag(w_t) S_{t-1} + k_t v_t^T ,
               o_t = r_t (S_{t-1} + diag(u) k_t v_t^T)
is sequential in t but embarrassingly parallel over (batch, head).  TPU
mapping: grid ``(B, H)``; each program keeps its (D, D) state matrix
resident in f32 VMEM and walks the time axis with on-chip rank-1 updates —
the state never round-trips to HBM between tokens (on GPU this is the shared
-memory variant; on TPU VMEM plays that role).  D=64 keeps the (D, D) tile
lane-aligned.  All math f32 for the decay products.

Sequence-packed rows pass per-token ``segment_ids`` (B, T): the (D, D)
state is zeroed at every packed-segment start (derived reset mask, one
(1, T) int32 tile per program), so no wkv state leaks across a packing
boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import segment_reset_mask


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, *refs,
                 T: int, has_reset: bool):
    if has_reset:
        reset_ref, o_ref, sT_ref, state_ref = refs
    else:
        reset_ref, (o_ref, sT_ref, state_ref) = None, refs
    state_ref[...] = s0_ref[0, 0].astype(jnp.float32)   # (D, D)
    u = u_ref[0].astype(jnp.float32)                    # (D,)

    def step(t, _):
        rt = r_ref[0, t, 0, :].astype(jnp.float32)      # (D,)
        kt = k_ref[0, t, 0, :].astype(jnp.float32)
        vt = v_ref[0, t, 0, :].astype(jnp.float32)
        wt = w_ref[0, t, 0, :].astype(jnp.float32)
        s = state_ref[...]
        if has_reset:
            # packed-segment start: drop the previous segment's state
            s = s * (1.0 - reset_ref[0, t].astype(jnp.float32))
        kv = kt[:, None] * vt[None, :]                  # (D, D) rank-1
        out = ((s + u[:, None] * kv) * rt[:, None]).sum(axis=0)  # (D,)
        o_ref[0, t, 0, :] = out.astype(o_ref.dtype)
        state_ref[...] = wt[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, T, step, 0)
    sT_ref[0, 0] = state_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_pallas(r, k, v, w, u, state, segment_ids=None, *,
                interpret: bool = False):
    """r,k,v,w: (B, T, H, D); u: (H, D); state: (B, H, D, D) [key-dim first].

    ``segment_ids``: optional (B, T) int32 packed-row labels — the VMEM
    state matrix is zeroed whenever the label changes from the previous
    token (``state`` still seeds the row's first token).

    Returns (out (B, T, H, D), final state (B, H, D, D))."""
    B, T, H, D = r.shape
    has_reset = segment_ids is not None
    kernel = functools.partial(_wkv6_kernel, T=T, has_reset=has_reset)
    seq_spec = pl.BlockSpec((1, T, 1, D), lambda b, h: (b, 0, h, 0))
    in_specs = [
        seq_spec, seq_spec, seq_spec, seq_spec,
        pl.BlockSpec((1, D), lambda b, h: (h, 0)),
        pl.BlockSpec((1, 1, D, D), lambda b, h: (b, h, 0, 0)),
    ]
    inputs = [r, k, v, w, u, state]
    if has_reset:
        inputs.append(segment_reset_mask(segment_ids))
        in_specs.append(pl.BlockSpec((1, T), lambda b, h: (b, 0)))
    out, s_final = pl.pallas_call(
        kernel,
        grid=(B, H),
        in_specs=in_specs,
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, D, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, D), r.dtype),
            jax.ShapeDtypeStruct((B, H, D, D), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(*inputs)
    return out, s_final

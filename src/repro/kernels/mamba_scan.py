"""Mamba selective-scan Pallas kernel.

The XLA lowering of the recurrence round-trips the (d_in, N) state through
HBM on every timestep (lax.scan carry), which §Perf iteration 3 measured as
the dominant memory term of the jamba prefill.  TPU mapping: grid over
(batch, d_in blocks); each program keeps its (blk_d, N) state slice
resident in f32 VMEM for the whole time loop — the state never touches HBM
between tokens.  Inputs stream through VMEM tiles; y writes stream out.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) B_t
    y_t = h_t . C_t + D * u_t

Sequence-packed rows pass per-token ``segment_ids`` (B, T): the carried
state is zeroed at every packed-segment start (derived reset mask, one
(1, T) int32 tile per program), so a segment scans exactly as it would
in its own row — recurrent state never leaks across a packing boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import segment_reset_mask


def _mamba_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                  *refs, T: int, has_reset: bool):
    if has_reset:
        reset_ref, y_ref, hT_ref, state_ref = refs
    else:
        reset_ref, (y_ref, hT_ref, state_ref) = None, refs
    state_ref[...] = h0_ref[0].astype(jnp.float32)      # (blk_d, N)
    A = a_ref[...].astype(jnp.float32)                  # (blk_d, N)
    D = d_ref[...].astype(jnp.float32)                  # (blk_d,)

    def step(t, _):
        u_t = u_ref[0, t, :].astype(jnp.float32)        # (blk_d,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)
        b_t = b_ref[0, t, :].astype(jnp.float32)        # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)
        h = state_ref[...]
        if has_reset:
            # packed-segment start: the carried state belongs to the
            # previous segment — zero it before this token consumes it
            h = h * (1.0 - reset_ref[0, t].astype(jnp.float32))
        dA = jnp.exp(dt_t[:, None] * A)
        h = dA * h + (dt_t * u_t)[:, None] * b_t[None, :]
        state_ref[...] = h
        y_ref[0, t, :] = ((h * c_t[None, :]).sum(axis=1)
                          + D * u_t).astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, T, step, 0)
    hT_ref[0] = state_ref[...].astype(hT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_d", "interpret"))
def mamba_scan_pallas(u, dt, B_, C_, A, D, h0, segment_ids=None, *,
                      blk_d: int = 512, interpret: bool = False):
    """u, dt: (B, T, d_in); B_, C_: (B, T, N); A: (d_in, N); D: (d_in,);
    h0: (B, d_in, N).  Returns (y (B, T, d_in), h_final (B, d_in, N)).

    ``segment_ids``: optional (B, T) int32 packed-row labels — the VMEM
    state is zeroed whenever the label changes from the previous token
    (h0 still seeds the row's first token: carried state from a previous
    chunk belongs to the same stream)."""
    B, T, d_in = u.shape
    N = B_.shape[-1]
    blk_d = min(blk_d, d_in)
    assert d_in % blk_d == 0
    nd = d_in // blk_d
    in_specs = [
        pl.BlockSpec((1, T, blk_d), lambda b, i: (b, 0, i)),   # u
        pl.BlockSpec((1, T, blk_d), lambda b, i: (b, 0, i)),   # dt
        pl.BlockSpec((1, T, N), lambda b, i: (b, 0, 0)),       # B
        pl.BlockSpec((1, T, N), lambda b, i: (b, 0, 0)),       # C
        pl.BlockSpec((blk_d, N), lambda b, i: (i, 0)),         # A
        pl.BlockSpec((blk_d,), lambda b, i: (i,)),             # D
        pl.BlockSpec((1, blk_d, N), lambda b, i: (b, i, 0)),   # h0
    ]
    inputs = [u, dt, B_, C_, A, D, h0]
    has_reset = segment_ids is not None
    if has_reset:
        inputs.append(segment_reset_mask(segment_ids))
        in_specs.append(pl.BlockSpec((1, T), lambda b, i: (b, 0)))
    y, hT = pl.pallas_call(
        functools.partial(_mamba_kernel, T=T, has_reset=has_reset),
        grid=(B, nd),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, T, blk_d), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, blk_d, N), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, d_in), u.dtype),
            jax.ShapeDtypeStruct((B, d_in, N), h0.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((blk_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(*inputs)
    return y, hT

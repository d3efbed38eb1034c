"""Plain reference of the dense GQA decoder the configurations describe,
written from the published architecture (Qwen2/Qwen3 family): token
embedding, pre-norm RMSNorm blocks, GQA attention with rotary positions
(rotate-half convention) and optional per-head RMSNorm on queries and keys
(Qwen3), SwiGLU feed-forward, final RMSNorm, tied or untied output head.

It imports nothing of the program.  In float32 every matrix product runs at
``Precision.HIGHEST``.  The control, ``"int8"``, is the step below the
bfloat16 the configurations state: every linear layer (and the output
head) takes int8 weights (symmetric, one scale per output column) and int8
activations (one scale per row), the rest as in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
INT8 = "int8"
HIGHEST = jax.lax.Precision.HIGHEST


def _fake_int8(x, axis):
    """x rounded to a symmetric int8 grid, one scale per slice along
    ``axis`` (straight-through gradient)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def _linear(dtype):
    """x @ w for the model's linear layers at ``dtype``."""
    if dtype == INT8:
        return lambda x, w: jnp.matmul(_fake_int8(x, -1), _fake_int8(w, 0),
                                       precision=HIGHEST)
    return functools.partial(jnp.matmul, precision=HIGHEST)


def _rms(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = pos[..., None].astype(F32) * jnp.asarray(inv, F32)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def forward(params, p: dict, tokens, dtype=F32):
    """tokens (B, S) int -> logits (B, S, V) float32; every row is one
    sequence starting at position 0, attention causal."""
    mm = _linear(dtype)
    B, S = tokens.shape
    H, Hkv, hd = p["num_heads"], p["num_kv_heads"], p["head_dim"]
    eps = p["norm_eps"]
    pos = jnp.arange(S)[None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = params["embed"]["embedding"][tokens]
    for lp in params["layers"]:
        a = lp["attn"]
        h = _rms(x, lp["norm1"]["scale"], eps)
        q = mm(h, a["w_q"]).reshape(B, S, H, hd)
        k = mm(h, a["w_k"]).reshape(B, S, Hkv, hd)
        v = mm(h, a["w_v"]).reshape(B, S, Hkv, hd)
        if p["qk_norm"]:
            q = _rms(q, a["q_norm"]["scale"], eps)
            k = _rms(k, a["k_norm"]["scale"], eps)
        q, k = _rope(q, pos, p["rope_theta"]), _rope(k, pos, p["rope_theta"])
        qg = q.reshape(B, S, Hkv, H // Hkv, hd)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, precision=HIGHEST,
                       preferred_element_type=F32) / np.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", w, v, precision=HIGHEST)
        x = x + mm(o.reshape(B, S, H * hd), a["w_o"])
        h = _rms(x, lp["norm2"]["scale"], eps)
        f = lp["ffn"]
        g = mm(h, f["w_gate"])
        x = x + mm(jax.nn.silu(g) * mm(h, f["w_up"]), f["w_down"])
    x = _rms(x, params["norm_f"]["scale"], eps)
    e = params["embed"]
    head = e["embedding"].T if p["tie_embeddings"] else e["lm_head"]
    return mm(x, head).astype(F32)


def token_logprobs(params, p: dict, tokens, dtype=F32):
    """(B, S) -> (B, S-1) log-probability of token t+1 after 0..t."""
    logits = forward(params, p, tokens, dtype)
    lsm = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return jnp.take_along_axis(lsm, tokens[:, 1:, None], axis=-1)[..., 0]

"""Random weights from the run's seed, made on the device in one jitted
call, in the parameter layout the program's model takes (a plain nested
dict of float32 arrays, the same layout a checkpoint would carry).

The reference builds its weights with this same function from the same
seed; neither side takes weights the other made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _shapes(p: dict):
    d, hd, V, f = p["d_model"], p["head_dim"], p["vocab_size"], p["d_ff"]
    hq, hkv = p["num_heads"], p["num_kv_heads"]
    layer = {
        "norm1": {"scale": ("norm", (d,))},
        "attn": {"w_q": ("dense", (d, hq * hd)),
                 "w_k": ("dense", (d, hkv * hd)),
                 "w_v": ("dense", (d, hkv * hd)),
                 "w_o": ("dense", (hq * hd, d))},
        "norm2": {"scale": ("norm", (d,))},
        "ffn": {"w_gate": ("dense", (d, f)), "w_up": ("dense", (d, f)),
                "w_down": ("dense", (f, d))},
    }
    if p["qk_norm"]:
        layer["attn"]["q_norm"] = {"scale": ("norm", (hd,))}
        layer["attn"]["k_norm"] = {"scale": ("norm", (hd,))}
    embed = {"embedding": ("embed", (V, d))}
    if not p["tie_embeddings"]:
        embed["lm_head"] = ("dense", (d, V))
    return {"embed": embed, "norm_f": {"scale": ("norm", (d,))},
            "layers": tuple(layer for _ in range(p["num_layers"]))}


def _leaf(key, kind, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "dense":       # unit-variance activations through a layer
        w = z * (1.0 / shape[0] ** 0.5)
    elif kind == "embed":
        w = z * 0.02
    else:                     # norm scales near 1, not all equal
        w = 1.0 + 0.1 * z
    return w.astype(dtype)


def make_params(p: dict, seed: int, dtype=jnp.float32):
    """The whole parameter tree for sizes ``p`` (``model_config.plain``)
    from ``seed``, in one jitted call on the default device."""
    return _make(tuple(sorted(p.items())), dtype)(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _make(p_items, dtype):
    spec = _shapes(dict(p_items))
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], str))

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(
            treedef, [_leaf(k, kind, shape, dtype)
                      for k, (kind, shape) in zip(keys, leaves)])

    return jax.jit(build)


def seed_key(seed: int):
    """A PRNG key from any whole number, all its bits used (seeds may
    exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    return jnp.array([seed >> 32, seed & 0xFFFFFFFF], dtype=jnp.uint32)

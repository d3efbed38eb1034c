"""Comparisons that decide ``correct``: the readings of a run against the
plain reference, and the arithmetic shared by the drivers.  Nothing here
imports the program."""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def token_reward(seed: int, tokens: Sequence[int]) -> float:
    """The RL cell's verifiable reward: a seeded hash of the trajectory's
    tokens, 1 or 0 with even odds, so every group mixes both."""
    h = zlib.crc32(np.asarray(tokens, np.int64).tobytes(),
                   zlib.crc32(str(int(seed)).encode()))
    return float((h >> 7) & 1)


@jax.jit
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


@jax.jit
def leaf_diff_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray = None) -> float:
    """The largest |got - ref| over leaves, each against the larger of the
    leaf's reference norm and the median leaf's."""
    keep = np.ones(len(ref), bool) if keep is None else keep
    floor = float(np.median(ref[keep]))
    gaps = np.abs(got - ref) / np.maximum(ref, floor)
    return float(np.max(np.where(keep, gaps, 0.0)))


def tree_advantages(rows: List[dict], rewards: np.ndarray, seg_len: int,
                    max_depth: int, eps: float = 1e-6) -> np.ndarray:
    """TreePO's tree advantage (Eq. 5): for each trajectory, the mean over
    depths j = 0..max_depth of (R - mean R of the subgroup sharing its
    depth-j ancestor), over the std of those terms.  The subgroup at depth
    j is every trajectory of the query with the same first j segments of
    tokens; below its own last segment a trajectory is alone."""
    adv = np.zeros(len(rows))
    J = max_depth + 1
    queries: Dict[int, List[int]] = {}
    for i, r in enumerate(rows):
        queries.setdefault(r["query"], []).append(i)
    for idx in queries.values():
        keys = []
        for i in idx:
            toks = rows[i]["tokens"]
            d = -(-len(toks) // seg_len)
            keys.append([("root",) if j == 0 else
                         ("node", tuple(toks[:min(j * seg_len, len(toks))]))
                         if j <= d else ("leaf", i) for j in range(J)])
        R = rewards[idx].astype(np.float64)
        terms = np.zeros((len(idx), J))
        for j in range(J):
            col = [k[j] for k in keys]
            for a, ka in enumerate(col):
                same = [b for b, kb in enumerate(col) if kb == ka]
                terms[a, j] = R[a] - R[same].mean()
        adv[idx] = terms.mean(axis=1) / (terms.std(axis=1) + eps)
    return adv


def global_norm_scale(adv: np.ndarray, lengths: np.ndarray,
                      eps: float = 1e-6) -> float:
    """REINFORCE++ normalization over every response token of the batch:
    the factor 1/sqrt(var + eps) (the mean is not subtracted)."""
    tok = np.repeat(adv, lengths)
    return 1.0 / math.sqrt(float(tok.var()) + eps)

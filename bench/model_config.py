"""A configuration file of ``bench/configs/`` as the program and the
reference each take it.

The file holds the model's published ``config.json`` keys as run (with the
cut ones changed and the published values under ``published``), plus what
the program needs to pick its code path (``program``).  The program gets a
``repro`` ``ModelConfig`` built from those keys; the reference gets the
plain numbers.
"""
from __future__ import annotations

import dataclasses
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        raw = json.load(f)
    raw["name"] = name
    return raw


def plain(raw: dict) -> dict:
    """The sizes the reference and the FLOP/byte counts read."""
    d, h = raw["hidden_size"], raw["num_attention_heads"]
    return {
        "d_model": d,
        "num_heads": h,
        "num_kv_heads": raw["num_key_value_heads"],
        "head_dim": raw.get("head_dim") or d // h,
        "d_ff": raw["intermediate_size"],
        "vocab_size": raw["vocab_size"],
        "num_layers": raw["num_hidden_layers"],
        "rope_theta": float(raw["rope_theta"]),
        "norm_eps": float(raw["rms_norm_eps"]),
        "tie_embeddings": bool(raw["tie_word_embeddings"]),
        "qk_norm": bool(raw["program"]["qk_norm"]),
    }


def program_config(raw: dict):
    """The program's ``ModelConfig`` for this file: the program's own
    entry for the model, with every size taken from the file."""
    from repro.configs import get_config

    p = plain(raw)
    base = get_config(raw["program"]["base"])
    cfg = dataclasses.replace(
        base, name=raw["name"], num_layers=p["num_layers"],
        d_model=p["d_model"], num_heads=p["num_heads"],
        num_kv_heads=p["num_kv_heads"], head_dim=p["head_dim"],
        d_ff=p["d_ff"], vocab_size=p["vocab_size"],
        rope_theta=p["rope_theta"], norm_eps=p["norm_eps"],
        tie_embeddings=p["tie_embeddings"], qk_norm=p["qk_norm"],
        max_position_embeddings=raw["max_position_embeddings"])
    if (cfg.arch_type != "dense" or cfg.attention_kind != "gqa"
            or cfg.act != raw["hidden_act"] or cfg.mlp_kind != "gated"
            or cfg.sliding_window or cfg.moe is not None):
        raise ValueError(f"{raw['name']}: the program's {base.name} entry "
                         "is not the dense gated GQA block this file states")
    return cfg


def param_counts(p: dict) -> dict:
    """Parameter counts for FLOP arithmetic: ``matmul`` is every weight a
    token passes through in a matrix product (layers and the output head;
    the embedding lookup is a gather)."""
    d, hd, V = p["d_model"], p["head_dim"], p["vocab_size"]
    attn = d * hd * (2 * p["num_heads"] + 2 * p["num_kv_heads"])
    mlp = 3 * d * p["d_ff"]
    layers = p["num_layers"] * (attn + mlp)
    head = d * V
    total = layers + V * d + (0 if p["tie_embeddings"] else head)
    return {"matmul": layers + head, "total": total}

"""Tiny CPU runs of the benchmark's cells for the tests: the cells' own
code paths at a model and traffic size a CPU test can hold, with the chip
check skipped and the peaks of the TPU standing in for the CPU's."""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = dict(hidden_size=64, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, intermediate_size=96,
                  num_hidden_layers=2)

TINY_MIX = {
    "rl": {"tree": {"max_width": 8, "segment_len": 8, "max_depth": 3,
                    "branch_factor": 2, "init_divergence_low": 2,
                    "init_divergence_high": 2, "temperature": 1.0}},
    "serve": {"rate_per_s": 4.0,
              "system_prompts": {"count": 2, "tokens": 64, "zipf_s": 1.1},
              "user_tokens": {"median": 8, "sigma": 0.5, "min": 1, "cap": 32},
              "output_tokens": {"median": 32, "sigma": 0.5, "min": 2,
                                "cap": 64},
              "max_running": 4, "segment_len": 8, "page_size": 4,
              "num_pages": 512, "check_requests": 8, "drain_seconds": 30},
}

RL_CELL = "qwen3-4b.rl-tree"
SERVE_CELL = "qwen2.5-7b.serve-shared"


def context(workload: str, seed: int = 3, seconds: float = 1.0,
            trace: bool = False):
    from bench import run
    from bench.traffic import generate

    cell = run.load_cell(workload)
    kind = generate.load(cell["traffic"])["kind"]
    return run.make_context(cell, seed, seconds, trace, TINY_MODEL,
                            TINY_MIX[kind])


def run_tiny(workload: str, seed: int = 3, seconds: float = 1.0,
             trace: bool = False):
    """(result, checks) of one tiny run on the CPU."""
    import jax

    from bench import harness, peaks, run

    kind = jax.devices()[0].device_kind
    peaks.PEAKS.setdefault(kind, peaks.PEAKS["TPU v5 lite"])
    ctx = context(workload, seed, seconds, trace)
    return run.run_cell(ctx, jax.devices()[:1], harness.CompileClock(),
                        time.perf_counter())

"""The one traffic generator: it reads a mix file ``bench/traffic/<mix>.json``
and makes the run's inputs from ``--seed``.  A mix is data only; a new mix
is a new file.

Two kinds of mix:

* ``rl``: verifiable math prompts for an RL step (``math_prompts``), padded
  to a fixed token count so every step runs the same shapes;
* ``serve``: an open-loop request stream (``serve_requests``): arrival
  times, a Zipf-skewed shared system prompt, a lognormal user part and a
  lognormal output length per request.

Sizes and the gaps between arrivals come from the mix's ``shape_seed``
and are the same for every run seed; the run seed draws their order and
the token contents.  So two seeds do the same work at the same offered
load, in another order.  The sizes' order is spread evenly over the
window (``spread_order``) before the seed shuffles it locally, so no seed
clusters the longest requests: that clustering, not the work, would set
a run's tail.

``poisson_trace`` and ``MathTaskGenerator`` are copies of the program's
(``repro.core.scheduler``, ``repro.data.synthetic_math``), kept here so the
yardstick does not move when the program does.
"""
from __future__ import annotations

import json
import math
import os
import random
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))
BYTE_VOCAB, BOS = 256, 257


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def run_rng(seed: int, stream: str) -> random.Random:
    """An independent ``random.Random`` per (seed, stream)."""
    return random.Random(f"{int(seed)}/{stream}")


def poisson_trace(rng, n: int, *, rate: float,
                  start: float = 0.0) -> List[float]:
    """``n`` Poisson arrival times (exponential gaps of mean ``1/rate``)."""
    out: List[float] = []
    t = start
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


_OPS = [("+", lambda a, b: a + b), ("-", lambda a, b: a - b),
        ("*", lambda a, b: a * b)]


class MathTaskGenerator:
    """Seeded multi-step integer arithmetic tasks with a unique answer."""

    def __init__(self, rng: random.Random, min_difficulty: int,
                 max_difficulty: int):
        self.rng = rng
        self.min_difficulty = min_difficulty
        self.max_difficulty = max_difficulty

    def sample(self):
        diff = self.rng.randint(self.min_difficulty, self.max_difficulty)
        lo, hi = 2, 6 + 2 * diff
        val = self.rng.randint(lo, hi)
        parts = [f"start with {val}"]
        for _ in range(diff):
            name, op = self.rng.choice(_OPS)
            y = self.rng.randint(lo, hi)
            val = op(val, y)
            verb = {"+": "add", "-": "subtract", "*": "multiply by"}[name]
            parts.append(f"{verb} {y}")
        query = ("Compute the following: " + ", then ".join(parts)
                 + ". Show your steps and put the final answer in \\boxed{}.")
        return query, str(val)


def math_prompts(mix: dict, rng: random.Random, n: int):
    """``n`` (prompt token ids, answer) pairs: [BOS] + the query's bytes,
    right-padded with spaces to exactly ``prompt_tokens``."""
    P = mix["prompt_tokens"]
    gen = MathTaskGenerator(rng, mix["min_difficulty"],
                            mix["max_difficulty"])
    out = []
    for _ in range(1000 * n):
        query, answer = gen.sample()
        ids = [BOS] + list(query.encode("utf-8"))
        if len(ids) <= P:
            out.append((ids + [ord(" ")] * (P - len(ids)), answer))
            if len(out) == n:
                return out
    raise ValueError(f"no math prompt of at most {P} tokens")


def _lognormal_int(rng, spec: dict) -> int:
    x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    return int(min(max(round(x), spec["min"]), spec["cap"]))


ORDER_BLOCK = 4


def spread_order(n: int) -> List[int]:
    """The ranks 0..n-1 in bit-reversed (van der Corput) order: any run of
    consecutive positions holds ranks from the whole range."""
    bits = max(1, (n - 1).bit_length())
    return sorted(range(n), key=lambda r: int(f"{r:0{bits}b}"[::-1], 2))


def serve_requests(mix: dict, seed: int, seconds: float) -> List[dict]:
    """The requests due in a window of ``seconds``: each a dict with
    ``due`` (seconds after the window opens), ``prompt`` (token ids) and
    ``max_new``.  Their count is ``round(rate * seconds)``; the gaps
    between arrivals are those of one Poisson trace of the mix's shape
    seed, scaled to fill the window, in the run seed's order; the sizes
    are the shape seed's too, in ``spread_order`` with each block of
    ``ORDER_BLOCK`` shuffled by the run seed."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    shape = random.Random(f"{mix['shape_seed']}/shape")
    sysp = mix["system_prompts"]
    weights = [1.0 / (k + 1) ** sysp["zipf_s"] for k in range(sysp["count"])]
    sizes = [(shape.choices(range(sysp["count"]), weights)[0],
              _lognormal_int(shape, mix["user_tokens"]),
              _lognormal_int(shape, mix["output_tokens"]))
             for _ in range(n)]
    trace = poisson_trace(shape, n + 1, rate=mix["rate_per_s"])
    gaps = [b - a for a, b in zip([0.0] + trace[:-1], trace[:-1])]
    scale = seconds / trace[-1]
    sizes.sort(key=lambda x: -(x[1] + x[2]))
    sizes = [sizes[r] for r in spread_order(n)]
    order = run_rng(seed, "order")
    for j in range(0, n, ORDER_BLOCK):
        block = sizes[j:j + ORDER_BLOCK]
        order.shuffle(block)
        sizes[j:j + ORDER_BLOCK] = block
    run_rng(seed, "arrivals").shuffle(gaps)
    times, t = [], 0.0
    for g in gaps:
        t += g * scale
        times.append(t)
    systems = system_prompts(mix, seed)
    text = run_rng(seed, "tokens")
    return [{"due": t, "system": k,
             "prompt": systems[k] + [text.randrange(BYTE_VOCAB)
                                     for _ in range(user)],
             "max_new": out}
            for t, (k, user, out) in zip(times, sizes)]


def system_prompts(mix: dict, seed: int) -> List[List[int]]:
    """The run's shared system prompts: [BOS] + random bytes each."""
    sysp = mix["system_prompts"]
    rng = run_rng(seed, "system")
    return [[BOS] + [rng.randrange(BYTE_VOCAB)
                     for _ in range(sysp["tokens"] - 1)]
            for _ in range(sysp["count"])]

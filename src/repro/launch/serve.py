"""Continuous-batching serving driver (the inference side of the paper).

Requests arrive on a seeded Poisson trace and are served by the
Scheduler / ModelRunner pair (``repro.core.scheduler``): admission is
continuous, prompt-prefill chunks and decode segments mix in one jitted
dispatch per round, and shared prompt prefixes (the --shared system
prompt) reuse KV pages across requests through the radix cache.
``--mode sync`` reproduces the old batch driver on the same serve
function — same per-request streams (the parity oracle), lower
throughput — and ``--sampler tree|sequential`` keeps the original
tree-rollout driver around for the paper's TrajPS numbers.

  python -m repro.launch.serve --arch qwen2.5-7b-smoke --requests 8
  python -m repro.launch.serve --mode sync --radix off
"""
from __future__ import annotations

import argparse
import random
import time
from collections import Counter
from typing import List, Tuple

import jax

from repro.configs import get_config
from repro.configs.base import TreeConfig
from repro.core.engine import TreeEngine
from repro.core.sampler import sample_sequential, sample_trees
from repro.core.scheduler import (Request, Scheduler, ServeReport,
                                  poisson_trace)
from repro.data.reward import extract_boxed, verify_answer
from repro.data.synthetic_math import MathTaskGenerator
from repro.data.tokenizer import ByteTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params

SYSTEM_PROMPT = ("You are a careful math assistant. Work step by step "
                 "and put the final answer in \\boxed{}. ")


def serve_requests(args, engine: TreeEngine, tok: ByteTokenizer,
                   rng) -> Tuple[List[Request], ServeReport]:
    """Serve ``args.requests`` seeded math prompts on a Poisson trace and
    print the summary; returns the finished requests and the report."""
    gen = MathTaskGenerator(seed=args.seed, min_difficulty=1,
                            max_difficulty=2)
    samples = gen.batch(args.requests)
    prefix = SYSTEM_PROMPT if args.shared == "on" else ""
    arrivals = poisson_trace(rng, args.requests, rate=args.rate)
    reqs = [Request(rid=i, prompt=tok.encode(prefix + s.query, bos=True),
                    max_new_tokens=args.max_new, arrival=a)
            for i, (s, a) in enumerate(zip(samples, arrivals))]
    sched = Scheduler(engine, mode=args.mode, max_running=args.max_running,
                      radix=args.radix == "on", base_seed=args.seed,
                      clock="wall")
    t0 = time.time()
    report = sched.run(reqs)
    wall = time.time() - t0
    print(f"{args.mode} serving summary ({args.requests} requests, "
          f"Poisson rate {args.rate}/s, seed {args.seed}):")
    print(f"  TrajPS  : {report.finished / max(wall, 1e-9):.3f}")
    print(f"  TokenPS : {report.gen_tokens / max(wall, 1e-9):.1f} "
          f"generated ({report.model_tokens / max(wall, 1e-9):.1f} "
          f"model-processed)")
    print(f"  rounds  : {report.rounds}; max admission wait "
          f"{report.max_admission_wait} rounds; "
          f"preemptions {report.preemptions}")
    print(f"  radix   : reuse ratio {report.reuse_ratio:.3f} "
          f"({report.radix_hit_tokens}/{report.prompt_tokens} prompt "
          f"tokens from cache; {report.evicted_pages} pages evicted)")
    print(f"  peak KV pages: {engine.stats.peak_pages}")
    return reqs, report


def serve_trees(args, engine: TreeEngine, tok: ByteTokenizer,
                rng) -> None:
    gen = MathTaskGenerator(seed=args.seed, min_difficulty=1,
                            max_difficulty=2)
    fn = sample_trees if args.sampler == "tree" else sample_sequential
    total_traj, total_wall = 0, 0.0
    for b in range(args.batches):
        samples = gen.batch(args.requests)
        prompts = [tok.encode(s.query, bos=True) for s in samples]
        t0 = time.time()
        trees, rep = fn(engine, prompts, [s.answer for s in samples],
                        rng=rng)
        wall = time.time() - t0
        answered = 0
        for tree, s in zip(trees, samples):
            answers = [a for p in tree.finished
                       if (a := extract_boxed(tok.decode(p.tokens)))]
            if answers and verify_answer(
                    Counter(answers).most_common(1)[0][0], s.answer):
                answered += 1
        total_traj += rep.num_trajectories
        total_wall += wall
        print(f"batch {b}: {rep.num_trajectories} trajs "
              f"({rep.num_fallbacks} fallbacks) in {wall:.1f}s, "
              f"maj-correct {answered}/{args.requests}", flush=True)
    s = engine.stats
    print(f"\n{args.sampler} rollout summary:")
    print(f"  TrajPS  : {total_traj / max(total_wall, 1e-9):.3f}")
    print(f"  TokenPS : {s.model_tokens / max(total_wall, 1e-9):.1f}")
    print(f"  tokens  : {s.model_tokens} "
          f"(prefill {s.prefill_tokens}, decode {s.decode_tokens}, "
          f"replay {s.replay_tokens})")
    print(f"  peak KV pages: {s.peak_pages}; forks {s.forks} "
          f"(COW {s.cow_pages})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-7b-smoke")
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "sync", "rollout"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="Poisson arrival rate (requests per second)")
    ap.add_argument("--max-running", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--radix", default="on", choices=["on", "off"])
    ap.add_argument("--shared", default="on", choices=["on", "off"],
                    help="prepend a shared system prompt (radix workload)")
    ap.add_argument("--batches", type=int, default=2,
                    help="rollout mode: number of tree batches")
    ap.add_argument("--width", type=int, default=6)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--segment", type=int, default=16)
    ap.add_argument("--sampler", default="tree",
                    choices=["tree", "sequential"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    tok = ByteTokenizer()
    cfg = get_config(args.arch)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    tree_cfg = TreeConfig(max_depth=args.depth, segment_len=args.segment,
                          max_width=args.width, branch_factor=2,
                          init_divergence_low=2, init_divergence_high=4,
                          temperature=1.0)
    engine = TreeEngine(params, cfg, tree_cfg, num_pages=4096,
                        page_size=args.segment, max_slots=256,
                        max_queries=64, max_prompt_len=256,
                        seed=args.seed)
    rng = random.Random(args.seed)
    if args.mode == "rollout":
        serve_trees(args, engine, tok, rng)
    else:
        serve_requests(args, engine, tok, rng)


if __name__ == "__main__":
    main()

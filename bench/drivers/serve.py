"""Serve cells: open-loop continuous serving through the program's
``Scheduler`` (mode ``continuous``, radix cache on) over a ``TreeEngine``.

The benchmark drives ``Scheduler.submit`` and ``Scheduler.step`` itself on
wall-clock due times: a request is submitted once its due time has passed,
whatever the scheduler is doing, and every latency counts from the due
time.  Set-up puts the mix's shared system prompts into the radix cache by
serving each once (the serve program compiles there).  Requests due in the
window are drained after it.  Decoding is greedy, so the check can hold
every served token to the reference's best.
"""
from __future__ import annotations

import math
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights
from bench.traffic import generate

KERNELS = ("fused_paged_attention_pallas",)


def _scheduler_class():
    from repro.core.scheduler import Scheduler

    class BenchScheduler(Scheduler):
        """The program's scheduler with host spans around admission and
        the dispatch (pack, serve program, unpack)."""

        spans = None

        def _admit(self):
            with self.spans("admit"):
                super()._admit()

        def _dispatch(self):
            rows = list(self.running)
            self.dispatches.append(
                (len(rows), [r.ep.position for r in rows]))
            with self.spans("dispatch"):
                super()._dispatch()

    return BenchScheduler


def build(ctx):
    from repro.configs.base import TreeConfig
    from repro.core.engine import TreeEngine
    from repro.core.scheduler import Request

    mix = ctx.mix
    l = mix["segment_len"]
    sysp = mix["system_prompts"]
    max_prompt = sysp["tokens"] + mix["user_tokens"]["cap"]
    max_depth = -(-(mix["output_tokens"]["cap"] + l) // l)
    tree_cfg = TreeConfig(max_depth=max_depth, segment_len=l, max_width=1,
                          temperature=mix["temperature"])
    engine = TreeEngine(weights.make_params(ctx.p, ctx.seed), ctx.cfg,
                        tree_cfg, num_pages=mix["num_pages"],
                        page_size=mix["page_size"],
                        max_slots=2 * mix["max_running"], max_queries=1,
                        max_prompt_len=max_prompt, seed=ctx.seed32)
    sched = _scheduler_class()(engine, mode="continuous",
                               max_running=mix["max_running"], seg_len=l,
                               radix=True, base_seed=ctx.seed32)
    sched.spans = ctx.run.spans
    sched.dispatches = ctx.run.dispatches
    # the shared system prompts, served once each, fill the radix cache
    for k, prompt in enumerate(generate.system_prompts(mix, ctx.seed)):
        sched.submit(Request(rid=1_000_000 + k, prompt=prompt,
                             max_new_tokens=1))
    while sched.waiting or sched.running:
        sched.step()
    return sched


def window(ctx, sched) -> dict:
    """Submit each request at its due time; step while work is queued or
    running; after the window, drain what was due in it."""
    from repro.core.scheduler import Request

    mix, run = ctx.mix, ctx.run
    reqs = generate.serve_requests(mix, ctx.seed, ctx.seconds)
    objs = [Request(rid=i, prompt=r["prompt"], max_new_tokens=r["max_new"])
            for i, r in enumerate(reqs)]
    due = [r["due"] for r in reqs]
    first = [math.inf] * len(objs)
    last = [math.inf] * len(objs)
    admitted = [math.inf] * len(objs)
    lateness = 0.0
    sched.dispatches.clear()
    rep0 = (sched.report.prompt_tokens, sched.report.radix_hit_tokens,
            sched.report.preemptions)
    inflight: List[int] = []
    nxt = 0
    longest_step = (0.0, 0.0)
    t0 = time.perf_counter()
    deadline = ctx.seconds + mix["drain_seconds"]
    while True:
        now = time.perf_counter() - t0
        while nxt < len(objs) and due[nxt] <= now:
            sched.submit(objs[nxt])
            lateness = max(lateness, now - due[nxt])
            inflight.append(nxt)
            nxt += 1
        if sched.waiting or sched.running:
            start = time.perf_counter() - t0
            sched.step()
            end = time.perf_counter() - t0
            longest_step = max(longest_step, (end - start, start))
            still = []
            for i in inflight:
                r = objs[i]
                if r.admit_round >= 0 and admitted[i] == math.inf:
                    admitted[i] = start
                if r.out_tokens and first[i] == math.inf:
                    first[i] = end
                if r.state == "finished":
                    last[i] = end
                else:
                    still.append(i)
            inflight = still
        elif nxt < len(objs):
            time.sleep(max(0.0, min(due[nxt] - now, 0.001)))
        else:
            break
        if now > deadline:
            break
    ok = [i for i, r in enumerate(objs)
          if r.state == "finished" and r.finish_reason in ("length", "eos")]
    ttft = [first[i] - due[i] if i in set(ok) else math.inf
            for i in range(len(objs))]
    okset = set(ok)
    tpot = [(last[i] - first[i]) / max(len(objs[i].out_tokens) - 1, 1)
            if i in okset else math.inf for i in range(len(objs))]
    queue = [admitted[i] - due[i] for i in range(len(objs))]
    return {
        "requests": objs, "ok": ok, "ttft": ttft, "tpot": tpot,
        "queue": queue, "due": due, "lateness": lateness,
        "longest_step": longest_step,
        "elapsed": ctx.seconds,
        "failed": len(objs) - len(ok),
        "prompt_tokens": sched.report.prompt_tokens - rep0[0],
        "radix_hit_tokens": sched.report.radix_hit_tokens - rep0[1],
        "preemptions": sched.report.preemptions - rep0[2],
        "out_tokens": sum(len(objs[i].out_tokens) for i in ok),
    }


def check_sample(ctx, res) -> List[int]:
    """The finished requests the check compares: a draw from the seed with
    the one that served the most tokens in it."""
    ok = res["ok"]
    if not ok:
        return []
    n = min(ctx.mix["check_requests"], len(ok))
    longest = max(ok, key=lambda i: len(res["requests"][i].out_tokens))
    rest = [i for i in ok if i != longest]
    pick = generate.run_rng(ctx.seed, "check").sample(rest, n - 1)
    return sorted([longest] + pick)


def reference_gaps(ctx, seqs: List[tuple], control=None) -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best at its position, over ``seqs`` (prompt, served
    tokens).  With a ``control`` precision, the token scored at each
    position is the one the reference at that precision puts first, not
    the served one."""
    p = ctx.p
    params = weights.make_params(p, ctx.seed)
    L = max(len(a) + len(b) for a, b in seqs)
    L = -(-L // 256) * 256

    @jax.jit
    def gaps(params, toks, start, n):
        logits = reference.forward(params, p, toks)[0]
        pos = jnp.arange(L)
        sel = (pos >= start - 1) & (pos < start - 1 + n)
        nxt = jnp.concatenate([toks[0, 1:], toks[0, :1]])
        if control is not None:
            nxt = jnp.argmax(reference.forward(params, p, toks, control)[0],
                             axis=-1)
        got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
        return jnp.max(jnp.where(sel, logits.max(axis=-1) - got, 0.0))

    worst = 0.0
    for prompt, out in seqs:
        toks = np.zeros((1, L), np.int32)
        toks[0, :len(prompt)] = prompt
        toks[0, len(prompt):len(prompt) + len(out)] = out
        worst = max(worst, float(gaps(params, toks, len(prompt),
                                      len(out))))
    return worst

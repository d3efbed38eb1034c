"""From a cell's window to the numbers its metrics read: the end-to-end
metrics, and a context of counts, spans, operations and bytes (computed
from shapes), and the reduced trace, for the per-layer readers in
``bench/metrics/``."""
from __future__ import annotations

import statistics
import sys
import types


from bench import harness, model_config
from bench.peaks import peaks_for

F32_BYTES = 4


def _attn_flops(p: dict, ctx_tokens: float) -> float:
    """Attention FLOPs of one query token against ``ctx_tokens`` keys,
    over all layers (QK^T and PV, two FLOPs a multiply-add)."""
    return 4.0 * ctx_tokens * p["num_heads"] * p["head_dim"] * p["num_layers"]


def _kv_bytes(p: dict, ctx_tokens: float) -> float:
    """K and V bytes of ``ctx_tokens`` positions over all layers."""
    return (2.0 * ctx_tokens * p["num_kv_heads"] * p["head_dim"]
            * p["num_layers"] * F32_BYTES)


def _decode_work(p: dict, R: int, l: int, positions) -> tuple:
    """(attention FLOPs, least paged-attention seconds at the peak) of one
    l-token segment for rows at ``positions``."""
    flops = kv = 0.0
    for pos in positions:
        ctx = l * pos + l * (l + 1) / 2       # keys seen over the l steps
        flops += _attn_flops(p, ctx)
        kv += _kv_bytes(p, ctx)
    q_o = 2.0 * R * l * p["num_heads"] * p["head_dim"] * F32_BYTES \
        * p["num_layers"]
    return flops, kv + q_o


def _flash_call(p: dict, seq_lens) -> tuple:
    """(FLOPs, bytes) a causal flash-attention forward needs over rows of
    the given valid lengths, all layers."""
    pairs = sum(n * (n + 1) / 2 for n in seq_lens)
    tokens = sum(seq_lens)
    flops = _attn_flops(p, pairs)
    byts = (tokens * (2 * p["num_heads"] + 2 * p["num_kv_heads"])
            * p["head_dim"] * F32_BYTES * p["num_layers"])
    return flops, byts


def build(ctx, res: dict, reduced, setup_s: float, device: dict):
    p = ctx.p
    pk = peaks_for(device["kind"])
    pf, pb = pk["flops_bf16"], pk["hbm_bytes_per_s"]
    counts = model_config.param_counts(p)
    m = types.SimpleNamespace(kind=ctx.mix["kind"], trace=reduced,
                              window_s=res["window_s"], peaks=pk,
                              compile_s=res["compile_s"], spans=res["spans"],
                              end_to_end={"setup_s": setup_s})
    paged_least = flash_least = 0.0
    if m.kind == "rl":
        steps = len(res["metrics"])
        m.end_to_end["rl_step_s"] = res["elapsed"] / max(steps, 1)
        m.pad_fractions = [x["padded_token_fraction"] for x in res["metrics"]]
        model_tokens = sum(s.model_tokens for s in ctx.run.engine_stats)
        traj = sum(len(r["tokens"]) for rows in ctx.run.steps for r in rows)
        prompt = sum(len(r["prompt"]) for rows in ctx.run.steps for r in rows)
        m.sharing = (max(traj + prompt - model_tokens, 0), traj + prompt)
        flops = 2.0 * counts["matmul"] * model_tokens
        for R, l, positions in ctx.run.decode_calls:
            f, b = _decode_work(p, R, l, positions)
            flops += f
            paged_least += max(f / pf, b / pb)
        for prompts in ctx.run.prompts:
            f, b = _flash_call(p, [len(x) for x in prompts])
            flops += f
            flash_least += max(f / pf, b / pb)
        for rows in ctx.run.steps:
            lens = [len(r["prompt"]) + len(r["tokens"]) for r in rows]
            f, b = _flash_call(p, lens)
            flops += 6.0 * counts["matmul"] * sum(lens) + 3.0 * f
            flash_least += max(f / pf, b / pb)
        m.model_flops = flops
    else:
        done = [res["requests"][i] for i in res["ok"]]
        m.end_to_end["ttft_p95_ms"] = 1e3 * harness.p95(res["ttft"])
        m.end_to_end["tpot_p95_ms"] = 1e3 * harness.p95(res["tpot"])
        m.radix = (res["radix_hit_tokens"], res["prompt_tokens"])
        m.queue_p95_ms = 1e3 * harness.p95(res["queue"])
        l = ctx.mix["segment_len"]
        flops = 0.0
        for R, positions in ctx.run.dispatches:
            f, b = _decode_work(p, R, l, positions)
            flops += 2.0 * counts["matmul"] * R * l + f
            paged_least += max(f / pf, b / pb)
        m.model_flops = flops
        m.done = len(done)
    m.least_s = {"fused_paged_attention_pallas": paged_least,
                 "flash_attention_pallas": flash_least}
    return m


def report(ctx, res: dict, m) -> None:
    """Human-readable lines on standard error: what the window did."""
    out = sys.stderr
    print(f"window: {m.window_s:.3f} s, compile {res['compile_s']:.3f} s "
          f"({res['cache_hits']} cache hits, {res['cache_misses']} misses), "
          f"gc {res['gc_s']:.3f} s in {res['gc_collections']} collections",
          file=out)
    print(f"spans: " + ", ".join(f"{k} {v:.3f} s"
                                 for k, v in sorted(res["spans"].items())),
          file=out)
    if m.kind == "rl":
        steps = [x.get("loss", float("nan")) for x in res["metrics"]]
        print(f"rl: {len(steps)} steps, {res['failed']} failed, "
              f"losses {steps}", file=out)
    else:
        n = len(res["requests"])
        ttft = [t for t in res["ttft"] if t != float("inf")]
        tpot = [t for t in res["tpot"] if t != float("inf")]
        print(f"serve: {n} requests due, {len(res['ok'])} finished, "
              f"{res['failed']} failed, {res['out_tokens']} tokens out, "
              f"generator late by at most {res['lateness'] * 1e3:.1f} ms, "
              f"{res['preemptions']} preemptions; longest step "
              f"{res['longest_step'][0]:.3f} s at {res['longest_step'][1]:.1f}"
              f" s; queue wait {backlog(res, ctx.seconds)}", file=out)
        if ttft and tpot:
            print(f"serve: ttft median {statistics.median(ttft) * 1e3:.1f} "
                  f"ms, tpot median {statistics.median(tpot) * 1e3:.2f} ms",
                  file=out)
    print(f"model FLOPs {m.model_flops:.4g}; end to end "
          f"{m.end_to_end}", file=out)
    out.flush()


def backlog(res: dict, seconds: float) -> dict:
    """Median queue wait (ms) of the requests due in the window's first
    half, its second half and its last quarter: steady below the knee,
    growing above it."""
    parts = {"first_half": (0.0, 0.5), "second_half": (0.5, 1.0),
             "last_quarter": (0.75, 1.0)}
    out = {}
    for name, (lo, hi) in parts.items():
        waits = [q for d, q in zip(res["due"], res["queue"])
                 if lo * seconds <= d < hi * seconds]
        out[name] = (round(1e3 * statistics.median(waits), 1)
                     if waits else None)
    return out

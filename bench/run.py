#!/usr/bin/env python3
"""The benchmark's one command:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout on a machine whose accelerator the cell
needs.  It reads the cell from ``BENCHMARK.json``, its configuration from
``bench/configs/<config>.json``, its traffic mix from
``bench/traffic/<traffic>.json`` and its limits from
``bench/cells/<cell>.json``; builds the weights on the device from the seed;
warms up; measures for ``--seconds``; checks what the timed path produced
against the plain reference; and prints one JSON result as the last line of
standard output (``--trace 0``: the cell's end-to-end metrics; ``--trace
1``: its per-layer metrics, with the window under the profiler).  It exits
non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell needs.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
import zlib  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def enable_cache() -> None:
    """JAX's persistent compilation cache, in the checkout at a fixed path,
    for every program however small."""
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    cell = dict(cells[workload])
    with open(os.path.join(ROOT, "bench", "cells", f"{workload}.json")) as f:
        cell.update(json.load(f))
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if workload in m.get("workloads", [workload])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if workload in m.get("workloads", [workload])]
    return cell


def make_context(cell: dict, seed: int, seconds: float, trace: bool,
                 config_override: dict = None, mix_override: dict = None):
    """Everything a driver reads about the run."""
    from bench import harness, model_config
    from bench.traffic import generate

    raw = model_config.load(cell["config"])
    raw.update(config_override or {})
    mix = generate.load(cell["traffic"])
    mix.update(mix_override or {})
    ctx = types.SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=trace, raw=raw,
        p=model_config.plain(raw), cfg=model_config.program_config(raw),
        mix=mix,
        # the program's own seeds are 32-bit: a hash of the whole seed
        seed32=zlib.crc32(str(int(seed)).encode()) & 0x7FFFFFFF,
        run=types.SimpleNamespace(
            spans=harness.Spans(), steps=[], batches=[], decode_calls=[],
            engine_stats=[], prompts=[], dispatches=[]))
    return ctx


def measure(ctx, devices, clock, setup_start: float) -> dict:
    """Set up, warm up and measure one window; the program's state is
    freed before this returns.  The drivers end set-up and the window on
    host reads of the program's output, so the device has finished."""
    from bench import harness
    from bench.drivers import rl, serve

    driver = {"rl": rl, "serve": serve}[ctx.mix["kind"]]
    out = {"driver": driver, "prog": None}
    state = driver.build(ctx)
    if ctx.mix["kind"] == "rl":
        state, out["prog"] = state
    out["setup_s"] = time.perf_counter() - setup_start
    ctx.run.spans.reset()
    c0 = clock.snapshot()
    prof = harness.ProfilerWindow(ctx.trace, ctx.cell["name"])
    prof.start()
    gc_clock = harness.GcClock()
    w0 = time.perf_counter()
    res = driver.window(ctx, state)
    window_s = time.perf_counter() - w0
    res["gc_s"], res["gc_collections"] = gc_clock.close()
    xplane = prof.stop()
    c1 = clock.snapshot()
    res["compile_s"] = c1[0] - c0[0]
    res["cache_hits"] = c1[1] - c0[1]
    res["cache_misses"] = c1[2] - c0[2]
    res["window_s"] = window_s
    res["spans"] = dict(ctx.run.spans.seconds)
    out["res"] = res
    dev = devices[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices),
                     "memory_peak_bytes": harness.peak_bytes(devices)}
    out["trace"] = None
    if xplane is not None:
        from bench import trace as trace_mod
        ops, spans = trace_mod.load(xplane)
        out["trace"] = trace_mod.reduce(ops, spans, driver.KERNELS)
        prof.cleanup()
        out["device"]["busy_s"] = out["trace"]["busy_s"]
        out["device"]["window_s"] = window_s
    # the program's state is freed before the reference runs
    del state
    gc.collect()
    return out


def check(ctx, m: dict, control=None) -> dict:
    """The numbers compared: of the program against the float32
    reference, or with the ``control`` precision ``reference.INT8`` of the
    reference at that precision against it."""
    from bench.drivers import rl, serve

    res = m["res"]
    if ctx.mix["kind"] == "rl":
        prog = m["prog"]
        ref = m.get("ref") or rl.reference_run(ctx, prog["steps"])
        m["ref"] = ref
        prog["logprobs"] = rl.program_logprobs(prog)
        got = (rl.reference_run(ctx, prog["steps"], control)
               if control is not None else prog)
        return rl.readings(prog, ref, got)
    seqs = [(res["requests"][i].prompt, res["requests"][i].out_tokens)
            for i in serve.check_sample(ctx, res)]
    if not seqs:
        return {"served_logit_gap": float("inf")}
    return {"served_logit_gap": serve.reference_gaps(ctx, seqs, control)}


def run_cell(ctx, devices, clock, setup_start: float) -> tuple:
    """Set up, measure, check.  Returns (result, checks)."""
    from bench import harness, metrics_context

    m = measure(ctx, devices, clock, setup_start)
    got = check(ctx, m)
    res = m["res"]
    limits = ctx.cell["limits"]
    checks = {k: (got[k], limits[k]) for k in limits}
    correct = all(v <= lim for v, lim in checks.values())
    if ctx.mix["kind"] == "rl":
        attempted = len(res["metrics"])
    else:
        attempted = len(res["requests"])
    correct = correct and res["failed"] == 0 and attempted > 0
    mctx = metrics_context.build(ctx, res, m["trace"], m["setup_s"],
                                 m["device"])
    if ctx.trace:
        metrics = harness.read_metrics(
            [x["name"] for x in ctx.cell["per_layer"]], mctx)
    else:
        metrics = {x["name"]: {"value": mctx.end_to_end[x["name"]],
                               "unit": x["unit"]}
                   for x in ctx.cell["end_to_end"]}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": res["failed"], "metrics": metrics,
              "device": m["device"]}
    if m["trace"] is not None:
        result["breakdown"] = {"device_ops": m["trace"]["device_ops"],
                               "idle_gaps": m["trace"]["idle_gaps"]}
    metrics_context.report(ctx, res, mctx)
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    # the checkout's root, not bench/, heads the path: bench/trace.py must
    # not stand in for the standard library's module of that name
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax

    from bench import harness

    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    enable_cache()
    clock = harness.CompileClock()
    ctx = make_context(cell, args.seed, args.seconds, bool(args.trace))
    result, checks = run_cell(ctx, devices, clock, PROCESS_START)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip, in one process:

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--control int8] [--fault <name>] [--rates <r1,r2>] [--check 0]

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds``), then prints one JSON line with the numbers the check
compares: the program's against the float32 reference, and with
``--control int8`` the reference's at that precision against it too.
``--fault`` plants one of ``bench/faults.py``'s faults in the program
first.  ``--rates`` offers a serve cell's mix at each of these rates in
turn, and with ``--check 0`` the line holds the window alone: the knee
sweep.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rates", default=None)
    ap.add_argument("--check", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    from bench import faults, harness, metrics_context, reference, run

    cell = run.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    clock = harness.CompileClock()
    rates = ([{"rate_per_s": float(r)} for r in args.rates.split(",")]
             if args.rates else [None])
    for seed, mix in itertools.product(
            [int(s) for s in args.seeds.split(",")], rates):
        ctx = run.make_context(cell, seed, args.seconds, False,
                               mix_override=mix)
        t0 = time.perf_counter()
        plant = (faults.planted(ctx.mix["kind"], args.fault)
                 if args.fault else contextlib.nullcontext())
        with plant:
            m = run.measure(ctx, devices, clock, t0)
        mctx = metrics_context.build(ctx, m["res"], None, m["setup_s"],
                                     m["device"])
        metrics_context.report(ctx, m["res"], mctx)
        line = {"seed": seed, "fault": args.fault,
                "rate_per_s": ctx.mix.get("rate_per_s"),
                "end_to_end": mctx.end_to_end, "failed": m["res"]["failed"],
                "setup_s": m["setup_s"], "window_s": m["res"]["window_s"],
                "memory_peak_bytes": m["device"]["memory_peak_bytes"]}
        if ctx.mix["kind"] == "serve":
            line["backlog"] = metrics_context.backlog(m["res"], ctx.seconds)
        t1 = time.perf_counter()
        if args.check:
            line["program"] = run.check(ctx, m)
            if args.control:
                line["control_int8"] = run.check(ctx, m,
                                                 control=reference.INT8)
        line["check_s"] = time.perf_counter() - t1
        print(json.dumps(line), flush=True)
        del m
    return 0


if __name__ == "__main__":
    sys.exit(main())

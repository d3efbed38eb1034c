#!/usr/bin/env python3
"""The program's own spans and build counters, as the per-layer readers and
an operator read them.

The program (``repro.core.guard``) records host spans while a
``jax.profiler.start_trace`` session runs, so a ``--trace 1`` window carries them: each
span is ``(name, start_ns, end_ns, parent, attrs)`` on the wall clock, and
every jaxpr trace, lowering and backend compile (or persistent-cache load)
of a program is a ``build.<program>.<trace|lower|compile>`` span.  A
program without spans gives nothing to read, and every reader then returns
``None``.

Reductions (pure functions of span and operation lists, times in ns):

* host time of a span: its duration less the union of its descendants
  named ``transfer.*`` (the host waiting on the device) and ``build.*``;
* the per-program build table: traces, lowerings, compiles, cache hits and
  misses, and seconds per phase;
* the offset between the spans' clock and the profiler trace's, from the
  spans present in both (``repro.<name>`` host events): the median of the
  start differences, with the largest disagreement;
* device idle by program span: each gap between busy intervals on the
  first chip, split at span edges, each piece to the innermost span over
  it, or ``unattributed``.

Run as a command, it runs one cell as ``bench/run.py --trace 1`` does and
keeps the profiler's trace to print that attribution too::

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s>

``--spans-only`` runs the cell as ``--trace 0`` does, with the program's
spans switched on and the profiler off: the cost of the spans alone.
"""
from __future__ import annotations

import collections
import math
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "repro."
LEAVE_OUT = ("transfer.", "build.")

# the spans the last reader took from the program, for the command below
taken: Optional[list] = None


def window_spans(ctx) -> Optional[list]:
    """The program's spans of the traced window, taken from the program
    once per metrics context; ``None`` where the program records none."""
    global taken
    if not hasattr(ctx, "program_spans"):
        try:
            from repro.core import guard
        except ImportError:
            guard = None
        take = getattr(guard, "take_spans", None)
        ctx.program_spans = (take() or None) if take is not None else None
        taken = ctx.program_spans
    return ctx.program_spans


# ---------------------------------------------------------------------------
# span arithmetic
# ---------------------------------------------------------------------------

def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def children(spans: Sequence) -> List[List[int]]:
    out: List[List[int]] = [[] for _ in spans]
    for i, sp in enumerate(spans):
        if sp[3] >= 0:
            out[sp[3]].append(i)
    return out


def host_ns(spans: Sequence, kids: List[List[int]], i: int,
            leave_out: Tuple[str, ...] = LEAVE_OUT) -> int:
    """Duration less the union of the descendants whose names start with
    one of ``leave_out``."""
    s, e = spans[i][1], spans[i][2]
    out, todo = [], list(kids[i])
    while todo:
        k = todo.pop()
        if spans[k][0].startswith(leave_out):
            out.append((max(spans[k][1], s), min(spans[k][2], e)))
        else:
            todo.extend(kids[k])
    return (e - s) - union_ns(iv for iv in out if iv[1] > iv[0])


def named(spans: Sequence, name: str) -> List[int]:
    return [i for i, sp in enumerate(spans) if sp[0] == name]


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile."""
    vals = sorted(values)
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


def build_table(spans: Sequence) -> Dict[str, dict]:
    """Per program: traces, lowerings, compiles (or cache loads), cache
    hits and misses, and seconds per phase, from the ``build.*`` spans."""
    table: Dict[str, dict] = {}
    for name, s, e, _parent, attrs in spans:
        if not name.startswith("build."):
            continue
        prog, phase = name[len("build."):].rsplit(".", 1)
        row = table.setdefault(prog, {
            "traces": 0, "lowerings": 0, "compiles": 0, "cache_hits": 0,
            "cache_misses": 0, "trace_s": 0.0, "lower_s": 0.0,
            "compile_s": 0.0})
        row[{"trace": "traces", "lower": "lowerings",
             "compile": "compiles"}[phase]] += 1
        row[f"{phase}_s"] += (e - s) / 1e9
        row["cache_hits"] += attrs.get("cache_hits", 0)
        row["cache_misses"] += attrs.get("cache_misses", 0)
    return table


def longest_round(spans: Sequence, kids: List[List[int]],
                  name: str = "scheduler.round") -> Optional[dict]:
    """The round of the most host time, with its children's durations."""
    rounds = named(spans, name)
    if not rounds:
        return None
    i = max(rounds, key=lambda k: host_ns(spans, kids, k))
    return {"host_ms": host_ns(spans, kids, i) / 1e6,
            "ms": (spans[i][2] - spans[i][1]) / 1e6,
            "attrs": spans[i][4],
            "children": [(spans[k][0], (spans[k][2] - spans[k][1]) / 1e6,
                          host_ns(spans, kids, k) / 1e6)
                         for k in kids[i]]}


# ---------------------------------------------------------------------------
# the profiler trace's clock
# ---------------------------------------------------------------------------

def clock_offset(spans: Sequence, traced: Sequence
                 ) -> Optional[Tuple[int, int, int]]:
    """(offset, largest disagreement, pairs), in ns: trace time = span
    time + offset.  ``traced`` holds the trace's ``repro.*`` host events
    as (name without the prefix, start, end); the k-th span of a name
    pairs with the k-th event of that name, for names the two count
    alike."""
    mem = collections.defaultdict(list)
    tr = collections.defaultdict(list)
    for sp in spans:
        if not sp[0].startswith("build."):
            mem[sp[0]].append((sp[1], sp[2]))
    for name, s, e in traced:
        tr[name].append((s, e))
    pairs = []
    for name, ms in mem.items():
        ts = tr.get(name, [])
        if len(ts) == len(ms):
            pairs.extend(zip(sorted(ms), sorted(ts)))
    if not pairs:
        return None
    offset = int(statistics.median(t[0] - m[0] for m, t in pairs))
    worst = max(max(abs(t[0] - m[0] - offset), abs(t[1] - m[1] - offset))
                for m, t in pairs)
    return offset, worst, len(pairs)


def idle_by_span(busy: Sequence[Tuple[int, int]], spans: Sequence,
                 offset: int) -> Dict[str, float]:
    """Seconds of device idle (the gaps between ``busy`` intervals) under
    each span name, the innermost span over each piece; ``unattributed``
    where no span is open."""
    depth = []
    for sp in spans:
        depth.append(depth[sp[3]] + 1 if sp[3] >= 0 else 0)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0]
    points = []
    for i, sp in enumerate(spans):
        if sp[2] > sp[1]:
            points.append((sp[1] + offset, 1, i))
            points.append((sp[2] + offset, 0, i))
    points.sort()
    out: Dict[str, float] = collections.defaultdict(float)
    active: Dict[int, Tuple[int, int]] = {}
    g, prev = 0, -math.inf
    for t, opening, i in points + [(math.inf, 0, -1)]:
        if t > prev:
            # idle inside [prev, t), to the innermost open span
            while g < len(gaps) and gaps[g][1] <= prev:
                g += 1
            k, idle = g, 0
            while k < len(gaps) and gaps[k][0] < t:
                idle += min(gaps[k][1], t) - max(gaps[k][0], prev)
                k += 1
            if idle > 0:
                label = "unattributed"
                if active:
                    j = max(active, key=active.get)
                    label = spans[j][0]
                out[label] += idle / 1e9
        if i < 0:
            break
        if opening:
            active[i] = (depth[i], -(spans[i][2] - spans[i][1]))
        else:
            active.pop(i, None)
        prev = t
    return dict(out)


def load_trace(path: str):
    """Device op events per chip and the program's host spans of the trace
    at ``path``: ({chip: [(op, start_ns, end_ns)]}, [(span name without
    ``repro.``, start_ns, end_ns)])."""
    from jax.profiler import ProfileData

    from bench import trace as trace_mod

    data = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    spans: list = []
    for plane in data.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == trace_mod.OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    return ops, spans


# ---------------------------------------------------------------------------
# what a traced run prints
# ---------------------------------------------------------------------------

def print_build_table(spans: Sequence, out=None, top: int = 20) -> None:
    """The window's per-program build table, the most seconds first; the
    programs past ``top`` summed in one line."""
    out = out or sys.stderr
    rows = sorted(build_table(spans).items(), key=lambda kv: -(
        kv[1]["trace_s"] + kv[1]["lower_s"] + kv[1]["compile_s"]))
    print("builds in the window: program traces lowerings compiles "
          "cache_hits cache_misses trace_s lower_s compile_s", file=out)
    rest = collections.Counter()
    for k, (prog, r) in enumerate(rows):
        if k >= top:
            rest.update(r)
            continue
        print(f"  {prog} {r['traces']} {r['lowerings']} {r['compiles']} "
              f"{r['cache_hits']} {r['cache_misses']} {r['trace_s']:.4f} "
              f"{r['lower_s']:.4f} {r['compile_s']:.4f}", file=out)
    if rest:
        print(f"  ({len(rows) - top} more) {rest['traces']} "
              f"{rest['lowerings']} {rest['compiles']} {rest['cache_hits']} "
              f"{rest['cache_misses']} {rest['trace_s']:.4f} "
              f"{rest['lower_s']:.4f} {rest['compile_s']:.4f}", file=out)


def print_longest_round(spans: Sequence, out=None) -> None:
    out = out or sys.stderr
    r = longest_round(spans, children(spans))
    if r is not None:
        print(f"longest scheduler.round: {r['ms']:.3f} ms, host "
              f"{r['host_ms']:.3f} ms, {r['attrs']}; children (ms, host "
              f"ms): " + ", ".join(f"{n} {d:.3f} {h:.3f}"
                                   for n, d, h in r["children"]), file=out)


def attribution(xplane: str, spans: Sequence, window_s: float,
                out=None) -> dict:
    """Clock agreement and device idle by program span, printed."""
    from bench import trace as trace_mod

    out = out or sys.stderr
    ops, traced = load_trace(xplane)
    got = clock_offset(spans, traced)
    if not ops or got is None:
        print("attribution: no device ops or no shared spans", file=out)
        return {}
    offset, worst, pairs = got
    busy = trace_mod._union([(s, e) for _, s, e in ops[min(ops)]])
    idle = idle_by_span(busy, spans, offset)
    total = sum(idle.values())
    build = sum(v for k, v in idle.items() if k.startswith("build."))
    print(f"clock: offset {offset} ns over {pairs} spans, largest "
          f"disagreement {worst / 1e6:.4f} ms", file=out)
    print(f"device idle between ops: {total:.3f} s; in build.* spans "
          f"{build:.3f} s ({100 * build / window_s:.2f} % of the window); "
          f"unattributed {idle.get('unattributed', 0.0):.3f} s "
          f"({100 * idle.get('unattributed', 0.0) / max(total, 1e-12):.2f}"
          f" % of idle)", file=out)
    by_name = sorted(idle.items(), key=lambda kv: -kv[1])
    print("device idle by span: " + ", ".join(
        f"{k} {v:.4f}" for k, v in by_name[:25]), file=out)
    return {"offset_ns": offset, "worst_ms": worst / 1e6, "pairs": pairs,
            "idle_s": total, "build_idle_s": build,
            "build_idle_share": 100 * build / window_s,
            "unattributed_s": idle.get("unattributed", 0.0)}


def main(argv=None) -> int:
    """``bench/run.py`` with the profile kept until the attribution above
    is printed (``--spans-only``: ``--trace 0`` with the spans on)."""
    import argparse
    import glob
    import os
    import shutil

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans-only", action="store_true")
    args, rest = ap.parse_known_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "src")]
    from bench import harness, program_trace, run
    from repro.core import guard

    if args.spans_only:
        guard.tracing(True)
        return run.main(rest + ["--trace", "0"])
    emit, cleanup = harness.emit, harness.ProfilerWindow.cleanup

    def emit_attributed(result, checks):
        found = glob.glob(os.path.join(root, ".bench_trace", "**",
                                       "*.xplane.pb"), recursive=True)
        # the readers took the spans in the imported module, not here
        spans = program_trace.taken
        if found and spans:
            result["attribution"] = attribution(
                found[0], spans, result["device"]["window_s"])
        shutil.rmtree(os.path.join(root, ".bench_trace"), ignore_errors=True)
        emit(result, checks)

    # the profile outlives the reduction, for the attribution
    harness.ProfilerWindow.cleanup = lambda self: None
    harness.emit = emit_attributed
    try:
        return run.main(rest + ["--trace", "1"])
    finally:
        harness.emit, harness.ProfilerWindow.cleanup = emit, cleanup


if __name__ == "__main__":
    sys.exit(main())

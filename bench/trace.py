"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics and the ``breakdown`` read.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the chips traced;
* kernel seconds: the summed durations of the device operations whose
  name holds a kernel's name;
* top device operations: summed duration by operation name;
* idle gaps: the gaps between busy intervals on the first chip, each
  labelled by the innermost benchmark host span (``bench.<layer>``) that
  covers its midpoint, or ``no-span``.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, Iterable, List, Sequence, Tuple

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _op_name(name: str) -> str:
    """A device event's operation name: the instruction's name where the
    event carries the whole HLO text (``%while.5 = (...) while(...)``)."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(path: str):
    """Device op events per chip and the benchmark's host spans of the
    trace at ``path``: ({chip: [(op name, start_ns, end_ns)]},
    [(span name, start_ns, end_ns)])."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Tuple[str, int, int]]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (_op_name(ev.name), int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    (ev.name[len(SPAN_PREFIX):], int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX))
    return ops, spans


def reduce(ops: Dict[int, Sequence[Tuple[str, int, int]]],
           spans: Sequence[Tuple[str, int, int]],
           kernels: Iterable[str] = (), top: int = 10) -> dict:
    """The trace's numbers; times in seconds."""
    kernels = tuple(kernels)
    if not ops:
        return {"chips": 0, "busy_s": 0.0, "kernel_s": {},
                "kernel_calls": {}, "device_ops": [], "idle_gaps": []}
    busy = {}
    for chip, evs in ops.items():
        busy[chip] = _union([(s, e) for _, s, e in evs])
    busy_s = sum(sum(e - s for s, e in iv) for iv in busy.values()) \
        / len(busy) / 1e9
    kernel_s = collections.Counter()
    kernel_calls = collections.Counter()
    by_op = collections.Counter()
    for evs in ops.values():
        for op, s, e in evs:
            by_op[op] += (e - s) / 1e9
            for k in kernels:
                if k in op:
                    kernel_s[k] += (e - s) / 1e9
                    kernel_calls[k] += 1
    first = busy[min(busy)]
    gaps = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                   in zip(first, first[1:])), reverse=True)[:top]
    idle = []
    for length, e0, s1 in gaps:
        mid = (e0 + s1) / 2
        covering = [(se - ss, name) for name, ss, se in spans
                    if ss <= mid <= se]
        idle.append([min(covering)[1] if covering else "no-span",
                     length / 1e9])
    return {
        "chips": len(busy),
        "busy_s": busy_s,
        "kernel_s": dict(kernel_s),
        "kernel_calls": dict(kernel_calls),
        "device_ops": [[n, s] for n, s in by_op.most_common(top)],
        "idle_gaps": idle,
    }

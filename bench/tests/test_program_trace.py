"""``bench/program_trace.py``: host time, the build table, the clock offset
and device idle by program span on synthetic span and operation lists; the
four readers of the program's spans, on a program that records them and on
one that does not; and a traced tiny run of each cell on the CPU, whose
result line carries them."""
import types

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on the path)

from bench import harness, program_trace as pt

MS = 1_000_000

# (name, start, end, parent, attrs), ns
SPANS = [
    ("rl.step", 0, 100 * MS, -1, {}),                       # 0
    ("sampler.round", 10 * MS, 60 * MS, 0, {"rows": 4}),    # 1
    ("engine.decode", 12 * MS, 50 * MS, 1, {}),             # 2
    ("build.decode_fn.trace", 13 * MS, 20 * MS, 2, {}),     # 3
    ("build.decode_fn.lower", 20 * MS, 25 * MS, 2, {}),     # 4
    ("build.decode_fn.compile", 25 * MS, 30 * MS, 2,
     {"cache_hits": 1, "cache_misses": 0}),                 # 5
    ("transfer.decode-segment", 32 * MS, 48 * MS, 2, {}),   # 6
    ("build.add.trace", 14 * MS, 15 * MS, 2, {}),           # 7
]


def test_host_time():
    kids = pt.children(SPANS)
    assert kids[0] == [1] and kids[1] == [2] and kids[2] == [3, 4, 5, 6, 7]
    assert pt.host_ns(SPANS, kids, 2) == 38 * MS - 17 * MS - 16 * MS
    # a round's host time leaves out builds and transfers at any depth
    assert pt.host_ns(SPANS, kids, 1) == 50 * MS - 17 * MS - 16 * MS
    assert pt.host_ns(SPANS, kids, 0) == 100 * MS - 33 * MS
    assert pt.union_ns([(0, 5), (3, 8), (10, 12), (11, 11)]) == 10


def test_build_table():
    t = pt.build_table(SPANS)
    assert set(t) == {"decode_fn", "add"}
    d = t["decode_fn"]
    assert (d["traces"], d["lowerings"], d["compiles"]) == (1, 1, 1)
    assert (d["cache_hits"], d["cache_misses"]) == (1, 0)
    assert d["trace_s"] == pytest.approx(0.007)
    assert d["compile_s"] == pytest.approx(0.005)


def test_percentile_is_nearest_rank():
    vals = list(range(1, 201))
    assert pt.percentile(vals, 99) == 198
    assert pt.percentile([3.0], 99) == 3.0


def test_clock_offset_and_idle_attribution():
    off = 7 * MS
    jitter = {0: 0, 1: 200_000, 2: -100_000, 6: 50_000}
    traced = [(SPANS[i][0], SPANS[i][1] + off + j, SPANS[i][2] + off + j)
              for i, j in jitter.items()]
    got = pt.clock_offset(SPANS, traced)
    assert got is not None
    offset, worst, pairs = got
    assert pairs == 4
    assert offset == off + 25_000               # the median of the starts
    assert worst == 175_000
    # busy on the trace clock: before the step, inside the decode, in the
    # transfer, and after the step; the gaps are idle
    busy = [(0, 5 * MS), (off + 31 * MS, off + 40 * MS),
            (off + 120 * MS, off + 130 * MS)]
    idle = pt.idle_by_span(busy, SPANS, off)
    # gap 1: 5 ms .. off+31 ms; before the step opens (off+0) unattributed
    assert idle["unattributed"] == pytest.approx(0.002 + 0.020)
    assert idle["rl.step"] == pytest.approx(0.010 + 0.040)
    assert idle["sampler.round"] == pytest.approx(0.002 + 0.010)
    assert idle["engine.decode"] == pytest.approx(0.001 + 0.001 + 0.002)
    assert idle["build.add.trace"] == pytest.approx(0.001)
    assert idle["build.decode_fn.trace"] == pytest.approx(0.006)
    assert idle["build.decode_fn.lower"] == pytest.approx(0.005)
    assert idle["build.decode_fn.compile"] == pytest.approx(0.005)
    assert idle["transfer.decode-segment"] == pytest.approx(0.008)
    gaps = (off + 31 * MS - 5 * MS) + (off + 120 * MS - off - 40 * MS)
    assert sum(idle.values()) == pytest.approx(gaps / 1e9)


def _ctx(kind, spans, window_s=1.0):
    return types.SimpleNamespace(kind=kind, trace=None, window_s=window_s,
                                 program_spans=spans)


SERVE = [
    ("scheduler.round", 0, 10 * MS, -1, {}),
    ("scheduler.dispatch", 1 * MS, 9 * MS, 0, {"rows": 3, "bucket": 4}),
    ("transfer.serve-segment", 2 * MS, 8 * MS, 1, {}),
    ("scheduler.round", 10 * MS, 14 * MS, -1, {}),
    ("scheduler.dispatch", 10 * MS, 14 * MS, 3, {"rows": 1, "bucket": 4}),
]


def test_readers_on_the_programs_spans(capsys):
    rl = harness.read_metrics(
        ["program_builds.rl", "sampler_host_share.rl"],
        _ctx("rl", SPANS, window_s=0.1))
    assert rl["program_builds.rl"]["value"] == 1.0
    assert rl["sampler_host_share.rl"]["value"] == pytest.approx(17.0)
    serve = harness.read_metrics(
        ["round_host_ms_p99.serve", "row_occupancy.serve"],
        _ctx("serve", SERVE))
    assert serve["round_host_ms_p99.serve"]["value"] == pytest.approx(4.0)
    assert serve["row_occupancy.serve"]["value"] == pytest.approx(50.0)
    err = capsys.readouterr().err
    assert "decode_fn 1 1 1 1 0" in err
    assert "longest scheduler.round" in err


NEW = {"rl": ["program_builds.rl", "sampler_host_share.rl"],
       "serve": ["round_host_ms_p99.serve", "row_occupancy.serve"]}


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    from repro.core import guard

    monkeypatch.delattr(guard, "take_spans")
    for kind, names in NEW.items():
        ctx = types.SimpleNamespace(kind=kind, trace=None, window_s=1.0)
        assert harness.read_metrics(names, ctx) == {}


@pytest.mark.parametrize("cell,kind", [(bench_tiny.RL_CELL, "rl"),
                                       (bench_tiny.SERVE_CELL, "serve")])
def test_traced_tiny_run_reports_the_program_metrics(cell, kind):
    from repro.core import guard

    result, checks = bench_tiny.run_tiny(cell, trace=True)
    assert result["correct"], checks
    for name in NEW[kind]:
        assert name in result["metrics"], result["metrics"]
    assert guard.take_spans() == []         # the window's were taken


def test_load_trace_reads_a_recorded_chip_trace():
    """The recorded v5e trace of ``test_bench_trace.py``: its device ops
    load; it has host spans of the benchmark's but none of the program's,
    so there is no clock to share and nothing is attributed."""
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "small.xplane.pb")
    ops, traced = pt.load_trace(path)
    assert list(ops) == [0] and len(ops[0]) == 18
    assert traced == []
    assert pt.clock_offset(SPANS, traced) is None
    assert pt.union_ns((s, e) for _, s, e in ops[0]) == 946872

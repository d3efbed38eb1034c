"""The benchmark's files: every cell, configuration, traffic mix and
per-layer metric named in BENCHMARK.json loads by its name; the traffic is
a function of the seed; the window arithmetic; the peaks table; the
command's refusal to run without a TPU; the yardstick's independence from
the program."""
import ast
import json
import math
import os
import subprocess
import sys
import types

import pytest

import bench_tiny

ROOT = bench_tiny.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$"


def test_benchmark_json_keys():
    import re

    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w["name"] for w in BENCH["workloads"]]
    assert len(names) == len(set(names))
    assert all(re.match(NAME, n) for n in names)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_loads(cfg):
    from bench import model_config

    raw = model_config.load(cfg["name"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    assert set(cfg["reduced"]) == set(raw["published"])
    for key, value in raw["published"].items():
        assert raw[key] != value
    prog = model_config.program_config(raw)
    p = model_config.plain(raw)
    assert prog.d_model == p["d_model"] and prog.vocab_size == p["vocab_size"]
    assert prog.num_params() == pytest.approx(
        model_config.param_counts(p)["total"], rel=1e-3)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_load(cell):
    from bench import run
    from bench.traffic import generate

    got = run.load_cell(cell["name"])
    assert got["limits"] and all(v > 0 for v in got["limits"].values())
    assert generate.load(cell["traffic"])["kind"] in ("rl", "serve")
    assert cell["chips"] == 1
    assert any(m["name"] == "setup_s" for m in got["end_to_end"])
    assert len(got["end_to_end"]) >= 2 and got["per_layer"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_loads(metric):
    from bench import harness

    mod = harness.load_metric(metric["name"])
    assert mod.UNIT == metric["unit"]
    assert mod.LAYER == metric["layer"]
    assert mod.MOVES == metric["moves"]
    # a context with nothing to read gives nothing, never 0
    empty = types.SimpleNamespace(kind="none", trace=None)
    assert mod.read(empty) is None


def test_serve_traffic_is_a_function_of_the_seed():
    from bench.traffic import generate

    mix = generate.load("serve_shared_zipf")
    a = generate.serve_requests(mix, 2 ** 40 + 7, 20.0)
    b = generate.serve_requests(mix, 2 ** 40 + 7, 20.0)
    c = generate.serve_requests(mix, 2 ** 40 + 8, 20.0)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert [r["due"] for r in a] != [r["due"] for r in c]
    # the same work in another order: the sizes and the gaps between
    # arrivals do not depend on the seed
    sizes = lambda rs: sorted((len(r["prompt"]), r["max_new"]) for r in rs)
    assert sizes(a) == sizes(c)
    gaps = lambda rs: sorted(round(y["due"] - x["due"], 9)
                             for x, y in zip([{"due": 0.0}] + rs, rs))
    assert gaps(a) == gaps(c)
    # in another order, with the longest quarter spread over the window:
    # every 16 consecutive requests hold 2 to 6 of them
    total = lambda rs: [len(r["prompt"]) + r["max_new"] for r in rs]
    assert total(a) != total(c)
    cut = sorted(total(a))[-len(a) // 4]
    top = [t >= cut for t in total(a)]
    assert all(2 <= sum(top[i:i + 16]) <= 6 for i in range(len(top) - 15))
    assert len(a) == round(mix["rate_per_s"] * 20.0)
    assert all(0 <= r["due"] < 20.0 for r in a)


def test_rl_traffic_is_a_function_of_the_seed():
    from bench.traffic import generate

    mix = generate.load("rl_tree_math")
    get = lambda s: generate.math_prompts(
        mix, generate.run_rng(s, "prompts"), 4)
    assert get(5) == get(5) and get(5) != get(6)
    assert {len(p) for p, _ in get(5) + get(6)} == {mix["prompt_tokens"]}


def _imports(path):
    tree = ast.parse(open(path).read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""


@pytest.mark.parametrize("path", [
    "bench/traffic/generate.py", "bench/reference.py", "bench/compare.py",
    "bench/weights.py", "bench/peaks.py", "bench/trace.py"])
def test_yardstick_imports_nothing_of_the_program(path):
    mods = list(_imports(os.path.join(ROOT, path)))
    assert not [m for m in mods if m.split(".")[0] == "repro"], mods


def test_peaks_refuse_an_unknown_device():
    from bench.peaks import peaks_for

    assert peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99 imaginary")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", bench_tiny.RL_CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def _serve_res(ttft, tpot, window=10.0):
    reqs = [types.SimpleNamespace(out_tokens=[1, 2]) for _ in ttft]
    return {"window_s": window, "compile_s": 0.0, "spans": {},
            "requests": reqs, "ok": [i for i, t in enumerate(ttft)
                                     if t != math.inf],
            "ttft": ttft, "tpot": tpot, "queue": [0.0] * len(ttft),
            "radix_hit_tokens": 0, "prompt_tokens": 1}


def test_window_arithmetic():
    """The rate is the whole window over the steps done in it, the tail is
    over every request due in it, and a stall inside the window moves
    both."""
    from bench import metrics_context

    ctx = bench_tiny.context(bench_tiny.RL_CELL)
    dev = {"kind": "TPU v5 lite"}
    steps = [{"padded_token_fraction": 0.0}] * 4
    rl = {"window_s": 20.0, "elapsed": 20.0, "metrics": steps,
          "compile_s": 0.0, "spans": {}}
    base = metrics_context.build(ctx, rl, None, 1.0, dev)
    assert base.end_to_end["rl_step_s"] == 5.0
    stalled = metrics_context.build(ctx, dict(rl, elapsed=24.0), None, 1.0,
                                    dev)
    assert stalled.end_to_end["rl_step_s"] == 6.0

    sctx = bench_tiny.context(bench_tiny.SERVE_CELL)
    ttft = [0.1] * 95 + [0.2] * 5
    tpot = [0.01] * 100
    m = metrics_context.build(sctx, _serve_res(ttft, tpot), None, 1.0, dev)
    assert m.end_to_end["ttft_p95_ms"] == pytest.approx(100.0)
    # a stall that delays six requests moves the tail of all of them
    m2 = metrics_context.build(
        sctx, _serve_res([0.1] * 94 + [0.9] * 6, [0.01] * 94 + [0.05] * 6),
        None, 1.0, dev)
    assert m2.end_to_end["ttft_p95_ms"] == pytest.approx(900.0)
    assert m2.end_to_end["tpot_p95_ms"] == pytest.approx(50.0)
    # a request that never finished counts as missing every limit
    m3 = metrics_context.build(
        sctx, _serve_res([0.1] * 94 + [math.inf] * 6, tpot[:94] +
                         [math.inf] * 6), None, 1.0, dev)
    assert m3.end_to_end["ttft_p95_ms"] == math.inf

"""The program's tracing facility (repro.core.guard): spans that cost
nothing when off, their nesting, the per-program build counters, and the
invariant that tracing never reaches a program — the same HLO and the
same number of lowerings with tracing on and off."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import TrainConfig, TreeConfig
from repro.core import guard
from repro.core.engine import TreeEngine
from repro.core.scheduler import Request, Scheduler
from repro.kv.cache import ProgramTable
from repro.models.model import init_params
from repro.rl.packing import PackedRolloutBatch
from repro.rl.trainer import RLTrainer, TrainerMode

ENGINE_KW = dict(num_pages=512, page_size=16, max_slots=32, max_queries=16,
                 max_prompt_len=256)
TREE = TreeConfig(max_depth=4, segment_len=16, max_width=4, branch_factor=2,
                  init_divergence_low=2, init_divergence_high=2,
                  temperature=0.9)


@contextlib.contextmanager
def tracing(on: bool):
    guard.take_spans()
    prev = guard.tracing(on)
    try:
        yield
    finally:
        guard.tracing(prev)


def _trainer(seed=0, **train_kw):
    base = dict(batch_size=2, group_size=4, oversample_factor=2,
                max_resample_rounds=0, learning_rate=1e-3,
                reward_shaping=0.1, pack_sequences=True)
    base.update(train_kw)
    return RLTrainer(get_config("qwen2.5-7b", smoke=True),
                     TrainConfig(**base), TREE, TrainerMode.TREEPO,
                     seed=seed, engine_kwargs=ENGINE_KW, min_difficulty=1,
                     max_difficulty=1)


def test_tracing_off_records_nothing():
    with tracing(False):
        a = guard.span("engine.decode", rows=3)
        b = guard.span("rl.step")
        assert a is b and not a             # one shared, falsy no-op
        with a as got:
            got.set(rows=4)
        guard.annotated_transfer(jnp.ones(3), reason="t")
        jax.jit(lambda x: x * 5 - 2)(jnp.arange(3.0))   # a build, too
        assert guard.take_spans() == []


def test_span_nesting_and_attributes():
    x = jnp.ones(3)                         # built before recording
    with tracing(True):
        with guard.span("outer", k=1):
            with guard.span("inner.a") as a:
                a.set(x=2)
            with guard.span("inner.b"):
                guard.annotated_transfer(x, reason="pull")
        spans = guard.take_spans()
    assert [s.name for s in spans] == ["outer", "inner.a", "inner.b",
                                       "transfer.pull"]
    assert [s.parent for s in spans] == [-1, 0, 0, 2]
    assert spans[0].attrs == {"k": 1} and spans[1].attrs == {"x": 2}
    assert spans[3].attrs == {"to": "host"}
    o, a, b, t = spans
    assert o.start_ns <= a.start_ns <= a.end_ns <= b.start_ns
    assert b.start_ns <= t.start_ns <= t.end_ns <= b.end_ns <= o.end_ns
    assert guard.take_spans() == []         # the reader cleared the store


def test_builds_are_spans_under_the_open_span():
    fn = jax.jit(lambda x: x * 7 + 3)
    with tracing(True):
        with guard.span("caller"):
            fn(jnp.arange(5.0))
        spans = guard.take_spans()
    assert spans[0].name == "caller"
    phases = {s.name.rsplit(".", 1)[1] for s in spans[1:]
              if s.name.startswith("build.<lambda>.")}
    assert phases == {"trace", "lower", "compile"}
    assert all(s.parent == 0 for s in spans[1:])


def test_spanned_method_records_its_calls():
    asked = []

    class Host:
        @guard.spanned("host.work",
                       lambda self, n, **k: asked.append(n) or {"n": n})
        def work(self, n, *, scale=1):
            with guard.span("host.inner"):
                return n * scale

    with tracing(False):
        assert Host().work(3, scale=2) == 6
        assert asked == [] and guard.take_spans() == []   # attrs not built
    with tracing(True):
        assert Host().work(4) == 4
        spans = guard.take_spans()
    assert [(s.name, s.parent, s.attrs) for s in spans] == [
        ("host.work", -1, {"n": 4}), ("host.inner", 0, {})]
    assert Host.work.__name__ == "work"


def test_a_profiler_session_starts_the_store_afresh(monkeypatch):
    """Spans switched on by a profiler session alone never carry over
    into the next session, though no reader took them."""
    profiling = [True]
    monkeypatch.setattr(guard, "_profiling", lambda: profiling[0])
    with tracing(False):
        with guard.span("first"):
            pass
        profiling[0] = False
        assert not guard.span("between")    # the session has ended
        profiling[0] = True
        with guard.span("second"):
            pass
        profiling[0] = False
        assert [s.name for s in guard.take_spans()] == ["second"]


def test_a_full_store_keeps_no_more(monkeypatch):
    monkeypatch.setattr(guard, "MAX_SPANS", 3)
    with tracing(True):
        with pytest.warns(RuntimeWarning, match="dropped") as warned:
            for k in range(5):
                with guard.span(f"s{k}"):
                    pass
        assert len(warned) == 1
        assert [s.name for s in guard.take_spans()] == ["s0", "s1", "s2"]
        with guard.span("after"):             # a reader emptied it
            pass
        assert [s.name for s in guard.take_spans()] == ["after"]


def test_build_counters_name_the_engine_programs():
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    before = guard.build_counts()
    count0 = guard.compile_count()
    eng = TreeEngine(params, cfg, TREE, **ENGINE_KW)
    paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
    eng.decode_segments(paths)
    after = guard.build_counts()
    zero = guard.ProgramBuilds()
    for name in ("prefill_fn", "decode_fn"):
        got, was = after[name], before.get(name, zero)
        assert got.traces - was.traces >= 1
        assert got.lowerings - was.lowerings == 1    # a fresh engine's jit
        assert got.compiles - was.compiles == 1
        assert got.lower_s > was.lower_s
    # compile_count is the sum of the programs' backend compiles
    grown = sum(v.compiles - before.get(k, zero).compiles
                for k, v in after.items())
    assert guard.compile_count() - count0 == grown


def _capture(obj, getter: str, texts: list) -> None:
    """Record the lowered text (locations included) of every program the
    ``getter`` hands out before it runs."""
    orig = getattr(obj, getter)

    def get(*key):
        fn = orig(*key)

        def call(*args):
            texts.append(fn.lower(*args).as_text(debug_info=True))
            return fn(*args)
        return call

    setattr(obj, getter, get)


def _program_texts() -> dict:
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, TREE, seed=0, **ENGINE_KW)
    texts = {"prefill": [], "decode": [], "serve": [], "update": []}
    for kind in ("prefill", "decode", "serve"):
        _capture(eng.runner, f"get_{kind}_fn", texts[kind])
    paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
    eng.decode_segments(paths)
    sched = Scheduler(eng, max_running=2, seg_len=8, radix=False)
    sched.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=2))
    sched.step()
    tr = _trainer()
    _capture(tr, "_get_packed_update_fn", texts["update"])
    N, L, S = 2, 64, 2
    rng = np.random.default_rng(0)
    tr.update_packed(PackedRolloutBatch(
        tokens=rng.integers(1, 200, (N, L)).astype(np.int32),
        logprobs_old=np.full((N, L), -1.0, np.float32),
        seg_prompt_lens=np.full((N, S), 4, np.int32),
        seg_resp_lens=np.full((N, S), 8, np.int32),
        seg_adv=np.ones((N, S), np.float32),
        seg_rewards=np.ones((N, S), np.float32),
        num_queries=1, num_trajectories=N * S, mean_response_len=8.0,
        leaf_rate=1.0, host_pack_bytes=0, padded_rows=N))
    return texts


def test_programs_identical_with_tracing_on_and_off():
    texts, spans = {}, {}
    # one call site for both: locations carry the caller's frames
    for on in (False, True):
        with tracing(on):
            texts[on] = _program_texts()
            spans[on] = guard.take_spans()
    assert spans[False] == []
    assert {s.name for s in spans[True]} >= {
        "engine.prefill", "engine.decode", "scheduler.round",
        "scheduler.dispatch", "transfer.update-metrics"}
    for kind in ("prefill", "decode", "serve", "update"):
        assert texts[False][kind], kind
        assert texts[True][kind] == texts[False][kind], kind


def _lowerings() -> int:
    return sum(v.lowerings for v in guard.build_counts().values())


def test_same_lowerings_per_train_step_with_tracing_on_and_off():
    tr = _trainer(seed=2)
    tr.train_step()                         # builds what every step shares
    with tracing(False):
        n0 = _lowerings()
        tr.programs = ProgramTable()        # the step builds its programs
        tr.train_step()
        off = _lowerings() - n0
    with tracing(True):
        n0 = _lowerings()
        tr.programs = ProgramTable()
        tr.train_step()
        on = _lowerings() - n0
        spans = guard.take_spans()
    assert off == on > 0                    # each step's engine programs
    names = [s.name for s in spans]
    for name in ("rl.step", "rl.engine_build", "rl.pack", "rl.update",
                 "sampler.prefill", "sampler.round", "engine.decode",
                 "engine.fork"):
        assert name in names, name
    assert sum(1 for s in spans
               if s.name.startswith("build.") and s.name.endswith(".lower")
               ) == on
    step = names.index("rl.step")
    assert spans[step].parent == -1
    assert all(s.parent >= 0 for i, s in enumerate(spans) if i != step)
    rounds = [s for s in spans if s.name == "sampler.round"]
    assert all(s.attrs["rows"] >= 1 for s in rounds)


def test_serve_report_counts_rows():
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, TREE, **ENGINE_KW)
    sched = Scheduler(eng, max_running=4, seg_len=8)
    for rid in range(3):
        sched.submit(Request(rid=rid, prompt=[1 + rid, 2, 3],
                             max_new_tokens=12))
    with tracing(True):
        while sched.waiting or sched.running:
            sched.step()
        spans = guard.take_spans()
    rep = sched.report
    rounds = [s for s in spans if s.name == "scheduler.round"]
    assert len(rounds) == sched.round
    assert rep.rows_dispatched == 4 * sched.round   # one compiled bucket
    assert rep.rows_real == sum(s.attrs["running"] for s in rounds)
    assert 0 < rep.rows_real < rep.rows_dispatched
    dispatch = [s.attrs for s in spans if s.name == "scheduler.dispatch"]
    assert sum(a["rows"] for a in dispatch) == rep.rows_real
    assert sum(a["bucket"] for a in dispatch) == rep.rows_dispatched
    admitted = [rid for s in spans if s.name == "scheduler.admit"
                for rid in s.attrs["rids"]]
    finished = [s.attrs["rid"] for s in spans
                if s.name == "scheduler.finish"]
    assert sorted(admitted) == sorted(finished) == [0, 1, 2]
    assert sum(s.attrs["admitted"] for s in rounds) == 3

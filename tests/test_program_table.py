"""The engine's program table (repro.kv.cache.ProgramTable): an RL
trainer's engines share their jitted programs across steps, so a warm step
builds nothing for a bucket an earlier step used; sharing changes no
program and no result; and the signature keeps apart engines whose
programs differ, while a kept program pins no engine."""
import dataclasses
import gc
import weakref

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import TrainConfig, TreeConfig
from repro.core import guard
from repro.core.engine import TreeEngine
from repro.core.scheduler import Request, Scheduler
from repro.kv.cache import ProgramTable, _build_fork_fn
from repro.models.model import init_params
from repro.rl.trainer import RLTrainer, TrainerMode

ENGINE_KW = dict(num_pages=512, page_size=16, max_slots=32, max_queries=16,
                 max_prompt_len=256)
TREE = TreeConfig(max_depth=4, segment_len=16, max_width=4, branch_factor=2,
                  init_divergence_low=2, init_divergence_high=2,
                  temperature=0.9)
ENGINE_PROGRAMS = {"prefill": "prefill_fn", "decode": "decode_fn",
                   "fork": "fork_fn"}
STEPS = 3


class _Recording(RLTrainer):
    """Keeps every engine it builds and every trajectory it samples."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        self.engines, self.trajectories = [], []

    def _make_engine(self):
        engine = super()._make_engine()
        self.engines.append(engine)
        return engine

    def rollout(self, num_queries, progress=0.0):
        trees, engine = super().rollout(num_queries, progress)
        self.trajectories.append(
            [(list(p.tokens), list(p.logprobs))
             for t in trees for p in t.finished])
        return trees, engine


class _PrivateTables(_Recording):
    """Each engine builds its own programs, as before the shared table."""

    def _make_engine(self):
        self.programs = ProgramTable()
        return super()._make_engine()


def _trainer(cls):
    tr = cls(get_config("qwen2.5-7b", smoke=True),
             TrainConfig(batch_size=2, group_size=4, oversample_factor=2,
                         max_resample_rounds=0, learning_rate=1e-3,
                         reward_shaping=0.1),
             TREE, TrainerMode.TREEPO, seed=3, engine_kwargs=ENGINE_KW,
             min_difficulty=1, max_difficulty=1)
    tr.bc_warmup(steps=15, batch_size=4, lr=3e-3)
    return tr


def _lowerings(before, after, name):
    was = before.get(name, guard.ProgramBuilds()).lowerings
    return after.get(name, guard.ProgramBuilds()).lowerings - was


def _entries(tr):
    sig = tr.engines[0].runner.signature
    kv_sig = tr.engines[0].kv.signature
    return {kind: tr.programs.entries(kv_sig if kind == "fork" else sig,
                                      kind)
            for kind in ENGINE_PROGRAMS}


@pytest.fixture(scope="module")
def shared():
    """Three steps of a trainer whose engines share the table, with each
    step's lowerings per program, its new entries per kind, and its
    table counts."""
    tr = _trainer(_Recording)
    steps, spans = [], None
    for i in range(STEPS):
        seen = {k: set(v) for k, v in _entries(tr).items()} if i else None
        before = guard.build_counts()
        hits, misses = tr.programs.hits, tr.programs.misses
        prev = guard.tracing(i == STEPS - 1)
        guard.take_spans()
        try:
            metrics = tr.train_step()
        finally:
            guard.tracing(prev)
        if i == STEPS - 1:
            spans = guard.take_spans()
        after = guard.build_counts()
        entries = _entries(tr)
        steps.append(dict(
            metrics=metrics, hits_before=hits, misses_before=misses,
            hits=tr.programs.hits - hits, misses=tr.programs.misses - misses,
            lowered={k: _lowerings(before, after, n)
                     for k, n in ENGINE_PROGRAMS.items()},
            new={k: (set(v) - seen[k]) if seen else set(v)
                 for k, v in entries.items()}))
    return tr, steps, spans


def test_warm_steps_build_no_bucket_already_seen(shared):
    tr, steps, _ = shared
    assert len(tr.engines) == STEPS
    first = steps[0]
    assert all(first["lowered"][k] == len(first["new"][k]) > 0
               for k in ENGINE_PROGRAMS)
    for step in steps[1:]:
        # a program lowers only for a bucket no earlier step used
        for kind in ENGINE_PROGRAMS:
            assert step["lowered"][kind] == len(step["new"][kind]), kind
        assert step["hits"] > 0
        assert step["misses"] == sum(len(v) for v in step["new"].values())
    # every miss built one program, lowered once: the counts agree
    assert tr.programs.misses == sum(
        s["lowered"][k] for s in steps for k in ENGINE_PROGRAMS)
    for kind, programs in _entries(tr).items():
        for fn in programs.values():
            assert guard.compile_cache_size(fn) in (1, -1)


def test_engines_of_every_step_hand_back_the_same_programs(shared):
    tr, _, _ = shared
    entries = _entries(tr)
    for eng in tr.engines:
        assert eng.runner.programs is tr.programs
        for b, fn in entries["decode"].items():
            assert eng.runner.get_decode_fn(*b) is fn
        for b, fn in entries["prefill"].items():
            assert eng.runner.get_prefill_fn(*b) is fn
        for b, fn in entries["fork"].items():
            assert eng.kv._get_fork_fn(*b) is fn
    assert len({id(e.kv) for e in tr.engines}) == STEPS   # fresh pools


def test_train_step_frees_each_rollout_pools(shared):
    tr, _, _ = shared
    for eng in tr.engines:
        assert eng.kv.kv_pools == {} and eng.kv.rec_state == {}
        assert eng.kv.pool.peak_in_use > 0 and eng.stats.decode_tokens > 0


def test_engine_build_span_carries_the_table_counts(shared):
    _, steps, spans = shared
    [build] = [s for s in spans if s.name == "rl.engine_build"]
    last = steps[-1]
    assert build.attrs == {"hits": last["hits_before"],
                           "misses": last["misses_before"]}


def test_shared_table_gives_the_private_tables_results_bitwise(shared):
    tr, steps, _ = shared
    ref = _trainer(_PrivateTables)
    ref_metrics = [ref.train_step() for _ in range(STEPS)]
    assert len({id(e.runner.programs) for e in ref.engines}) == STEPS
    assert tr.trajectories == ref.trajectories
    assert any(tr.trajectories)
    for got, want in zip((s["metrics"] for s in steps), ref_metrics):
        assert "loss" in got
        assert {k: v for k, v in got.items() if k != "wall_time"} == \
            {k: v for k, v in want.items() if k != "wall_time"}
    for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                    jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _capture(obj, getter, calls, kind):
    orig = getattr(obj, getter)

    def get(*bucket):
        fn = orig(*bucket)

        def call(*args):
            calls.append((kind, bucket, fn, args))
            return fn(*args)
        return call

    setattr(obj, getter, get)


def test_kept_programs_lower_as_freshly_built_ones():
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    table = ProgramTable()

    def run(eng):
        paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
        eng.decode_segments(paths)
        eng.fork_path(paths[0])
        sched = Scheduler(eng, max_running=2, seg_len=8, radix=False)
        sched.submit(Request(rid=0, prompt=[5, 6, 7], max_new_tokens=2))
        sched.step()

    run(TreeEngine(params, cfg, TREE, seed=0, programs=table, **ENGINE_KW))
    eng = TreeEngine(params, cfg, TREE, seed=1, programs=table, **ENGINE_KW)
    calls = []
    for kind in ("prefill", "decode", "serve"):
        _capture(eng.runner, f"get_{kind}_fn", calls, kind)
    _capture(eng.kv, "_get_fork_fn", calls, "fork")
    misses = table.misses
    run(eng)
    assert table.misses == misses           # every program from the table
    assert {c[0] for c in calls} == {"prefill", "decode", "serve", "fork"}
    fresh = {"prefill": lambda Q, Sp, *_: eng.runner._build_prefill(Q, Sp),
             "decode": eng.runner._build_decode,
             "serve": eng.runner._build_serve,
             "fork": _build_fork_fn}
    for kind, bucket, fn, args in calls:
        assert fn.lower(*args).as_text() == \
            fresh[kind](*bucket).lower(*args).as_text(), kind


def test_signature_keeps_differing_engines_apart():
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    table = ProgramTable()

    def engine(tree_cfg=TREE, **kw):
        eng = TreeEngine(params, cfg, tree_cfg, programs=table,
                         **dict(ENGINE_KW, **kw))
        paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
        eng.decode_segments(paths)
        eng.fork_path(paths[0])
        return eng

    base = engine()
    hotter = engine(dataclasses.replace(TREE, temperature=1.3))
    paged = engine(page_size=8)
    # the trainer's evaluate config: same sampling, another width; its
    # programs read nothing that differs, so it shares them
    wide = engine(dataclasses.replace(
        TREE, max_width=2, init_divergence_low=2, init_divergence_high=2,
        branch_factor=1, fallback=False))

    def programs(eng, kind):
        sig = eng.kv.signature if kind == "fork" else eng.runner.signature
        return table.entries(sig, kind)

    for other in (hotter, paged):
        assert other.runner.signature != base.runner.signature
        for kind in ("prefill", "decode"):
            mine, theirs = programs(base, kind), programs(other, kind)
            assert mine and theirs
            assert not {id(f) for f in mine.values()} & \
                {id(f) for f in theirs.values()}, kind
    assert paged.kv.signature != base.kv.signature
    assert not {id(f) for f in programs(base, "fork").values()} & \
        {id(f) for f in programs(paged, "fork").values()}
    assert wide.runner.signature == base.runner.signature
    assert programs(wide, "decode") == programs(base, "decode")


def test_a_kept_program_pins_no_engine():
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    table = ProgramTable()
    eng = TreeEngine(params, cfg, TREE, programs=table, **ENGINE_KW)
    paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
    eng.decode_segments(paths)
    eng.fork_path(paths[0])
    kv, runner = weakref.ref(eng.kv), weakref.ref(eng.runner)
    kept = len(table.entries(eng.runner.signature, "decode"))
    del eng, paths
    gc.collect()
    assert kv() is None and runner() is None
    assert kept > 0 and table.misses >= 3

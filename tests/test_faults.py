"""Deterministic fault-injection suite (docs/robustness.md).

Every robustness claim is *proven* here by injecting the fault and
asserting the degradation contract:

* KV pressure — injected and real page exhaustion are absorbed by
  preemption + throttled branching; a 50%-of-peak pool still completes
  with zero escaped ``OutOfPages``.
* Numeric quarantine — NaN decode/fork logits fail only the affected
  paths; a NaN-poisoned update batch skips the param update bitwise.
* Crash-safe resume — ``RLTrainer.state_dict`` checkpoints reproduce
  the uninterrupted run's remaining metrics stream and final params;
  a kill at any checkpoint-store kill point leaves the newest complete
  checkpoint loadable; the launch driver resumes its JSONL stream.

All tests carry the ``fault`` marker (``pytest -m fault``).
"""
import glob
import json
import os
import random

import jax
import numpy as np
import pytest

from repro.checkpoint import (latest_step, list_steps, load_checkpoint,
                              save_checkpoint)
from repro.configs import get_config
from repro.configs.base import TrainConfig, TreeConfig
from repro.core import branching as br
from repro.core import faults
from repro.core.engine import TreeEngine
from repro.core.faults import FaultInjector, InjectedCrash
from repro.core.lifecycle import lifecycle_guard
from repro.core.sampler import sample_trees
from repro.core.tree import Status
from repro.kv.cache import OutOfPages, PagePool
from repro.models.model import init_params
from repro.rl.trainer import RLTrainer, TrainerMode

pytestmark = pytest.mark.fault


@pytest.fixture(autouse=True)
def _lifecycle_tracker():
    """Every fault test runs with the runtime lifecycle tracker armed:
    any page/slot refcount or path-FSM violation under injected faults
    fails the test at teardown (docs/static_analysis.md, R5/R6 runtime
    twin)."""
    with lifecycle_guard() as rep:
        yield rep

ENGINE_KW = dict(num_pages=256, page_size=16, max_slots=32, max_queries=16,
                 max_prompt_len=128)
TREE_CFG = TreeConfig(max_depth=5, segment_len=16, max_width=8,
                      branch_factor=2, init_divergence_low=2,
                      init_divergence_high=2, temperature=0.9)


def _trainer(seed=0, engine_kwargs=None, tree_cfg=TREE_CFG, ppo_epochs=2):
    cfg = get_config("qwen2.5-7b", smoke=True)
    trc = TrainConfig(batch_size=2, group_size=tree_cfg.max_width,
                      oversample_factor=1, max_resample_rounds=0,
                      dynamic_sampling=False, learning_rate=1e-3,
                      ppo_epochs=ppo_epochs, reward_shaping=0.1)
    return RLTrainer(cfg, trc, tree_cfg, TrainerMode.TREEPO, seed=seed,
                     engine_kwargs=dict(engine_kwargs or ENGINE_KW),
                     min_difficulty=1, max_difficulty=2)


def _leaves(trees):
    return [p for t in trees for p in t.finished if p.status == Status.LEAF]


# ---------------------------------------------------------------------------
# injector mechanics
# ---------------------------------------------------------------------------

def test_injector_deterministic_and_scoped():
    def drive(fi):
        fired = []
        with fi:
            for _ in range(6):
                fired.append(faults.fires("page_pool.alloc"))
            a = np.ones((3, 4), np.float32)
            out = faults.corrupt_array("engine.decode_logprobs", a)
        return fired, out

    mk = lambda: (FaultInjector(seed=7)
                  .page_exhaustion(at_alloc=3, times=2)
                  .nan_logits(at_round=1, rows=(1,)))
    f1, o1 = drive(mk())
    f2, o2 = drive(mk())
    assert f1 == f2 == [False, False, True, True, False, False]
    np.testing.assert_array_equal(o1, o2)
    assert np.isnan(o1[1, 0]) and np.isfinite(o1[0]).all()
    # disarmed: helpers are identity no-ops
    assert faults.active() is None
    assert not faults.fires("page_pool.alloc")
    a = np.ones((2, 2), np.float32)
    assert faults.corrupt_array("engine.decode_logprobs", a) is a
    faults.kill_point("train.step")  # no raise


def test_injector_does_not_nest_and_disarms_on_error():
    with pytest.raises(RuntimeError, match="does not nest"):
        with FaultInjector():
            with FaultInjector():
                pass
    assert faults.active() is None  # outer __exit__ ran
    with pytest.raises(ValueError):
        with FaultInjector():
            raise ValueError("boom")
    assert faults.active() is None


# ---------------------------------------------------------------------------
# KV-pressure degradation
# ---------------------------------------------------------------------------

def test_injected_page_exhaustion_absorbed():
    """An injected mid-rollout OutOfPages triggers the pressure protocol
    (leaf-KV release + retry) instead of escaping the rollout."""
    tr = _trainer()
    with FaultInjector().page_exhaustion(at_alloc=40):
        trees, eng = tr.rollout(2)
    assert eng.stats.pressure_events >= 1
    assert sum(len(t.finished) for t in trees) > 0
    assert len(_leaves(trees)) > 0


def test_half_pool_completes_without_escape():
    """Acceptance: a seeded rollout with the pool capped at 50% of the
    measured nominal peak completes with zero escaped OutOfPages and a
    non-trivial share of kept trajectories."""
    nominal = _trainer(seed=0)
    trees0, eng0 = nominal.rollout(2)
    peak = eng0.kv.pool.peak_in_use
    n0 = sum(len(t.finished) for t in trees0)
    assert peak > 0 and n0 > 0

    half = _trainer(seed=0, engine_kwargs=dict(
        ENGINE_KW, num_pages=max(peak // 2, 1)))
    trees, eng = half.rollout(2)  # must not raise
    assert eng.kv.pool.peak_in_use <= max(peak // 2, 1)
    assert eng.stats.preempted_paths > 0  # degradation actually engaged
    kept = sum(len(t.finished) for t in trees)
    assert kept > 0 and len(_leaves(trees)) > 0
    # every path was accounted for: finished or explicitly preempted
    for t in trees:
        assert not t.active and not t.preempted


def test_throttle_budget_scales_with_pressure():
    tc = TreeConfig(kv_watermark_soft=0.8, kv_watermark_hard=0.95)
    assert br.pressure_scale(tc, 0.5) == 1.0
    assert br.pressure_scale(tc, 0.95) == 0.0
    mid = br.pressure_scale(tc, (0.8 + 0.95) / 2)
    assert 0.4 < mid < 0.6
    # continuations (one per active path) are never throttled
    assert br.throttle_budget(tc, 8, 3, 0.99) == 3
    assert br.throttle_budget(tc, 8, 3, 0.0) == 8
    off = TreeConfig(pressure_aware=False)
    assert br.pressure_scale(off, 0.99) == 1.0


def test_preempt_restore_roundtrip():
    """restore_path replays a preempted path's tokens into fresh pages
    and resumes with a sampled pending token."""
    cfg = get_config("yi-6b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, TREE_CFG, num_pages=64, page_size=8,
                     max_slots=8, max_queries=4, max_prompt_len=32, seed=0)
    prompt = [1, 2, 3, 4, 5]
    [root] = eng.prefill_queries([prompt])
    [res] = eng.decode_segments([root])
    tokens = prompt + list(res.tokens)
    in_use = eng.kv.pool.pages_in_use
    freed = eng.preempt_path(root)
    assert freed > 0 and eng.kv.pool.pages_in_use == in_use - freed
    assert eng.stats.preempted_paths == 1
    assert eng.can_restore
    path = eng.restore_path(tokens)
    assert path.position == len(tokens)
    assert eng.stats.regenerated_paths == 1
    # the restored context decodes exactly like a never-preempted one
    [res2] = eng.decode_segments([path])
    assert len(res2.tokens) > 0 and res2.finite


def test_serve_radix_eviction_before_preemption():
    """Injected page exhaustion mid-serve: the engine reclaims radix
    leaves (recomputable cache) before any live request is preempted
    (non-recomputable working set), and the serve run completes."""
    from repro.core.scheduler import Request, Scheduler
    from repro.data.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    cfg = get_config("qwen2.5-7b", smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, TREE_CFG, **ENGINE_KW)
    sched = Scheduler(eng, mode="continuous", max_running=4, base_seed=7)
    sys_prompt = "You are a helpful math assistant. Answer concisely."
    prompts = [tok.encode(sys_prompt + f" What is {i}+{i}?", bos=True)
               for i in range(4)]
    # wave 1 populates the radix (requests finish -> cache is sole owner)
    wave1 = [Request(rid=i, prompt=p, max_new_tokens=8)
             for i, p in enumerate(prompts[:2])]
    sched.run(wave1)
    assert sched.radix is not None and sched.radix.cached_pages > 0
    # wave 2 hits an injected allocator exhaustion mid-serve
    wave2 = [Request(rid=10 + i, prompt=p, max_new_tokens=8)
             for i, p in enumerate(prompts[2:])]
    with FaultInjector().page_exhaustion(at_alloc=2):
        report = sched.run(wave2)
    assert eng.stats.pressure_events >= 1          # the fault really fired
    assert sched.radix.evicted_pages > 0           # eviction kicked in...
    assert eng.stats.preempted_paths == 0          # ...before preemption
    assert report.finished == len(wave1) + len(wave2)   # cumulative report
    assert all(r.state == "finished" for r in wave2)
    sched.radix.evict(eng.kv.pool.num_pages)       # drain the cache


def test_out_of_pages_diagnostics():
    pool = PagePool(2)
    pool.alloc(), pool.alloc()
    with pytest.raises(OutOfPages) as ei:
        pool.alloc()
    assert "pages_in_use=2/2" in str(ei.value)
    # rollout-level annotation: a pool too small for even the prefill
    # escapes (nothing preemptible exists yet) but carries the forensics
    tr = _trainer(engine_kwargs=dict(ENGINE_KW, num_pages=2))
    with pytest.raises(OutOfPages) as ei:
        tr.rollout(2)
    msg = str(ei.value)
    assert "live_paths=" in msg and "per_query_pages=" in msg
    # serving annotation: pressure failures with a radix attached report
    # cache-held vs evictable pages, distinguishing them from path-held
    exc = OutOfPages("pool exhausted", pages_in_use=2, num_pages=2)
    exc.annotate(radix_pages=5, radix_evictable=3)
    assert "radix_pages=5(evictable 3)" in str(exc)


def test_allocator_interleaving_seeded():
    """Randomized (seeded) alloc/retain/release/preempt interleaving
    keeps refcounts consistent and drains back to an empty pool —
    the always-run twin of the hypothesis property in test_property.py."""
    rng = np.random.default_rng(123)
    pool = PagePool(32)
    tables = []  # simulated per-path page tables (shared via retain)
    for _ in range(400):
        op = rng.integers(4)
        if op == 0 and pool.num_free:
            tables.append([pool.alloc()])
        elif op == 1 and tables:  # fork: share every page
            src = tables[rng.integers(len(tables))]
            for pid in src:
                pool.retain(pid)
            tables.append(list(src))
        elif op == 2 and tables and pool.num_free:  # grow one table
            tables[rng.integers(len(tables))].append(pool.alloc())
        elif op == 3 and tables:  # preempt: drop a whole table
            tbl = tables.pop(rng.integers(len(tables)))
            for pid in tbl:
                pool.release(pid)
        assert (pool.refcount >= 0).all()
        held = {p for t in tables for p in t}
        assert set(np.nonzero(pool.refcount)[0]) == held
        assert pool.pages_in_use == len(held)
    for tbl in tables:
        for pid in tbl:
            pool.release(pid)
    assert pool.pages_in_use == 0 and pool.num_free == 32
    assert pool.peak_in_use > 0


# ---------------------------------------------------------------------------
# numeric quarantine
# ---------------------------------------------------------------------------

def test_nan_logits_quarantine_only_affected_paths():
    tr = _trainer()
    with FaultInjector().nan_logits(at_round=2, rows=(0,)):
        trees, eng = tr.rollout(2)
    bad = [p for t in trees for p in t.finished
           if p.finish_reason == "nonfinite"]
    ok = [p for t in trees for p in t.finished
          if p.finish_reason != "nonfinite"]
    assert eng.stats.quarantined_paths >= 1
    assert len(bad) >= 1
    assert all(p.status == Status.FAILED for p in bad)
    assert len(ok) > 0  # the tree survived the poisoned row
    for t in trees:
        assert not t.active


def test_nan_fork_logits_quarantine():
    tr = _trainer()
    with FaultInjector().nan_fork_logits(at_call=2, rows=(0,)):
        trees, eng = tr.rollout(2)
    assert eng.stats.quarantined_paths >= 1
    assert sum(len(t.finished) for t in trees) > 0
    for t in trees:
        assert not t.active


def test_nan_grads_skip_preserves_params_bitwise():
    tr = _trainer(ppo_epochs=2)
    before = jax.device_get(tr.params)
    opt_step = int(tr.opt_state.step)
    with FaultInjector().nan_grads(at_step=1):
        m = tr.train_step(num_queries=2)
    # every epoch of the poisoned batch is skipped and reported
    assert m["skipped_nonfinite"] == float(tr.train_cfg.ppo_epochs)
    after = jax.device_get(tr.params)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(tr.opt_state.step) == opt_step  # Adam state also reverted
    # the next, clean batch updates normally
    m2 = tr.train_step(num_queries=2)
    assert m2["skipped_nonfinite"] == 0.0
    assert int(tr.opt_state.step) == opt_step + tr.train_cfg.ppo_epochs


# ---------------------------------------------------------------------------
# crash-safe resume
# ---------------------------------------------------------------------------

def _params_equal(a, b, atol=0.0):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=atol)


def test_trainer_resume_reproduces_run(tmp_path):
    """4 uninterrupted steps == 2 steps + checkpoint + fresh-process
    restore + 2 steps: params within 1e-6, metrics stream identical."""
    ref = _trainer(seed=3)
    ref_metrics = [ref.train_step(num_queries=2) for _ in range(4)]

    half = _trainer(seed=3)
    for _ in range(2):
        half.train_step(num_queries=2)
    save_checkpoint(str(tmp_path), half.step, half.state_dict())

    fresh = _trainer(seed=3)
    fresh.train_step(num_queries=2)  # desync before restore, on purpose
    fresh.load_state_dict(load_checkpoint(str(tmp_path)))
    # the cursor truncates rows logged AFTER the checkpoint; a fresh
    # process (whose history lives in the JSONL file) just keeps its own
    assert fresh.step == 2 and len(fresh.metrics_log) <= 2
    resumed = [fresh.train_step(num_queries=2) for _ in range(2)]

    _params_equal(ref.params, fresh.params, atol=1e-6)
    for want, got in zip(ref_metrics[2:], resumed):
        assert want["step"] == got["step"]
        for k in ("reward_mean", "response_len", "num_trajectories"):
            assert want[k] == pytest.approx(got[k], abs=1e-9), k


def test_kill_during_save_keeps_latest_loadable(tmp_path):
    d = str(tmp_path)
    tree = {"w": np.arange(4, dtype=np.float32)}
    save_checkpoint(d, 1, tree)
    for point in ("ckpt.pre_write", "ckpt.pre_rename"):
        with pytest.raises(InjectedCrash):
            with FaultInjector().kill(point):
                save_checkpoint(d, 2, {"w": np.zeros(4, np.float32)})
        assert latest_step(d) == 1  # half-written step 2 is invisible
        out = load_checkpoint(d)
        np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
    # post-rename kill: step 2 IS complete, and the next save prunes
    # any stale tmp files left behind
    with pytest.raises(InjectedCrash):
        with FaultInjector().kill("ckpt.post_rename"):
            save_checkpoint(d, 2, {"w": np.ones(4, np.float32)})
    assert latest_step(d) == 2
    save_checkpoint(d, 3, tree, keep_last=2)
    assert list_steps(d) == [2, 3]
    assert not glob.glob(os.path.join(d, "*.tmp"))


def test_checkpoint_low_precision_roundtrip(tmp_path):
    """bf16 / fp8 arrays round-trip through the store (np.dtype alone
    rejects their names — the ml_dtypes fallback resolves them)."""
    jnp = pytest.importorskip("jax.numpy")
    tree = {
        "bf16": jnp.asarray([[1.5, -2.25], [0.125, 3.0]], jnp.bfloat16),
        "fp8": jnp.asarray([1.0, -0.5, 2.0], jnp.float8_e4m3fn),
        "f32": np.linspace(0, 1, 7, dtype=np.float32),
        "meta": {"step": 5, "tag": "x", "blob": b"\x00\x01",
                 "tup": (1, 2.5)},
    }
    save_checkpoint(str(tmp_path), 1, tree)
    out = load_checkpoint(str(tmp_path), 1)
    assert out["bf16"].dtype == jnp.bfloat16
    assert out["fp8"].dtype == jnp.float8_e4m3fn
    np.testing.assert_array_equal(
        np.asarray(out["bf16"], np.float32),
        np.asarray(tree["bf16"], np.float32))
    np.testing.assert_array_equal(
        np.asarray(out["fp8"], np.float32),
        np.asarray(tree["fp8"], np.float32))
    assert out["meta"] == tree["meta"]


def test_keep_last_pruning(tmp_path):
    d = str(tmp_path)
    for s in range(1, 6):
        save_checkpoint(d, s, {"s": np.asarray([s])}, keep_last=3)
    assert list_steps(d) == [3, 4, 5]
    assert latest_step(d) == 5


def test_launch_driver_crash_and_resume(tmp_path, monkeypatch, capsys):
    """In-process end-to-end: the driver is killed mid-run, then
    relaunched with --resume — the JSONL stream is contiguous, the
    post-resume rows carry ``resumed_from``, and the stream matches an
    uninterrupted run's."""
    from repro.launch import train as launch_train

    # main() turns on the persistent compile cache, in <repo>/.jax_cache
    # unless this is set: keep this test process's compiles out of the repo
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))

    def run(extra, ckpt, log):
        argv = ["train", "--arch", "qwen2.5-7b-smoke", "--mode", "treepo",
                "--steps", "4", "--bc-steps", "2", "--queries", "2",
                "--width", "4", "--depth", "3", "--segment", "16",
                "--seed", "5", "--eval-every", "100",
                "--ckpt-dir", ckpt, "--ckpt-interval", "1",
                "--log", log] + extra
        monkeypatch.setattr("sys.argv", argv)
        launch_train.main()

    ref_log = str(tmp_path / "ref.jsonl")
    run([], str(tmp_path / "ck_ref"), ref_log)

    crash_log = str(tmp_path / "crash.jsonl")
    ckpt = str(tmp_path / "ck")
    with pytest.raises(InjectedCrash):
        with FaultInjector().kill("train.step", at=3):
            run([], ckpt, crash_log)
    assert latest_step(ckpt) == 2
    run(["--resume"], ckpt, crash_log)

    ref_rows = [json.loads(l) for l in open(ref_log)]
    rows = [json.loads(l) for l in open(crash_log)]
    assert [r["step"] for r in rows] == [1, 2, 3, 4]
    assert "resumed_from" not in rows[0] and "resumed_from" not in rows[1]
    assert rows[2]["resumed_from"] == 2 and rows[3]["resumed_from"] == 2
    for want, got in zip(ref_rows, rows):
        assert want["step"] == got["step"]
        for k in ("reward_mean", "response_len", "num_trajectories"):
            assert want[k] == pytest.approx(got[k], abs=1e-9), k

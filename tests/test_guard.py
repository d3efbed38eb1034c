"""Runtime device-residency guard (repro.core.guard).

Unit half: annotated_transfer semantics, un-annotated transfer
interception in both directions, compile counting.  Integration half —
the PR's acceptance invariants:

* a warm decode round performs ZERO un-annotated transfers and ZERO
  recompilations;
* a 2-step train run keeps every bucketed jit at exactly one cached
  compilation (`one compile per (N, L) bucket`), with the whole warm
  step running under the armed guard.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import TrainConfig, TreeConfig
from repro.core.engine import TreeEngine
from repro.core.guard import (HotPathViolation, annotated_transfer,
                              compile_cache_size, compile_delta,
                              hot_path_guard)
from repro.models.model import init_params
from repro.rl.trainer import RLTrainer, TrainerMode

ENGINE_KW = dict(num_pages=512, page_size=16, max_slots=32, max_queries=16,
                 max_prompt_len=256)


def _trainer(seed=0, **train_kw):
    cfg = get_config("qwen2.5-7b", smoke=True)
    tc = TreeConfig(max_depth=4, segment_len=16, max_width=4,
                    branch_factor=2, init_divergence_low=2,
                    init_divergence_high=2, temperature=0.9)
    base = dict(batch_size=2, group_size=4, oversample_factor=2,
                max_resample_rounds=0, learning_rate=1e-3,
                reward_shaping=0.1)
    base.update(train_kw)
    return RLTrainer(cfg, TrainConfig(**base), tc, TrainerMode.TREEPO,
                     seed=seed, engine_kwargs=ENGINE_KW,
                     min_difficulty=1, max_difficulty=1)


# ---------------------------------------------------------------------------
# annotated_transfer
# ---------------------------------------------------------------------------

def test_annotated_transfer_roundtrip():
    x = jnp.arange(8, dtype=jnp.int32)
    h = annotated_transfer(x, reason="t")
    assert isinstance(h, np.ndarray)
    np.testing.assert_array_equal(h, np.arange(8))
    d = annotated_transfer(h, to="device", reason="t")
    assert isinstance(d, jax.Array)


def test_annotated_transfer_pytree_and_none_leaves():
    tok = jnp.ones((2, 3)); lp = jnp.zeros((2,))
    a, b, c = annotated_transfer((tok, lp, None), reason="t")
    assert isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
    assert c is None


def test_annotated_transfer_rejects_bad_direction():
    with pytest.raises(ValueError):
        annotated_transfer(jnp.ones(2), to="sideways")


# ---------------------------------------------------------------------------
# violation interception
# ---------------------------------------------------------------------------

def test_guard_catches_raw_d2h_pull():
    x = jnp.arange(4)
    with hot_path_guard(use_transfer_guard=False,
                        raise_on_violation=False) as rep:
        np.asarray(x)
    assert len(rep.violations) == 1
    assert "device->host" in rep.violations[0]


def test_guard_catches_raw_h2d_push():
    host = np.ones((4,), np.float32)
    with hot_path_guard(use_transfer_guard=False,
                        raise_on_violation=False) as rep:
        jnp.asarray(host)
    assert len(rep.violations) == 1
    assert "host->device" in rep.violations[0]


def test_guard_raises_hot_path_violation():
    x = jnp.arange(4)
    with pytest.raises(HotPathViolation):
        with hot_path_guard(use_transfer_guard=False):
            jax.device_get(x)


def test_guard_allows_and_tallies_annotated_transfers():
    x = jnp.arange(16, dtype=jnp.int32)
    with hot_path_guard(use_transfer_guard=False) as rep:
        h = annotated_transfer(x, reason="pull")
        annotated_transfer(h, to="device", reason="push")
    assert rep.violations == []
    assert rep.annotated_reasons == {"pull": 1, "push": 1}
    assert rep.annotated_bytes == 2 * 16 * 4


def test_guard_removed_after_exit():
    with hot_path_guard(use_transfer_guard=False,
                        raise_on_violation=False):
        pass
    # outside the guard raw transfers are ordinary numpy/jax calls
    np.testing.assert_array_equal(np.asarray(jnp.arange(3)),
                                  np.arange(3))


def test_guards_nest_and_propagate():
    x = jnp.arange(4)
    with hot_path_guard(use_transfer_guard=False,
                        raise_on_violation=False) as outer:
        with hot_path_guard(use_transfer_guard=False,
                            raise_on_violation=False) as inner:
            np.asarray(x)
            annotated_transfer(x, reason="inner")
    assert len(inner.violations) == 1
    assert len(outer.violations) == 1       # surfaced to the outer guard
    assert outer.annotated_reasons == {"inner": 1}


# ---------------------------------------------------------------------------
# compile counting
# ---------------------------------------------------------------------------

def test_compile_delta_counts_fresh_jit_once():
    fn = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7, dtype=jnp.float32)
    with compile_delta() as d:
        fn(x)
    assert d() >= 1
    with compile_delta() as d:
        fn(x)                               # warm: cached trace
    assert d() == 0
    assert compile_cache_size(fn) in (1, -1)


# ---------------------------------------------------------------------------
# integration: the hot paths under an armed guard
# ---------------------------------------------------------------------------

def test_warm_decode_round_zero_transfers_zero_compiles():
    """The acceptance invariant for decoding: after one cold round, a
    same-bucket decode round performs no un-annotated transfer (the
    guard raises otherwise) and no recompilation, and its traffic goes
    through the annotated pack/pull doors."""
    cfg = get_config("qwen2.5-7b", smoke=True)
    tc = TreeConfig(max_depth=4, segment_len=16, max_width=4,
                    branch_factor=2, init_divergence_low=2,
                    init_divergence_high=2, temperature=0.9)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, tc, **ENGINE_KW)
    paths = eng.prefill_queries([[1, 2, 3, 4, 5], [6, 7, 8]])
    eng.decode_segments(paths)              # cold: compiles the bucket
    with hot_path_guard(use_transfer_guard=False) as rep:
        res = eng.decode_segments(paths)    # warm, same R bucket
    assert len(res) == 2
    assert rep.violations == []
    assert rep.compiles == 0
    assert "decode-pack" in rep.annotated_reasons
    assert "decode-segment" in rep.annotated_reasons
    for fn in eng.runner.programs.entries(eng.runner.signature,
                                          "decode").values():
        assert compile_cache_size(fn) in (1, -1)


def test_warm_fork_round_zero_transfers():
    """Fork application (COW page copies) ships its padded index pairs
    through the annotated door and compiles once per pad bucket."""
    cfg = get_config("qwen2.5-7b", smoke=True)
    tc = TreeConfig(max_depth=4, segment_len=16, max_width=4,
                    branch_factor=2, init_divergence_low=2,
                    init_divergence_high=2, temperature=0.9)
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = TreeEngine(params, cfg, tc, **ENGINE_KW)
    [root] = eng.prefill_queries([[1, 2, 3, 4, 5]])
    eng.fork_path(root)                     # cold
    with hot_path_guard(use_transfer_guard=False) as rep:
        child = eng.fork_path(root)         # warm, same pad bucket
    assert rep.violations == []
    assert "fork-tables" in rep.annotated_reasons
    for fn in eng.kv.programs.entries(eng.kv.signature, "fork").values():
        assert compile_cache_size(fn) in (1, -1)
    eng.release_path(child)


def test_two_step_train_run_one_compile_per_bucket():
    """The acceptance invariant for training: across 2 train_steps the
    second, warm step runs fully guarded (zero un-annotated transfers
    end to end: rollout -> advantage -> pack -> update -> metrics), and
    each bucketed update jit holds exactly one cached compilation."""
    tr = _trainer(seed=0)
    tr.bc_warmup(steps=15, batch_size=4, lr=3e-3)
    m1 = tr.train_step()                    # cold: compiles the buckets
    with hot_path_guard(use_transfer_guard=False) as rep:
        m2 = tr.train_step()                # warm step, armed guard
    assert rep.violations == []
    assert m1["step"] == 1 and m2["step"] == 2
    assert "loss" in m1 and "loss" in m2    # both steps really updated
    assert np.isfinite(m2["loss"])
    assert "decode-pack" in rep.annotated_reasons
    assert "advantage-pack" in rep.annotated_reasons
    assert "update-pack" in rep.annotated_reasons
    assert "update-metrics" in rep.annotated_reasons
    assert tr._update_fns                   # at least one (N, L) bucket
    for fn in tr._update_fns.values():
        assert compile_cache_size(fn) in (1, -1)


def test_bc_warmup_runs_under_guard():
    tr = _trainer(seed=1)
    tr.bc_warmup(steps=2, batch_size=4, lr=3e-3)     # cold
    with hot_path_guard(use_transfer_guard=False) as rep:
        m = tr.bc_warmup(steps=2, batch_size=4, lr=3e-3)
    assert rep.violations == []
    assert "bc-pack" in rep.annotated_reasons
    assert "bc-loss" in rep.annotated_reasons
    assert np.isfinite(m["bc_loss"])

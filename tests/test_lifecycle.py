"""Runtime lifecycle tracker (repro.core.lifecycle) — the dynamic twin
of the R5/R6 static rules in tools/analyze/verify.py.

Each test either drives a *clean* sequence (guard must stay silent) or
injects the exact defect class a rule covers (guard must name it)."""
import random

import jax
import pytest

from repro.configs import get_config
from repro.configs.base import TreeConfig
from repro.core.engine import TreeEngine
from repro.core.lifecycle import (LifecycleViolation, lifecycle_guard)
from repro.core.sampler import sample_trees
from repro.kv.cache import PagePool, SlotAllocator
from repro.models.model import init_params

TC = TreeConfig(max_depth=3, segment_len=8, max_width=3, branch_factor=2,
                init_divergence_low=2, init_divergence_high=2,
                temperature=1.0)


def _engine(arch="yi-6b", **kw):
    cfg = get_config(arch, smoke=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kwargs = dict(num_pages=256, page_size=8, max_slots=16, max_queries=4,
                  max_prompt_len=32, seed=0)
    kwargs.update(kw)
    return TreeEngine(params, cfg, TC, **kwargs)


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------

def test_clean_pool_sequence_is_silent():
    pool = PagePool(num_pages=8)
    with lifecycle_guard() as rep:
        a = pool.alloc()
        b = pool.alloc()
        pool.retain(a)
        pool.release(a)
        pool.release(a)
        pool.release(b)
    assert rep.violations == []
    assert rep.page_allocs == 2
    assert rep.page_retains == 1
    assert rep.page_releases == 3
    assert pool.pages_in_use == 0


def test_page_double_release_is_reported():
    pool = PagePool(num_pages=4)
    with lifecycle_guard(raise_on_violation=False) as rep:
        pid = pool.alloc()
        pool.release(pid)
        # the pool's own assert still fires; the guard reports first
        with pytest.raises(AssertionError):
            pool.release(pid)
    assert any("double release" in v for v in rep.violations)


def test_retain_after_free_is_reported():
    pool = PagePool(num_pages=4)
    with lifecycle_guard(raise_on_violation=False) as rep:
        pid = pool.alloc()
        pool.release(pid)
        with pytest.raises(AssertionError):
            pool.retain(pid)
    assert any("retain" in v and "no live refcount" in v
               for v in rep.violations)


def test_violations_raise_at_guard_exit():
    pool = PagePool(num_pages=4)
    with pytest.raises(LifecycleViolation, match="double release"):
        with lifecycle_guard():
            pid = pool.alloc()
            pool.release(pid)
            try:
                pool.release(pid)
            except AssertionError:
                pass


def test_pool_created_before_arming_is_snapshotted():
    pool = PagePool(num_pages=8)
    held = pool.alloc()      # pre-existing refcount, e.g. the garbage page
    with lifecycle_guard() as rep:
        pid = pool.alloc()
        pool.retain(held)    # legal: snapshot saw the live refcount
        pool.release(held)
        pool.release(pid)
    assert rep.violations == []
    pool.release(held)


# ---------------------------------------------------------------------------
# slots — SlotAllocator has *no* native refcounts: a double release
# silently hands one slot to two paths.  Only the guard catches it.
# ---------------------------------------------------------------------------

def test_slot_double_release_is_reported():
    slots = SlotAllocator(num_slots=4)
    with lifecycle_guard(raise_on_violation=False) as rep:
        s = slots.alloc()
        slots.release(s)
        slots.release(s)     # native code is happy to corrupt the list
    assert any("double release of slot" in v for v in rep.violations)


def test_clean_slot_churn_is_silent():
    slots = SlotAllocator(num_slots=4)
    with lifecycle_guard() as rep:
        for _ in range(8):
            a, b = slots.alloc(), slots.alloc()
            slots.release(b)
            slots.release(a)
    assert rep.violations == []
    assert rep.slot_allocs == 16 and rep.slot_releases == 16


def test_short_lived_pools_are_silent():
    """Each engine's pool and slot allocator live one rollout; a freed
    one's id must not hand its shadow to the next one's."""
    with lifecycle_guard() as rep:
        for _ in range(16):
            pool, slots = PagePool(num_pages=4), SlotAllocator(num_slots=4)
            pool.alloc()
            slots.alloc()
    assert rep.violations == []
    assert rep.page_allocs == 16 and rep.slot_allocs == 16


# ---------------------------------------------------------------------------
# path FSM
# ---------------------------------------------------------------------------

def test_fork_of_released_path_is_reported():
    eng = _engine()
    with lifecycle_guard(raise_on_violation=False) as rep:
        [root] = eng.prefill_queries([[1, 2, 3, 4, 5]])
        eng.release_path(root)
        try:
            eng.fork_paths([root])
        except Exception:
            pass
    assert any("fork_paths on a released path" in v for v in rep.violations)


def test_decode_of_released_path_is_reported():
    eng = _engine()
    with lifecycle_guard(raise_on_violation=False) as rep:
        [root] = eng.prefill_queries([[1, 2, 3, 4, 5]])
        eng.preempt_path(root)
        try:
            eng.decode_segments([root])
        except Exception:
            pass
    assert any("decode_segments on a released path" in v
               for v in rep.violations)


def test_engine_fork_release_cycle_is_silent():
    eng = _engine()
    baseline = eng.kv.pool.pages_in_use   # garbage page etc.
    with lifecycle_guard() as rep:
        [root] = eng.prefill_queries([[1, 2, 3, 4, 5]])
        kids = eng.fork_paths([root])
        eng.decode_segments([root] + kids)
        child = eng.fork_from_prefix(root, 3, [1, 2, 3])
        for p in kids + [child]:
            eng.release_path(p)
        eng.preempt_path(root)
        restored = eng.restore_path([1, 2, 3, 4, 5])
        eng.release_path(restored)
    assert rep.violations == []
    assert rep.forks >= 1 and rep.preempts == 1 and rep.restores == 1
    assert eng.kv.pool.pages_in_use == baseline


@pytest.mark.parametrize("fused_kv", [True, False])
def test_fork_copy_failure_rolls_back_cleanly(fused_kv):
    """Satellite (K/V COW desync): a failure inside the jitted
    ``kv.apply_forks`` dispatch must not leave half-applied fork state.

    On device the copy is atomic by construction — the fused pool ships
    K and V in one array (a child can never hold copied K with stale V),
    and even on the legacy split path the pools are only rebound after
    the jitted fn returns.  What CAN leak is host state: the round's
    fresh COW pages, slots and table retains.  ``fork_paths`` must hand
    those back via ``release_partial`` and leave the parent decodable."""
    from repro.core.faults import FaultInjector
    from repro.kv.cache import OutOfPages

    eng = _engine(fused_kv=fused_kv)
    with lifecycle_guard() as rep:
        # 5 tokens, page_size 8 → partial tail page → fork must COW,
        # so apply_forks is guaranteed to run (and to be killed)
        [root] = eng.prefill_queries([[1, 2, 3, 4, 5]])
        baseline = eng.kv.pool.pages_in_use
        root_table = list(root.table)
        with FaultInjector(seed=0).on("kv.apply_forks", at=1):
            with pytest.raises(OutOfPages, match="injected"):
                eng.fork_paths([root])
        # full rollback: every COW page / table retain is back in the pool
        assert eng.kv.pool.pages_in_use == baseline
        # parent untouched and still usable: fork + decode succeed
        assert root.table == root_table and not root.released
        kids = eng.fork_paths([root])
        eng.decode_segments([root] + kids)
        for k in kids:
            eng.release_path(k)
        eng.release_path(root)
    assert rep.violations == []


def test_sampler_end_to_end_under_guard():
    """A full tree-sampling round must satisfy every runtime invariant."""
    eng = _engine()
    with lifecycle_guard() as rep:
        trees, _ = sample_trees(eng, [[1, 2, 3, 4, 5, 6, 7]], ["x"],
                                rng=random.Random(1))
    assert trees[0].num_trajectories >= 1
    assert rep.violations == []
    assert rep.page_allocs > 0 and rep.page_releases > 0


def test_guard_unpatches_on_exit():
    before = PagePool.alloc
    with lifecycle_guard():
        assert PagePool.alloc is not before
        with lifecycle_guard():     # nesting refcounts, no double patch
            pass
        assert PagePool.alloc is not before
    assert PagePool.alloc is before

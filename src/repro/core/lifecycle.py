"""Debug-armed runtime twin of the R5/R6 static verification rules.

``tools/analyze/verify.py`` proves page/slot lifecycle and path-FSM
invariants *statically*, per function, over the CFG.  This module
asserts the same invariants *dynamically*, across functions, by
shadowing the allocators under a context manager — the two halves
cross-validate: a static false negative (interprocedural leak, alias
the CFG cannot see) trips the runtime tracker under the fault-injection
suite, and a runtime miss (path never exercised) is exactly what the
static rules cover.  Same pattern as ``repro.core.guard`` is to R1/R2.

Usage (tests; zero overhead when not armed)::

    with lifecycle_guard() as rep:
        ... engine / sampler code ...
    assert rep.violations == []

Tracked invariants:

* **refcount conservation** — the shadow refcount (replayed from
  alloc/retain/release events) must equal the pool's at every step;
  release-at-zero (double release) and retain-after-free are violations
  at the offending call site.
* **free-list integrity** — no duplicates, never a page with a live
  refcount, ``pages_in_use`` consistent with the shadow.
* **slot double-release** — ``SlotAllocator`` keeps no refcounts, so a
  double release silently hands the same slot to two paths; the shadow
  free-set catches it.
* **path FSM** — released paths must not be forked from, decoded, or
  preempted again; ``preempt_path`` must leave the path released.
"""
from __future__ import annotations

import dataclasses
import threading
import traceback
from contextlib import contextmanager
from typing import Callable, Dict, List, Optional, Set, Tuple

from repro.kv.cache import PagePool, SlotAllocator

__all__ = ["LifecycleViolation", "LifecycleReport", "lifecycle_guard"]


class LifecycleViolation(RuntimeError):
    """A dynamic refcount / path-FSM invariant was broken."""


_tls = threading.local()


def _state() -> dict:
    if not hasattr(_tls, "state"):
        _tls.state = {"guard": None}
    return _tls.state


def _call_site() -> str:
    """First stack frame outside this module / the allocators."""
    for frame in reversed(traceback.extract_stack()):
        fn = frame.filename
        if "lifecycle.py" in fn or "kv/cache.py" in fn \
                or "traceback" in fn:
            continue
        return f"{fn}:{frame.lineno} in {frame.name}"
    return "<unknown>"


@dataclasses.dataclass
class LifecycleReport:
    violations: List[str] = dataclasses.field(default_factory=list)
    page_allocs: int = 0
    page_retains: int = 0
    page_releases: int = 0
    slot_allocs: int = 0
    slot_releases: int = 0
    forks: int = 0
    preempts: int = 0
    restores: int = 0
    pages_peak: int = 0


class _Tracker:
    def __init__(self, report: LifecycleReport):
        self.report = report
        # per-pool shadow refcounts, snapshotted on first sight (pools
        # created before arming already hold e.g. the garbage page)
        self.pages: Dict[int, Dict[int, int]] = {}
        self.slot_free: Dict[int, Set[int]] = {}
        self.released_paths: Set[int] = set()
        # every pool and allocator shadowed, held so that each id above
        # names one object: a freed pool's id can come back as the next
        # engine's pool, which would inherit a stale shadow
        self._tracked: List[object] = []

    def violate(self, msg: str) -> None:
        self.report.violations.append(f"{msg} at {_call_site()}")

    # -- page pool ----------------------------------------------------------

    def _shadow(self, pool: PagePool) -> Dict[int, int]:
        shadow = self.pages.get(id(pool))
        if shadow is None:
            shadow = {p: int(c) for p, c in enumerate(pool.refcount) if c}
            self.pages[id(pool)] = shadow
            self._tracked.append(pool)
        return shadow

    def _check_pool(self, pool: PagePool, shadow: Dict[int, int]) -> None:
        for pid, c in shadow.items():
            actual = int(pool.refcount[pid])
            if actual != c:
                self.violate(f"refcount divergence: page {pid} shadow={c} "
                             f"pool={actual}")
        in_use = sum(1 for c in shadow.values() if c > 0)
        if in_use != pool.pages_in_use:
            self.violate(f"pages_in_use divergence: shadow={in_use} "
                         f"pool={pool.pages_in_use}")
        free = pool.free
        if len(set(free)) != len(free):
            self.violate("free-list contains duplicate pages")
        for pid in free:
            if shadow.get(pid, 0) > 0:
                self.violate(f"page {pid} is on the free list with a "
                             "live refcount")

    def page_alloc(self, pool: PagePool, pid: int) -> None:
        first = id(pool) not in self.pages
        shadow = self._shadow(pool)
        if first:
            # first sight happens *after* orig() ran, so the snapshot
            # already reflects this alloc — nothing to pre-check
            shadow[pid] = int(pool.refcount[pid])
        elif shadow.get(pid, 0) > 0:
            self.violate(f"alloc returned in-use page {pid}")
            shadow[pid] = 1
        else:
            shadow[pid] = 1
        self.report.page_allocs += 1
        self.report.pages_peak = max(self.report.pages_peak,
                                     pool.pages_in_use)
        self._check_pool(pool, shadow)

    # retain/release split into a pre-check (report the bad call before
    # the pool's own assert aborts) and a post-sync (mutate the shadow
    # only after the pool really changed, so a raise leaves it exact)

    def pre_page_retain(self, pool: PagePool, pid: int) -> None:
        shadow = self._shadow(pool)
        if shadow.get(pid, 0) <= 0:
            self.violate(f"retain of page {pid} with no live refcount")

    def post_page_retain(self, pool: PagePool, pid: int) -> None:
        shadow = self._shadow(pool)
        shadow[pid] = shadow.get(pid, 0) + 1
        self.report.page_retains += 1
        self._check_pool(pool, shadow)

    def pre_page_release(self, pool: PagePool, pid: int) -> None:
        shadow = self._shadow(pool)
        if shadow.get(pid, 0) <= 0:
            self.violate(f"release of page {pid} at refcount 0 "
                         "(double release)")

    def post_page_release(self, pool: PagePool, pid: int) -> None:
        shadow = self._shadow(pool)
        shadow[pid] = shadow.get(pid, 0) - 1
        self.report.page_releases += 1
        self._check_pool(pool, shadow)

    # -- slots --------------------------------------------------------------

    def _slot_shadow(self, alloc: SlotAllocator) -> Set[int]:
        shadow = self.slot_free.get(id(alloc))
        if shadow is None:
            shadow = set(alloc.free)
            self.slot_free[id(alloc)] = shadow
            self._tracked.append(alloc)
        return shadow

    def slot_alloc(self, alloc: SlotAllocator, slot: int) -> None:
        first = id(alloc) not in self.slot_free
        shadow = self._slot_shadow(alloc)
        if first:
            # snapshot taken post-pop: the slot is correctly absent
            pass
        elif slot not in shadow:
            self.violate(f"slot alloc returned in-use slot {slot}")
        shadow.discard(slot)
        self.report.slot_allocs += 1

    def slot_release(self, alloc: SlotAllocator, slot: int) -> None:
        shadow = self._slot_shadow(alloc)
        if slot in shadow:
            self.violate(f"double release of slot {slot} — the free "
                         "list now hands it to two paths")
        shadow.add(slot)
        self.report.slot_releases += 1

    # -- path FSM -----------------------------------------------------------

    def check_live(self, op: str, paths) -> None:
        for p in paths:
            if p is not None and getattr(p, "released", False):
                self.violate(f"{op} on a released path")

    def note_released(self, path) -> None:
        self.released_paths.add(id(path))


class _PatchSet:
    """Reversible class-level patches, refcounted for nesting."""

    def __init__(self):
        self.depth = 0
        self._saved: List[Tuple[object, str, object]] = []

    def _patch(self, owner, name: str, wrapper: Callable) -> None:
        orig = getattr(owner, name)
        self._saved.append((owner, name, orig))
        setattr(owner, name, wrapper(orig))

    def install(self) -> None:
        self.depth += 1
        if self.depth > 1:
            return
        from repro.core.engine import TreeEngine

        def tracker() -> Optional[_Tracker]:
            return _state()["guard"]

        def wrap_page_alloc(orig):
            def alloc(pool):
                pid = orig(pool)
                t = tracker()
                if t is not None:
                    t.page_alloc(pool, pid)
                return pid
            return alloc

        def wrap_page_retain(orig):
            def retain(pool, pid):
                t = tracker()
                if t is not None:
                    t.pre_page_retain(pool, pid)
                orig(pool, pid)
                if t is not None:
                    t.post_page_retain(pool, pid)
            return retain

        def wrap_page_release(orig):
            def release(pool, pid):
                t = tracker()
                if t is not None:
                    t.pre_page_release(pool, pid)
                orig(pool, pid)
                if t is not None:
                    t.post_page_release(pool, pid)
            return release

        def wrap_slot_alloc(orig):
            def alloc(slots):
                slot = orig(slots)
                t = tracker()
                if t is not None:
                    t.slot_alloc(slots, slot)
                return slot
            return alloc

        def wrap_slot_release(orig):
            def release(slots, slot):
                t = tracker()
                if t is not None:
                    t.slot_release(slots, slot)
                orig(slots, slot)
            return release

        def wrap_fork_paths(orig):
            def fork_paths(engine, parents, **kw):
                t = tracker()
                if t is not None:
                    t.check_live("fork_paths", parents)
                out = orig(engine, parents, **kw)
                if t is not None:
                    t.report.forks += len(out)
                return out
            return fork_paths

        def wrap_fork_from_prefix(orig):
            def fork_from_prefix(engine, src, *a, **kw):
                t = tracker()
                if t is not None:
                    t.check_live("fork_from_prefix", [src])
                return orig(engine, src, *a, **kw)
            return fork_from_prefix

        def wrap_decode_segments(orig):
            def decode_segments(engine, paths, *a, **kw):
                t = tracker()
                if t is not None:
                    t.check_live("decode_segments", paths)
                return orig(engine, paths, *a, **kw)
            return decode_segments

        def wrap_preempt_path(orig):
            def preempt_path(engine, path):
                t = tracker()
                if t is not None:
                    t.check_live("preempt_path", [path])
                freed = orig(engine, path)
                if t is not None:
                    t.report.preempts += 1
                    if not path.released:
                        t.violate("preempt_path left the path unreleased")
                    t.note_released(path)
                return freed
            return preempt_path

        def wrap_release_path(orig):
            def release_path(engine, path):
                t = tracker()
                already = path.released
                orig(engine, path)
                if t is not None and not already:
                    t.note_released(path)
            return release_path

        def wrap_restore_path(orig):
            def restore_path(engine, tokens):
                out = orig(engine, tokens)
                t = tracker()
                if t is not None:
                    t.report.restores += 1
                    if out.released:
                        t.violate("restore_path returned a released path")
                return out
            return restore_path

        self._patch(PagePool, "alloc", wrap_page_alloc)
        self._patch(PagePool, "retain", wrap_page_retain)
        self._patch(PagePool, "release", wrap_page_release)
        self._patch(SlotAllocator, "alloc", wrap_slot_alloc)
        self._patch(SlotAllocator, "release", wrap_slot_release)
        self._patch(TreeEngine, "fork_paths", wrap_fork_paths)
        self._patch(TreeEngine, "fork_from_prefix", wrap_fork_from_prefix)
        self._patch(TreeEngine, "decode_segments", wrap_decode_segments)
        self._patch(TreeEngine, "preempt_path", wrap_preempt_path)
        self._patch(TreeEngine, "release_path", wrap_release_path)
        self._patch(TreeEngine, "restore_path", wrap_restore_path)

    def remove(self) -> None:
        self.depth -= 1
        if self.depth > 0:
            return
        for owner, name, orig in reversed(self._saved):
            setattr(owner, name, orig)
        self._saved.clear()


_patches = _PatchSet()


@contextmanager
def lifecycle_guard(*, raise_on_violation: bool = True):
    """Arm the dynamic lifecycle tracker.  Nests; the inner guard's
    violations propagate into the enclosing one."""
    st = _state()
    prev = st["guard"]
    report = LifecycleReport()
    tracker = _Tracker(report)
    st["guard"] = tracker
    _patches.install()
    try:
        yield report
    finally:
        st["guard"] = prev
        _patches.remove()
        if prev is not None:
            prev.report.violations.extend(report.violations)
    if report.violations and raise_on_violation and prev is None:
        head = "\n  ".join(report.violations[:20])
        raise LifecycleViolation(
            f"{len(report.violations)} lifecycle violation(s):\n  {head}")

"""Runtime hot-path guards: device residency as a *checked* invariant.

Every efficiency property the tree engine and trainer earn — amortized
prefix compute, device-resident boundary logits, one jitted K-epoch
update per bucket — survives only while the hot paths stay on device
and each (shape) bucket compiles exactly once.  This module is the
runtime half of the enforcement layer (the static half is
``tools/analyze``, see ``docs/static_analysis.md``):

* :func:`annotated_transfer` — the ONE sanctioned door between host and
  device on a hot path.  Takes an arbitrary pytree and moves it in a
  single batched call (``jax.device_get`` / ``jax.device_put``), so a
  round's pulls coalesce into one transfer instead of one per array,
  and tags the transfer with a ``reason`` an armed guard records.

* :func:`hot_path_guard` — a context manager that arms
  ``jax.transfer_guard("disallow")`` (authoritative on real
  accelerators) plus a Python-level interception of the repo's transfer
  entry points (``np.asarray`` / ``np.array`` / ``jax.device_get`` on
  device arrays, ``jnp.asarray`` / ``jnp.array`` / ``jax.device_put``
  on host ndarrays outside a trace) — the CPU container performs those
  zero-copy, so the XLA guard alone cannot see them.  Un-annotated
  transfers raise :class:`HotPathViolation` at exit, listing every
  offending call site; annotated ones are tallied per reason.

* :func:`compile_count` / :func:`compile_delta` — a process-wide
  compilation counter fed by ``jax.monitoring`` backend-compile events,
  and :func:`compile_cache_size` for per-jitted-function trace-cache
  sizes — together they turn "one compilation per bucket" into an
  assertable number (``tests/test_guard.py``,
  ``benchmarks/train_hotpath.py``'s ``recompiles`` field).

Known limits (documented, not silent): dunder conversions
(``float(x)`` / ``int(x)`` on a device array) cannot be intercepted
from Python and are only caught by the XLA transfer guard on non-CPU
backends — the static analyzer's R1 rule covers them at review time;
implicit h2d at jit dispatch (passing a raw ``np.ndarray`` into a
jitted function) is likewise only visible to the XLA guard.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import traceback
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
# JAX 0.9 exports no public "am I tracing?" query.  trace_state_clean is
# the function the removed ``jax.core.trace_state_clean`` alias named (it
# returns ``trace_ctx.is_top_level()``), so its meaning here is unchanged.
from jax._src.core import trace_state_clean

__all__ = [
    "HotPathViolation",
    "GuardReport",
    "annotated_transfer",
    "hot_path_guard",
    "compile_count",
    "compile_delta",
    "compile_cache_size",
]


class HotPathViolation(RuntimeError):
    """An un-annotated host<->device transfer happened under
    :func:`hot_path_guard`."""


# ---------------------------------------------------------------------------
# compile counter (jax.monitoring backend-compile events)
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_lock = threading.Lock()
_compile_state = {"count": 0, "registered": False}


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        with _compile_lock:
            _compile_state["count"] += 1


def _ensure_listener() -> None:
    if not _compile_state["registered"]:
        with _compile_lock:
            if not _compile_state["registered"]:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_event_duration)
                _compile_state["registered"] = True


def compile_count() -> int:
    """Total XLA backend compilations observed since the listener was
    first armed (any call to this module arms it).  Use deltas — the
    absolute value depends on what compiled before arming."""
    _ensure_listener()
    return _compile_state["count"]


@contextlib.contextmanager
def compile_delta():
    """``with compile_delta() as d: ...; d()`` — number of backend
    compilations inside the block (0 on a warm steady-state path)."""
    start = compile_count()
    yield lambda: compile_count() - start


def compile_cache_size(jitted_fn) -> int:
    """Number of traced specializations cached on a ``jax.jit`` function
    (-1 if this jax version doesn't expose it).  A per-bucket cached jit
    holding exactly 1 entry is the "compiled exactly once per bucket"
    invariant."""
    getter = getattr(jitted_fn, "_cache_size", None)
    if getter is None:
        return -1
    try:
        return int(getter())
    except Exception:
        return -1


# ---------------------------------------------------------------------------
# transfer interception
# ---------------------------------------------------------------------------

_tls = threading.local()


def _state() -> dict:
    st = getattr(_tls, "state", None)
    if st is None:
        st = {"guard": None, "annotating": 0, "intercepting": 0}
        _tls.state = st
    return st


def _tree_bytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


def _call_site(skip_prefixes: Tuple[str, ...] = ("guard.py",)) -> str:
    """repo-facing ``file:line`` of the frame that initiated a transfer
    (first frame outside this module and outside numpy/jax internals)."""
    for frame in reversed(traceback.extract_stack()[:-2]):
        fn = frame.filename
        if any(fn.endswith(p) for p in skip_prefixes):
            continue
        if "/numpy/" in fn or "/jax/" in fn or "/jaxlib/" in fn:
            continue
        return f"{fn}:{frame.lineno}"
    return "<unknown>"


@dataclasses.dataclass
class GuardReport:
    """What happened inside one :func:`hot_path_guard` block."""

    violations: List[str] = dataclasses.field(default_factory=list)
    annotated: List[Tuple[str, str, int]] = dataclasses.field(
        default_factory=list)          # (reason, direction, bytes)
    compiles_at_enter: int = 0

    @property
    def compiles(self) -> int:
        """Backend compilations since the guard was entered."""
        return compile_count() - self.compiles_at_enter

    @property
    def annotated_bytes(self) -> int:
        return sum(b for _, _, b in self.annotated)

    @property
    def annotated_reasons(self) -> Dict[str, int]:
        """reason -> number of annotated transfers under that label."""
        out: Dict[str, int] = {}
        for reason, _, _b in self.annotated:
            out[reason] = out.get(reason, 0) + 1
        return out


def _record_violation(direction: str, obj: Any) -> None:
    st = _state()
    guard: Optional[GuardReport] = st["guard"]
    # "intercepting" > 1: a patched entry point called another patched
    # entry point (jnp.asarray lowers to device_put) — one transfer,
    # recorded at the outermost wrapper only
    if guard is None or st["annotating"] or st["intercepting"] > 1:
        return
    desc = getattr(obj, "shape", None)
    dt = getattr(obj, "dtype", None)
    guard.violations.append(
        f"{direction} transfer of {dt}{list(desc) if desc is not None else ''}"
        f" at {_call_site()}")


def _is_device_array(x: Any) -> bool:
    return isinstance(x, jax.Array)


def _is_host_array(x: Any) -> bool:
    return isinstance(x, np.ndarray)


class _PatchSet:
    """Reversible monkeypatches of the transfer entry points.  Installed
    only while a guard is active (reference-counted for nesting)."""

    def __init__(self) -> None:
        self.depth = 0
        self._saved: List[Tuple[Any, str, Any]] = []

    def _patch(self, owner: Any, name: str, wrapper) -> None:
        self._saved.append((owner, name, getattr(owner, name)))
        setattr(owner, name, wrapper)

    def install(self) -> None:
        self.depth += 1
        if self.depth > 1:
            return
        import jax.numpy as jnp

        orig_np_asarray = np.asarray
        orig_np_array = np.array
        orig_device_get = jax.device_get
        orig_device_put = jax.device_put
        orig_jnp_asarray = jnp.asarray
        orig_jnp_array = jnp.array

        def _outermost(fn):
            # track wrapper nesting so a patched entry point that calls
            # another patched one (jnp.asarray lowers through
            # device_put) records ONE transfer, not two
            def wrapped(*a, **kw):
                st = _state()
                st["intercepting"] += 1
                try:
                    return fn(*a, **kw)
                finally:
                    st["intercepting"] -= 1
            return wrapped

        @_outermost
        def np_asarray(a, *args, **kwargs):
            if _is_device_array(a):
                _record_violation("device->host", a)
            return orig_np_asarray(a, *args, **kwargs)

        @_outermost
        def np_array(a, *args, **kwargs):
            if _is_device_array(a):
                _record_violation("device->host", a)
            return orig_np_array(a, *args, **kwargs)

        @_outermost
        def device_get(x):
            if any(_is_device_array(l)
                   for l in jax.tree_util.tree_leaves(x)):
                _record_violation("device->host", x)
            return orig_device_get(x)

        def _h2d_check(x):
            # constants materialized during tracing are baked into the
            # compiled program, not per-dispatch transfers — skip them
            if _is_host_array(x) and trace_state_clean():
                _record_violation("host->device", x)

        @_outermost
        def device_put(x, *args, **kwargs):
            for leaf in jax.tree_util.tree_leaves(x):
                _h2d_check(leaf)
            return orig_device_put(x, *args, **kwargs)

        @_outermost
        def jnp_asarray(a, *args, **kwargs):
            _h2d_check(a)
            return orig_jnp_asarray(a, *args, **kwargs)

        @_outermost
        def jnp_array(a, *args, **kwargs):
            _h2d_check(a)
            return orig_jnp_array(a, *args, **kwargs)

        self._patch(np, "asarray", np_asarray)
        self._patch(np, "array", np_array)
        self._patch(jax, "device_get", device_get)
        self._patch(jax, "device_put", device_put)
        self._patch(jnp, "asarray", jnp_asarray)
        self._patch(jnp, "array", jnp_array)

    def remove(self) -> None:
        self.depth -= 1
        if self.depth > 0:
            return
        for owner, name, orig in reversed(self._saved):
            setattr(owner, name, orig)
        self._saved.clear()


_patches = _PatchSet()


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def annotated_transfer(tree: Any, *, to: str = "host",
                       reason: str = "unlabeled") -> Any:
    """Move a pytree across the host/device boundary in ONE batched call.

    ``to="host"``: one ``jax.device_get`` over the whole tree (returns
    numpy arrays); ``to="device"``: one ``jax.device_put``.  Inside an
    armed :func:`hot_path_guard` the transfer is allowlisted and tallied
    under ``reason``; outside a guard it is just the transfer.  This is
    the single door intended hot-path transfers go through — raw
    ``np.asarray`` / ``jnp.asarray`` on the hot path is a guard
    violation and a ``tools/analyze`` R1 finding.
    """
    if to not in ("host", "device"):
        raise ValueError(f"annotated_transfer: to={to!r} "
                         "(expected 'host' or 'device')")
    st = _state()
    st["annotating"] += 1
    try:
        with jax.transfer_guard("allow"):
            if to == "host":
                out = jax.device_get(tree)
            else:
                out = jax.device_put(tree)
    finally:
        st["annotating"] -= 1
    guard: Optional[GuardReport] = st["guard"]
    if guard is not None:
        guard.annotated.append(
            (reason, "d2h" if to == "host" else "h2d",
             _tree_bytes(out if to == "host" else tree)))
    return out


@contextlib.contextmanager
def hot_path_guard(*, use_transfer_guard: Optional[bool] = None,
                   raise_on_violation: bool = True):
    """Assert device residency over a block of hot-path host code.

    Yields a :class:`GuardReport`.  While active:

    * ``jax.transfer_guard("disallow")`` is armed (XLA-level; the
      authoritative check on TPU/GPU where transfers are real copies —
      ``use_transfer_guard`` defaults to backend != cpu, because on CPU
      the XLA guard also trips on weak scalar constants of un-jitted
      glue ops whose "transfers" are zero-copy there);
    * the Python entry points are intercepted so un-annotated transfers
      are caught on this CPU container too;
    * backend compilations are counted (``report.compiles`` — a warm
      steady-state block must report 0).

    On exit, any recorded violation raises :class:`HotPathViolation`
    listing every offending call site (set ``raise_on_violation=False``
    to inspect the report instead — used by the tests of the guard
    itself).  Guards nest; the innermost report records the block's
    transfers and each active guard sees its own compile delta.
    """
    _ensure_listener()
    if use_transfer_guard is None:
        use_transfer_guard = jax.default_backend() != "cpu"
    st = _state()
    report = GuardReport(compiles_at_enter=compile_count())
    prev = st["guard"]
    st["guard"] = report
    _patches.install()
    ctx = (jax.transfer_guard("disallow") if use_transfer_guard
           else contextlib.nullcontext())
    try:
        with ctx:
            yield report
    finally:
        _patches.remove()
        st["guard"] = prev
        if prev is not None:
            # surface the inner block's traffic to the enclosing guard
            prev.violations.extend(report.violations)
            prev.annotated.extend(report.annotated)
    if report.violations and raise_on_violation:
        raise HotPathViolation(
            "un-annotated host transfer(s) on a guarded hot path "
            "(route intended transfers through "
            "repro.core.guard.annotated_transfer):\n  " +
            "\n  ".join(report.violations))

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
  compilation counter, and :func:`compile_cache_size` for
  per-jitted-function trace-cache sizes — together they turn "one
  compilation per bucket" into an assertable number
  (``tests/test_guard.py``, ``benchmarks/train_hotpath.py``'s
  ``recompiles`` field).  Both read the per-program build counters
  (:func:`build_counts`): one ``jax.monitoring`` listener keys every
  jaxpr trace, lowering and backend compile (or persistent-cache load)
  by the jitted function's name.  They are always on and cost nothing
  until something compiles.

* :func:`span` / :func:`tracing` / :func:`take_spans` — the program's
  host spans.  Off (the default) ``span`` returns one shared no-op
  context manager: nothing is timed or recorded.  On — ``tracing(True)``,
  or while a ``jax.profiler.start_trace`` session runs — each span is
  recorded in memory as ``(name, start_ns, end_ns, parent, attrs)`` and
  opens a ``jax.profiler.TraceAnnotation("repro.<name>")`` so the
  profiler's trace carries it too; the builds above become
  ``build.<program>.<phase>`` spans.  Spans live in host code only:
  nothing here enters a jitted body, wraps a jitted callable or sets a
  ``jax.config`` option, so every program's HLO is the same with
  tracing on or off.

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
import functools
import re
import threading
import time
import traceback
import warnings
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import numpy as np
# JAX 0.9 exports no public "am I tracing?" query.  trace_state_clean is
# the function the removed ``jax.core.trace_state_clean`` alias named (it
# returns ``trace_ctx.is_top_level()``), so its meaning here is unchanged.
from jax._src.core import trace_state_clean
from jax._src import profiler as _jax_profiler

__all__ = [
    "HotPathViolation",
    "GuardReport",
    "annotated_transfer",
    "hot_path_guard",
    "compile_count",
    "compile_delta",
    "compile_cache_size",
    "ProgramBuilds",
    "build_counts",
    "Span",
    "span",
    "spanned",
    "tracing",
    "take_spans",
]


class HotPathViolation(RuntimeError):
    """An un-annotated host<->device transfer happened under
    :func:`hot_path_guard`."""


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

class Span(NamedTuple):
    """One recorded span; ``parent`` indexes the same list (-1: none)."""

    name: str
    start_ns: int
    end_ns: int
    parent: int
    attrs: Dict[str, Any]


_tracing_on = False


def _profiling() -> bool:
    """Whether a ``jax.profiler.start_trace`` session is running.  JAX 0.9
    has no public query; its session lives here.  (``TraceAnnotation.
    is_enabled`` would also answer for host tracing that no session of
    this process started.)"""
    return _jax_profiler._profile_state.profile_session is not None


# whether the last look saw a profiler session (see _recording)
_in_session = False
# a store that no reader empties stops growing here (about 100 MB)
MAX_SPANS = 250_000
_span_lock = threading.Lock()
_spans: List[Optional[Span]] = []
_warned_full = False
_span_tls = threading.local()


def tracing(on: bool) -> bool:
    """Switch span recording on or off; returns the previous setting.
    Off by default; a ``jax.profiler.start_trace`` session records
    spans whatever the switch says, so a profiled window always carries
    them."""
    global _tracing_on
    prev, _tracing_on = _tracing_on, bool(on)
    return prev


def _recording() -> bool:
    """Whether a span would be kept now.  With the switch off, a profiler
    session that began since the last look starts the store afresh, so
    one session's spans never carry over into the next; a full store
    keeps nothing more."""
    global _in_session, _warned_full
    if not _tracing_on:
        if not _profiling():
            if _in_session:
                _in_session = False
            return False
        if not _in_session:
            _in_session = True
            take_spans()
    if len(_spans) < MAX_SPANS:
        return True
    if not _warned_full:
        _warned_full = True
        warnings.warn(f"repro.core.guard: {MAX_SPANS} spans kept and none "
                      "taken; later spans are dropped", RuntimeWarning,
                      stacklevel=3)
    return False


def take_spans() -> List[Span]:
    """The spans completed since the last call, in the order they were
    opened (a build phase: when it ended); the store is cleared.  A span
    still open when this runs is dropped from both lists."""
    global _spans, _warned_full
    with _span_lock:
        out, _spans = _spans, []
        _warned_full = False
    index = {}
    kept: List[Span] = []
    for i, sp in enumerate(out):
        if sp is not None:
            index[i] = len(kept)
            kept.append(sp)
    return [sp._replace(parent=index.get(sp.parent, -1)) for sp in kept]


def _stack() -> list:
    st = getattr(_span_tls, "stack", None)
    if st is None:
        st = _span_tls.stack = []
    return st


def _open_parent(store: list) -> int:
    stack = _stack()
    return stack[-1][1] if stack and stack[-1][0] is store else -1


class _NoSpan:
    """The one span handed out while tracing is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass

    def __bool__(self) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _LiveSpan:
    __slots__ = ("name", "attrs", "_store", "_index", "_parent", "_start",
                 "_ann")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        with _span_lock:
            store = self._store = _spans
            self._index = len(store)
            store.append(None)
        self._parent = _open_parent(store)
        _stack().append((store, self._index))
        self._ann = jax.profiler.TraceAnnotation("repro." + self.name)
        self._ann.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.time_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        self._store[self._index] = Span(self.name, self._start, end,
                                        self._parent, self.attrs)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only once the span's work has run."""
        self.attrs.update(attrs)

    def __bool__(self) -> bool:
        return True


def span(name: str, **attrs):
    """``with span("engine.decode", rows=R): ...`` — a host span around a
    layer's work.  Off: the shared no-op (falsy, so ``if sp: sp.set(...)``
    computes late attributes only when recording).  Wall clock in
    nanoseconds (``time.time_ns``), the clock the profiler's host events
    use.  Never put one inside a per-row or per-token loop."""
    if not _recording():
        return _NO_SPAN
    return _LiveSpan(name, attrs)


def spanned(name: str, attrs: Optional[Callable[..., Dict[str, Any]]] = None):
    """Decorator for a host function or method: each call runs inside
    ``span(name, **attrs(*args, **kwargs))``, ``attrs`` called only while
    recording.  Never on a jitted callable: a program built inside the
    call is built as without it."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            sp = (_LiveSpan(name, attrs(*args, **kwargs) if attrs else {})
                  if _recording() else _NO_SPAN)
            # one call line on or off: programs built inside take their
            # source locations from this stack
            with sp:
                return fn(*args, **kwargs)
        return call
    return wrap


def _record(name: str, start_ns: int, end_ns: int,
            attrs: Dict[str, Any]) -> None:
    """A span whose work has already run (a build phase, reported by
    JAX at its end), under the span open on this thread."""
    with _span_lock:
        store = _spans
        store.append(Span(name, start_ns, end_ns, _open_parent(store),
                          attrs))


# ---------------------------------------------------------------------------
# per-program build counters (jax.monitoring compile events)
# ---------------------------------------------------------------------------

_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 1}
# lowering and compile events name the module (``jit(decode_fn)``), the
# trace event the function (``decode_fn``): both key as ``decode_fn``
_MODULE_NAME = re.compile(r"^(?:jit|pmap)\((.*)\)$")


@dataclasses.dataclass
class ProgramBuilds:
    """What building one program (a jitted function, by ``__name__``)
    cost: traces to a jaxpr, lowerings to MLIR, and backend compiles or
    persistent-cache loads, with the cache's hits and misses and the
    seconds of each phase."""

    traces: int = 0
    lowerings: int = 0
    compiles: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    trace_s: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0


_build_lock = threading.Lock()
_builds: Dict[str, ProgramBuilds] = {}
_build_state = {"compiles": 0, "pending": [0, 0]}


def _program_name(fun_name: str) -> str:
    m = _MODULE_NAME.match(fun_name)
    return m.group(1) if m else fun_name


def _on_build_phase(event: str, start: float, end: float, **kw) -> None:
    phase = _PHASES.get(event)
    if phase is None:
        return
    name = _program_name(str(kw.get("fun_name", "?")))
    attrs: Dict[str, Any] = {}
    with _build_lock:
        b = _builds.get(name)
        if b is None:
            b = _builds[name] = ProgramBuilds()
        if phase == "trace":
            b.traces += 1
            b.trace_s += end - start
        elif phase == "lower":
            b.lowerings += 1
            b.lower_s += end - start
        else:
            # the cache's hit or miss fired inside this compile's span
            pending = _build_state["pending"]
            b.compiles += 1
            b.compile_s += end - start
            b.cache_hits += pending[0]
            b.cache_misses += pending[1]
            attrs = {"cache_hits": pending[0], "cache_misses": pending[1]}
            _build_state["pending"] = [0, 0]
            _build_state["compiles"] += 1
    if _recording():
        _record(f"build.{name}.{phase}", int(start * 1e9), int(end * 1e9),
                attrs)


def _on_cache_event(event: str, **_kw) -> None:
    k = _CACHE_EVENTS.get(event)
    if k is not None:
        with _build_lock:
            _build_state["pending"][k] += 1


# one listener per process on the compile phases (their time spans carry
# the durations too) and one on the persistent cache's hits and misses
jax.monitoring.register_event_time_span_listener(_on_build_phase)
jax.monitoring.register_event_listener(_on_cache_event)


def build_counts() -> Dict[str, ProgramBuilds]:
    """Per-program build counters since the process started (a copy)."""
    with _build_lock:
        return {k: dataclasses.replace(v) for k, v in _builds.items()}


def compile_count() -> int:
    """Total XLA backend compilations (persistent-cache loads included)
    since this module was imported: the sum of every program's
    ``compiles``.  Use deltas — the absolute value depends on what
    compiled before."""
    return _build_state["compiles"]


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
    # a pull to the host is where the host waits for the device
    sp = (_LiveSpan("transfer." + reason, {"to": to}) if _recording()
          else _NO_SPAN)
    try:
        with sp, jax.transfer_guard("allow"):
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

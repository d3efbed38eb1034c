"""Continuous-batching tree-serving scheduler (SGL-JAX-style loop).

The synchronous driver prefilled a fixed batch, ran it to completion,
then started the next — the accelerator idled between batches and every
prompt was recomputed from scratch.  This scheduler admits requests
continuously from an arrival trace and dispatches ONE jitted serve
segment per round in which prompt-prefill chunks and steady-state decode
mix freely: a row's first rounds *force* its prompt tokens through the
decode scan (chunked prefill as forced decode), later rounds sample.

Determinism contract (proven in tests/test_scheduler.py): the serve
function samples row ``i`` with a key derived from (request key,
absolute position) and every per-row computation is row-independent, so
a request's token/logprob stream is bitwise identical whatever arrival
interleaving, batch composition, preemption or admission order it
experienced — continuous and synchronous serving agree per request.

KV economics: a new request's prompt prefix is first looked up in the
cross-request :class:`~repro.kv.radix.RadixCache`; matched pages are
shared COW-style (no recompute, no copy).  Under pool pressure the
degradation order is radix-evict LRU leaves FIRST, preempt newest
request second (``docs/robustness.md`` composition) — cache contents are
recomputable, a live request's working set costs a full replay.

Scheduling is FCFS with preempted requests re-queued at the *front*, so
no request can starve: the head of the queue is always the next admitted
(bounded-admission-wait test).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import faults
from repro.core.early_stop import truncate_at_eos
from repro.core.engine import EnginePath, TreeEngine
from repro.core.guard import annotated_transfer, span
from repro.data.tokenizer import ByteTokenizer
from repro.kv.cache import OutOfPages, bucket_pow2
from repro.kv.radix import RadixCache

__all__ = ["Request", "ServeReport", "Scheduler", "poisson_trace"]


# ---------------------------------------------------------------------------
# request / report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    """One serving request and its full lifecycle state."""

    rid: int                          # also the per-request sampling key
    prompt: List[int]
    max_new_tokens: int = 64
    arrival: float = 0.0              # trace time the request appears
    state: str = "waiting"            # waiting -> running -> finished
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    out_logprobs: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    ep: Optional[EnginePath] = None
    consumed: int = 0                 # tokens fed to the model (KV built)
    cached_len: int = 0               # prompt tokens served by the radix
    inserted: bool = False            # prompt prefix offered to the cache
    visible_round: int = -1           # round the request entered the queue
    admit_round: int = -1             # round of FIRST admission
    preemptions: int = 0

    def history(self) -> List[int]:
        """Every token whose KV the model must hold: prompt + generated.
        Replay after preemption forces exactly this sequence."""
        return self.prompt + self.out_tokens


@dataclasses.dataclass
class ServeReport:
    rounds: int = 0
    admitted: int = 0
    finished: int = 0
    preemptions: int = 0
    prompt_tokens: int = 0            # across admitted requests
    radix_hit_tokens: int = 0         # prompt tokens served from cache
    forced_tokens: int = 0            # prompt/replay tokens fed as forced
    gen_tokens: int = 0               # sampled tokens fed (the output)
    model_tokens: int = 0             # R*l per round over real rows
    evicted_pages: int = 0            # radix pages dropped under pressure
    max_admission_wait: int = 0       # rounds from visible to admitted
    rows_dispatched: int = 0          # compiled rows (Rb) over dispatches
    rows_real: int = 0                # running rows over dispatches
    virtual_time: float = 0.0

    @property
    def reuse_ratio(self) -> float:
        """Fraction of admitted prompt tokens whose KV came from the
        cross-request radix cache instead of being recomputed."""
        return self.radix_hit_tokens / max(self.prompt_tokens, 1)

    @property
    def traj_ps(self) -> float:
        return self.finished / max(self.virtual_time, 1e-9)


def poisson_trace(rng, n: int, *, rate: float,
                  start: float = 0.0) -> List[float]:
    """``n`` Poisson arrival times (exponential inter-arrival gaps of
    mean ``1/rate``) from an externally-owned ``random.Random`` — the
    caller owns seeding and any checkpoint capture of the generator."""
    out: List[float] = []
    t = start
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

class Scheduler:
    """Continuous-batching frontend over a ``TreeEngine``/``ModelRunner``.

    mode="continuous" admits whenever a slot frees up; mode="sync"
    reproduces the old batch driver (admit a full batch only when the
    previous one drained) — same serve function, same per-request
    streams, used as the throughput baseline and parity oracle.
    clock="round" advances virtual time by 1 per dispatch round
    (deterministic tests); clock="wall" accumulates measured wall
    seconds (benchmarks).
    """

    def __init__(self, engine: TreeEngine, *, mode: str = "continuous",
                 max_running: int = 8, seg_len: Optional[int] = None,
                 radix: bool = True, base_seed: int = 0,
                 eos_id: int = ByteTokenizer.EOS, clock: str = "round"):
        assert mode in ("continuous", "sync")
        assert clock in ("round", "wall")
        assert engine.can_restore, \
            "serving needs token-complete contexts (no cross-KV / " \
            "modality prefix)"
        self.engine = engine
        self.mode = mode
        self.max_running = max_running
        # ONE compiled batch bucket for the whole serve lifetime: padded
        # to the pow2 bucket of max_running, so warm serving recompiles
        # exactly never (hot_path_guard regression test)
        self.Rb = bucket_pow2(max_running)
        self.seg_len = seg_len or engine.tree_cfg.segment_len
        self.base_seed = base_seed
        self.eos_id = eos_id
        self.clock = clock
        self.radix: Optional[RadixCache] = None
        if radix and not engine.has_rec:
            # recurrent archs carry slot state the cache cannot restore;
            # attention-only KV is fully page-addressed
            self.radix = RadixCache(engine.kv.pool, engine.page_size)
        # always (re)register: radix=False must detach any cache a
        # previous scheduler left on a reused engine
        engine.attach_radix(self.radix)
        self.waiting: Deque[Request] = deque()
        self.running: List[Request] = []
        self.round = 0
        self.now = 0.0
        self.report = ServeReport()

    # -- admission ----------------------------------------------------------

    def submit(self, r: Request) -> None:
        r.state = "waiting"
        if r.visible_round < 0:
            r.visible_round = self.round
        self.waiting.append(r)

    def _build_path(self, r: Request) -> Tuple[EnginePath, int]:
        """Admission-time path construction: radix-match the history,
        point the table at the shared pages, grow capacity for the first
        segment.  Mirrors ``TreeEngine.restore_path``'s error discipline:
        a mid-build ``OutOfPages`` releases everything acquired so far
        (matched pages included) before propagating."""
        hist = r.history()
        pages: List[int] = []
        cached = 0
        if self.radix is not None:
            pages, cached = self.radix.match_prefix(hist)
        path = EnginePath(table=pages, slot=-1, qslot=-1, position=cached,
                          pending_token=0, pending_logprob=0.0)
        try:
            self.engine._ensure_capacity(path, cached + self.seg_len)
            if self.engine.has_rec:
                path.slot = self.engine._alloc_slot()
        except Exception:
            self.engine.release_partial([path])
            raise
        return path, cached

    def _admit(self) -> None:
        if self.mode == "sync" and self.running:
            return
        while self.waiting and len(self.running) < self.max_running:
            r = self.waiting[0]
            try:
                path, cached = self._build_path(r)
            except OutOfPages:
                if not self.running:
                    raise    # nothing preemptible left: genuine exhaustion
                break        # wait for pages; FCFS head keeps its turn
            self.waiting.popleft()
            r.ep = path
            r.consumed = cached
            r.state = "running"
            if r.admit_round < 0:
                r.admit_round = self.round
                self.report.admitted += 1
                self.report.prompt_tokens += len(r.prompt)
                self.report.radix_hit_tokens += min(cached, len(r.prompt))
                self.report.max_admission_wait = max(
                    self.report.max_admission_wait,
                    self.round - r.visible_round)
                r.cached_len = cached
            self.running.append(r)

    # -- pressure -----------------------------------------------------------

    def _page_demand(self) -> int:
        ps = self.engine.page_size
        demand = 0
        for r in self.running:
            need = -(-(r.ep.position + self.seg_len) // ps)
            demand += max(0, need - len(r.ep.table))
        return demand

    def _make_room(self) -> None:
        """Guarantee the round's page demand: evict radix leaves first,
        preempt the NEWEST running request second (FCFS fairness: the
        oldest admitted work is protected)."""
        deficit = self._page_demand() - self.engine.pages_free()
        if deficit <= 0:
            return
        if self.radix is not None:
            self.radix.evict(deficit)
        while (self._page_demand() > self.engine.pages_free()
               and len(self.running) > 1):
            self._preempt_victim(self.running[-1])

    def _preempt_victim(self, r: Request) -> None:
        """Retract ``r`` to the FRONT of the waiting queue.  Its pages are
        freed; its generated tokens are kept and will be force-replayed on
        re-admission, where position-keyed sampling regenerates the
        dropped pending draw bitwise."""
        self.running.remove(r)
        self.engine.preempt_path(r.ep)
        r.ep = None
        r.consumed = 0
        r.state = "waiting"
        r.preemptions += 1
        self.report.preemptions += 1
        self.waiting.appendleft(r)

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self) -> None:
        l = self.seg_len
        eng = self.engine
        for r in list(self.running):
            try:
                eng._ensure_capacity(r.ep, r.ep.position + l)
            except OutOfPages:
                if len(self.running) == 1:
                    raise    # one request can't fit: pool too small
                self._preempt_victim(r)
        if not self.running:
            return
        # snapshot the row order: _finish_request mutates self.running
        # during the unpack loop, and row i of the packed batch must keep
        # naming the same request end to end
        rows = list(self.running)
        R = len(rows)
        Rb = self.Rb
        tok0 = np.zeros((Rb,), np.int32)
        lp0 = np.zeros((Rb,), np.float32)
        pos0 = np.zeros((Rb,), np.int32)
        tables = np.full((Rb, eng.MP), -1, np.int32)
        slots = np.full((Rb,), max(eng.scratch_slot, 0), np.int32)
        forced_tok = np.zeros((Rb, l), np.int32)
        forced_on = np.zeros((Rb, l), bool)
        row_keys = np.zeros((Rb, 2), np.uint32)
        n_forced: List[int] = []
        for i, r in enumerate(rows):
            ep = r.ep
            tok0[i] = ep.pending_token
            lp0[i] = ep.pending_logprob
            pos0[i] = ep.position
            tables[i, : len(ep.table)] = ep.table
            if ep.slot >= 0:
                slots[i] = ep.slot
            hist = r.history()
            nf = min(l, max(0, len(hist) - r.consumed))
            n_forced.append(nf)
            if nf:
                forced_tok[i, :nf] = hist[r.consumed:r.consumed + nf]
                forced_on[i, :nf] = True
            row_keys[i] = (np.uint32(self.base_seed), np.uint32(r.rid))
        tables[R:, 0] = eng.garbage_page
        self.report.rows_dispatched += Rb
        self.report.rows_real += R

        fn = eng.runner.get_serve_fn(Rb, l)
        (tok0, lp0, pos0, tables, slots, forced_tok, forced_on,
         row_keys) = annotated_transfer(
            (tok0, lp0, pos0, tables, slots, forced_tok, forced_on,
             row_keys), to="device", reason="serve-pack")
        pools, rec, toks, lps, pend_tok, pend_lp = fn(
            eng.params, eng.kv.kv_pools, eng.kv.rec_state,
            tok0, lp0, pos0, tables, slots, forced_tok, forced_on,
            row_keys)
        eng.kv.kv_pools = pools
        eng.kv.rec_state = rec
        toks, lps, pend_tok, pend_lp = annotated_transfer(
            (toks, lps, pend_tok, pend_lp), reason="serve-segment")
        eng.stats.host_bytes += (toks.nbytes + lps.nbytes
                                 + pend_tok.nbytes + pend_lp.nbytes)
        lps = faults.corrupt_array("engine.decode_logprobs", lps)

        total_forced = sum(n_forced)
        eng.stats.prefill_tokens += total_forced
        eng.stats.decode_tokens += R * l - total_forced
        eng.stats.segments += R
        self.report.forced_tokens += total_forced
        self.report.gen_tokens += R * l - total_forced
        self.report.model_tokens += R * l
        for i, r in enumerate(rows):
            nf = n_forced[i]
            r.ep.position += l
            r.ep.pending_token = int(pend_tok[i])
            r.ep.pending_logprob = float(pend_lp[i])
            r.consumed += l
            r.out_tokens.extend(int(t) for t in toks[i, nf:])
            r.out_logprobs.extend(float(v) for v in lps[i, nf:])
            if (self.radix is not None and not r.inserted
                    and r.consumed >= len(r.prompt)):
                n_ins = len(r.prompt) // eng.page_size
                if n_ins > 0:
                    self.radix.insert(r.prompt[: n_ins * eng.page_size],
                                      r.ep.table[:n_ins])
                r.inserted = True
            if not (np.isfinite(lps[i, nf:]).all()
                    and np.isfinite(float(pend_lp[i]))):
                eng.stats.quarantined_paths += 1
                self._finish_request(r, "nonfinite")
                continue
            cut_t, cut_l = truncate_at_eos(r.out_tokens, r.out_logprobs,
                                           self.eos_id)
            if len(cut_t) < len(r.out_tokens):
                r.out_tokens, r.out_logprobs = cut_t, cut_l
                self._finish_request(r, "eos")
            elif len(r.out_tokens) >= r.max_new_tokens:
                r.out_tokens = r.out_tokens[: r.max_new_tokens]
                r.out_logprobs = r.out_logprobs[: r.max_new_tokens]
                self._finish_request(r, "length")

    def _finish_request(self, r: Request, reason: str) -> None:
        with span("scheduler.finish", rid=r.rid):
            self.engine.release_path(r.ep)
            self.running.remove(r)
        r.state = "finished"
        r.finish_reason = reason
        self.report.finished += 1

    # -- serve loop ---------------------------------------------------------

    def step(self) -> None:
        """One scheduling round: admit, make room, dispatch one mixed
        prefill/decode serve segment."""
        rep = self.report
        before = (rep.admitted, rep.preemptions, rep.rows_real,
                  rep.rows_dispatched)
        with span("scheduler.round", round=self.round) as sp:
            with span("scheduler.admit") as asp:
                n0 = len(self.running)
                self._admit()
                if asp:
                    asp.set(rids=[r.rid for r in self.running[n0:]])
            with span("scheduler.make_room"):
                self._make_room()
            with span("scheduler.dispatch") as dsp:
                self._dispatch()
                if dsp:
                    dsp.set(rows=rep.rows_real - before[2],
                            bucket=rep.rows_dispatched - before[3])
            if sp:
                sp.set(running=rep.rows_real - before[2],
                       admitted=rep.admitted - before[0],
                       preempted=rep.preemptions - before[1])
        self.round += 1

    def run(self, requests: Sequence[Request], *,
            max_rounds: int = 100_000) -> ServeReport:
        """Serve a whole arrival trace to completion."""
        trace = sorted(requests, key=lambda r: (r.arrival, r.rid))
        idx = 0
        while self.round < max_rounds:
            while idx < len(trace) and trace[idx].arrival <= self.now:
                self.submit(trace[idx])
                idx += 1
            if not self.waiting and not self.running:
                if idx >= len(trace):
                    break
                self.now = trace[idx].arrival   # idle: jump to next arrival
                continue
            t0 = time.perf_counter()
            self.step()
            if self.clock == "wall":
                self.now += time.perf_counter() - t0
            else:
                self.now += 1.0
        self.report.rounds = self.round
        self.report.virtual_time = self.now
        if self.radix is not None:
            self.report.evicted_pages = self.radix.evicted_pages
        return self.report

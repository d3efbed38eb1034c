"""Segment-synchronous tree-decoding engine (the paper's inference engine,
TPU-native).

vLLM's continuous batching schedules per token; XLA wants fixed shapes, so
TreePO's own *fixed-length segment* abstraction becomes the scheduling
quantum (DESIGN.md §2): the host re-batches paths only at segment
boundaries, and one jitted ``segment_decode`` call generates ``l`` tokens
for a power-of-two bucket of active paths against the shared paged KV pool.

Branch = block-table copy (+ copy-on-write of at most one partial page);
KV data of shared prefixes is stored once (the paper's KV amortization).
Recurrent state (Mamba conv/ssm, RWKV wkv/shift) is slot-indexed and copied
on fork — it is a running reduction, not a prefix.

The per-segment inner loop is device-resident end to end:

* **Attention decode** runs through the paged Pallas kernels — by default
  the pipelined fused-pool generation (GQA: ``kops.fused_paged_attention``
  over a head-interleaved ``[K0,V0,...]`` pool; MLA:
  ``kops.mla_fused_paged_attention`` over ``[ckv|k_rope]`` latent pages),
  which multi-buffers its own page DMAs so the copy of page i+1 overlaps
  the scoring of page i; ``fused_kv=False`` selects the legacy split-pool
  kernels (``kops.paged_attention`` / ``kops.mla_paged_attention``) as the
  parity oracle.  Block-table indirection is resolved in scalar prefetch
  either way, never as a dense HBM gather.
* **Fork divergence is sampled on device**: full-vocab boundary logits stay
  in a device buffer keyed by (buffer, row) on each path, and a branching
  round draws all of its divergence tokens in one jitted ``fork_sample``
  dispatch.  Steady-state host transfer per decode round is the (R, l)
  segment token/logprob matrices plus (R,) pending scalars — never (R, V)
  logits.  ``EnginePath.last_logits`` remains as an opt-in debug fetch.
* **Fork application is batched**: a round's COW page copies and recurrent
  slot copies go through ``PagedKVState.apply_forks`` as one jitted
  multi-layer dispatch.

Device functions are kept per static shape bucket in a
:class:`~repro.kv.cache.ProgramTable`, which an owner (the RL trainer) may
share across the engines it builds:
  prefill  (Q, Sp)      — flash-attention forward, paged KV write-out,
                          returns last-position logits (kept on device).
  decode   (R, l)       — lax.scan over l tokens; paged attention per attn
                          layer; on-device temperature/top-p sampling.
  serve    (R, l)       — decode variant for the continuous-batching
                          scheduler: per-step forced tokens (chunked
                          prompt prefill mixed into the decode dispatch)
                          and per-row position-derived sampling keys, so
                          a request's stream is bitwise independent of
                          how arrivals were batched around it.

The device half (params, KV pools, the program table) lives on
:class:`ModelRunner`; :class:`TreeEngine` layers path scheduling policy
(allocation, forks, preemption, pressure) on top — the SGL-JAX-style
Scheduler / ModelRunner split.  ``repro.core.scheduler`` drives the
runner's serve functions directly for continuous batching.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TreeConfig
from repro.core import faults
from repro.core.guard import annotated_transfer, spanned
from repro.kernels import ops as kops
from repro.kv.cache import OutOfPages, PagedKVState, ProgramTable, \
    bucket_pow2
from repro.kv.layout import fuse_mla, interleave_kv
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm
from repro.models.layers import (
    embed,
    mlp,
    rmsnorm,
    sinusoidal_positions,
    unembed,
)


# ---------------------------------------------------------------------------
# path handle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EnginePath:
    """Device-side identity of one search path."""

    table: List[int]                  # page ids (prefix-shared, refcounted)
    slot: int                         # recurrent-state slot (-1 if none)
    qslot: int                        # cross-KV slot (-1 if none)
    position: int                     # tokens whose KV is materialized
    pending_token: int                # sampled, not yet fed
    pending_logprob: float
    logits_buf: Optional[jnp.ndarray] = None  # (Rb, V) device boundary
    logits_row: int = 0                       # logits, shared per round
    released: bool = False
    numeric_bad: bool = False         # non-finite divergence draw detected
                                      # (quarantined by the sampler)

    @property
    def last_logits(self) -> Optional[np.ndarray]:
        """Opt-in DEBUG fetch of this path's (V,) boundary logits.

        The decode/fork hot path never calls this — divergence tokens are
        sampled on device from ``logits_buf`` — but tests and external
        tooling can still pull the full distribution to the host.  This
        transfer is outside the engine's ``EngineStats.host_bytes``
        accounting (a path has no engine reference to report to).
        """
        if self.logits_buf is None:
            return None
        return np.asarray(self.logits_buf[self.logits_row],
                          dtype=np.float32)


@dataclasses.dataclass
class SegmentResult:
    tokens: List[int]
    logprobs: List[float]
    seg_logprob: float                # mean logprob (heuristic signal)
    finite: bool = True               # False -> non-finite logprobs pulled
                                      # for this row; quarantine the path


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0           # model-processed prompt tokens
    decode_tokens: int = 0            # model-processed generated tokens
    segments: int = 0
    forks: int = 0
    cow_pages: int = 0
    replay_tokens: int = 0            # fallback re-prefill cost
    peak_pages: int = 0
    host_bytes: int = 0               # device->host transfer in the
                                      # decode/fork loop (tokens, logprobs,
                                      # pending scalars); debug
                                      # last_logits fetches are NOT counted
    fork_dispatches: int = 0          # jitted fork-sample/apply calls
    # fault-tolerance counters (docs/robustness.md)
    preempted_paths: int = 0          # active paths retracted under pressure
    regenerated_paths: int = 0        # preempted paths replayed back in
    quarantined_paths: int = 0        # paths with non-finite logits/logprobs
    pressure_events: int = 0          # alloc failures absorbed by the
                                      # preemption callback + retry

    @property
    def model_tokens(self) -> int:
        return self.prefill_tokens + self.decode_tokens + self.replay_tokens


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def _top_p_mask(logits: jnp.ndarray, top_p: float) -> jnp.ndarray:
    """Mask logits outside the top-p nucleus. logits: (..., V)."""
    sort_idx = jnp.argsort(-logits, axis=-1)
    sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p       # always keeps the argmax
    inv = jnp.argsort(sort_idx, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
    return jnp.where(keep, logits, -1e30)


def sample_tokens(key, logits, temperature: float, top_p: float
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """logits: (R, V) -> (tokens (R,), logprobs (R,)) under the sampling
    distribution (temperature-scaled, pre-top-p renormalized)."""
    lg = logits.astype(jnp.float32) / max(temperature, 1e-6)
    lg_samp = _top_p_mask(lg, top_p) if top_p < 1.0 else lg
    tok = jax.random.categorical(key, lg_samp, axis=-1)
    logp = jax.nn.log_softmax(lg, axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok.astype(jnp.int32), lp


@functools.partial(jax.jit, static_argnames=("temperature", "top_p"))
def fork_sample(logits_rows: jnp.ndarray, rows: jnp.ndarray, key, *,
                temperature: float, top_p: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched on-device fork divergence sampling.

    Gathers the requested boundary-logit rows from a round's (Rb, V) device
    buffer and draws one token (+ its logprob) per fork in a single
    dispatch — replacing the old one-numpy-sample-per-fork host loop.
    logits_rows: (Rb, V) f32; rows: (F,) int32 row indices (padded rows are
    sampled and discarded by the caller).
    """
    return sample_tokens(key, logits_rows[rows], temperature, top_p)


def sample_rows(keys: jnp.ndarray, logits: jnp.ndarray,
                temperature: float, top_p: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row keyed variant of :func:`sample_tokens` for the serve loop.

    keys: (R, 2) raw uint32 PRNG keys, one per row — each derived from
    (request key, absolute position), so row i's draw depends only on its
    own request identity, position and logits, never on which other
    requests happened to share the batch.  logits: (R, V).
    """
    lg = logits.astype(jnp.float32) / max(temperature, 1e-6)
    lg_samp = _top_p_mask(lg, top_p) if top_p < 1.0 else lg
    tok = jax.vmap(jax.random.categorical)(keys, lg_samp)
    logp = jax.nn.log_softmax(lg, axis=-1)
    lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok.astype(jnp.int32), lp


def sample_token_host(rng: np.random.Generator, logits: np.ndarray,
                      temperature: float, top_p: float
                      ) -> Tuple[int, float]:
    """Host-side mirror of ``sample_tokens`` — kept as a distribution
    oracle for tests/debugging; the engine itself samples on device."""
    lg = logits.astype(np.float64) / max(temperature, 1e-6)
    lg = lg - lg.max()
    if top_p < 1.0:
        order = np.argsort(-lg)
        p = np.exp(lg[order])
        p /= p.sum()
        cum = np.cumsum(p)
        cut = np.searchsorted(cum, top_p) + 1
        mask = np.full_like(lg, -np.inf)
        mask[order[:cut]] = lg[order[:cut]]
        lg_samp = mask
    else:
        lg_samp = lg
    p = np.exp(lg_samp - lg_samp.max())
    p /= p.sum()
    tok = int(rng.choice(len(p), p=p))
    logp_all = lg - np.log(np.exp(lg).sum())
    return tok, float(logp_all[tok])


# the single jit-shape bucketing policy, shared with kv.cache's pad buckets
_bucket = bucket_pow2


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class TreeEngine:
    """Paged tree-decoding engine for one model replica.

    Scheduling-policy half of the Scheduler / ModelRunner split: owns path
    lifecycle (alloc/fork/preempt/release), pressure handling and host
    packing; every device concern (params, KV pools, jitted prefill /
    decode / serve functions) is delegated to ``self.runner``.
    """

    def __init__(self, params, cfg: ModelConfig, tree_cfg: TreeConfig, *,
                 num_pages: int = 4096, page_size: Optional[int] = None,
                 max_slots: int = 256, max_queries: int = 64,
                 max_prompt_len: int = 512, enc_len: int = 64,
                 dtype=jnp.float32, seed: int = 0, fused_kv: bool = True,
                 paged_num_buffers: int = 2,
                 programs: Optional[ProgramTable] = None):
        self.runner = ModelRunner(
            params, cfg, tree_cfg, num_pages=num_pages,
            page_size=page_size, max_slots=max_slots,
            max_queries=max_queries, max_prompt_len=max_prompt_len,
            enc_len=enc_len, dtype=dtype, seed=seed, fused_kv=fused_kv,
            paged_num_buffers=paged_num_buffers, programs=programs)
        self.stats = EngineStats()
        # pressure callback: called with the page deficit when an alloc
        # fails; frees pages (retracting retained/active KV) and the
        # allocation is retried once (docs/robustness.md)
        self._pressure_cb: Optional[Any] = None
        # optional cross-request radix cache (repro.kv.radix): its LRU
        # leaves are evicted before the preemption callback is consulted
        self._radix: Optional[Any] = None

    # -- runner delegation ----------------------------------------------------
    # Device state lives on the ModelRunner; these keep the engine's public
    # surface (and the sampler/trainer/tests that use it) unchanged.

    @property
    def params(self):
        return self.runner.params

    @params.setter
    def params(self, value) -> None:
        self.runner.params = value

    @property
    def cfg(self) -> ModelConfig:
        return self.runner.cfg

    @property
    def tree_cfg(self) -> TreeConfig:
        return self.runner.tree_cfg

    @property
    def kv(self) -> PagedKVState:
        return self.runner.kv

    @property
    def cross_pool(self):
        return self.runner.cross_pool

    @cross_pool.setter
    def cross_pool(self, value) -> None:
        self.runner.cross_pool = value

    @property
    def qslot_alloc(self) -> List[int]:
        return self.runner.qslot_alloc

    @property
    def page_size(self) -> int:
        return self.runner.page_size

    @property
    def max_prompt_len(self) -> int:
        return self.runner.max_prompt_len

    @property
    def dtype(self):
        return self.runner.dtype

    @property
    def MP(self) -> int:
        return self.runner.MP

    @property
    def fused_kv(self) -> bool:
        return self.runner.fused_kv

    @property
    def garbage_page(self) -> int:
        return self.runner.garbage_page

    @property
    def scratch_slot(self) -> int:
        return self.runner.scratch_slot

    @property
    def has_rec(self) -> bool:
        return self.runner.has_rec

    @property
    def has_cross(self) -> bool:
        return self.runner.has_cross

    @property
    def enc_len(self) -> int:
        return self.runner.enc_len

    @property
    def n_prefix(self) -> int:
        return self.runner.n_prefix

    # -- misc -----------------------------------------------------------------

    def close(self) -> None:
        """Free the device state once no path will be decoded again
        (see :meth:`ModelRunner.close`); ``stats`` and the allocators
        stay readable."""
        self.runner.close()

    def _next_key(self):
        return self.runner.next_key()

    def _track_pages(self):
        self.stats.peak_pages = max(self.stats.peak_pages,
                                    self.kv.pool.pages_in_use)

    # -- pressure / preemption ----------------------------------------------

    def attach_radix(self, radix) -> None:
        """Register a cross-request radix cache (``repro.kv.radix``).

        Under pressure the engine evicts the cache's LRU leaves before
        consulting the preemption callback, and :meth:`pressure` discounts
        cache-held pages that could be reclaimed on demand — a pool kept
        warm by the cache must not throttle branching or trigger
        preemption while eviction can still satisfy the demand."""
        self._radix = radix

    def pressure(self) -> float:
        """Effective KV pool occupancy in [0, 1] — the branching throttle
        signal.  Evictable radix-cache pages count as free."""
        pool = self.kv.pool
        if self._radix is None:
            return pool.watermark
        held = pool.pages_in_use - self._radix.evictable_pages
        return max(held, 0) / max(pool.num_pages, 1)

    def pages_free(self) -> int:
        return self.kv.pool.num_free

    @property
    def can_restore(self) -> bool:
        """True when a preempted path is exactly reconstructable from its
        token history alone — no retained modality prefix (VLM) and no
        cross-KV conditioning (enc-dec), both of which live outside the
        path's tokens."""
        return self.n_prefix == 0 and not self.has_cross

    def set_pressure_cb(self, cb) -> None:
        """Install ``cb(page_deficit) -> pages_freed``, consulted when a
        page/slot alloc fails before the allocation is retried once."""
        self._pressure_cb = cb

    def _alloc_page(self) -> int:
        try:
            return self.kv.pool.alloc()
        except OutOfPages:
            self.stats.pressure_events += 1
            # eviction before preemption: cache-held prefix KV is
            # recomputable, a live path's working set is not — reclaim
            # LRU radix leaves first and only then consult the
            # preemption callback
            if self._radix is not None and self._radix.evict(1) > 0:
                try:
                    return self.kv.pool.alloc()
                except OutOfPages:
                    pass
            if self._pressure_cb is not None:
                self._pressure_cb(1)
            # retry once: an injected fault's spec is consumed and a real
            # exhaustion either recovered via the callback or re-raises
            # with full allocator diagnostics
            try:
                return self.kv.pool.alloc()
            except OutOfPages as exc:
                if self._radix is not None:
                    exc.annotate(radix_pages=self._radix.cached_pages,
                                 radix_evictable=self._radix.evictable_pages)
                raise

    def _alloc_slot(self) -> int:
        try:
            return self.kv.slots.alloc()
        except OutOfPages:
            self.stats.pressure_events += 1
            if self._pressure_cb is not None:
                self._pressure_cb(1)
            return self.kv.slots.alloc()

    def preempt_path(self, path: EnginePath) -> int:
        """Retract an active path under KV pressure: free its pages/slot
        and report how many pages actually returned to the pool (shared
        prefix pages stay refcounted by siblings).  The caller keeps the
        host-side tokens and re-admits via :meth:`restore_path` when the
        budget recovers."""
        before = self.kv.pool.pages_in_use
        self.release_path(path)
        self.stats.preempted_paths += 1
        return before - self.kv.pool.pages_in_use

    def restore_path(self, tokens: List[int]) -> EnginePath:
        """Regenerate a preempted path by replaying its full token history
        (prompt + generated) into fresh pages — the `_replay_prefix`
        machinery DFS fallback already uses.  Returns a path with boundary
        logits and a freshly drawn pending token (the preempted pending
        draw is not retained; the continuation re-samples)."""
        assert self.can_restore, \
            "restore_path needs a token-complete context (no modality " \
            "prefix / cross-KV)"
        position = self.n_prefix + len(tokens)
        child = EnginePath(table=[], slot=-1, qslot=-1, position=position,
                           pending_token=0, pending_logprob=0.0)
        try:
            self._ensure_capacity(child, position)
            if self.has_rec:
                child.slot = self._alloc_slot()
            self._replay_prefix(child, list(tokens))
        except Exception:
            # an OutOfPages mid-restore must not leak the pages already
            # replayed into the half-built path (R5 kv-lifecycle)
            self.release_partial([child])
            raise
        self.sample_pending_batch([child])
        self.stats.regenerated_paths += 1
        return child

    # -- page / slot lifecycle --------------------------------------------------

    def _ensure_capacity(self, path: EnginePath, new_len: int) -> None:
        needed = -(-new_len // self.page_size)
        while len(path.table) < needed:
            path.table.append(self._alloc_page())
        self._track_pages()

    def _cow_pages(self, path: EnginePath, page_idxs
                   ) -> Tuple[List[int], List[int]]:
        """Host bookkeeping for COW of ``path.table[idx]`` for each idx:
        allocate private pages and retarget the table, returning the
        (src, dst) copy pairs for a later batched ``kv.apply_forks``.
        Sources stay refcounted by their other owners, so deferring the
        device copy to the end of the round is safe."""
        src_pages: List[int] = []
        dst_pages: List[int] = []
        for page_idx in page_idxs:
            src = path.table[page_idx]
            if self.kv.pool.refcount[src] == 1:
                continue  # already private
            dst = self._alloc_page()
            self.kv.pool.release(src)
            path.table[page_idx] = dst
            src_pages.append(src)
            dst_pages.append(dst)
            self.stats.cow_pages += 1
        self._track_pages()
        return src_pages, dst_pages

    # -- on-device fork sampling ------------------------------------------------

    def sample_pending_batch(self, paths: Sequence[EnginePath]) -> None:
        """Resample every path's pending token from its device-side
        boundary logits — one ``fork_sample`` dispatch per distinct logits
        buffer (a branching round shares a single buffer, so normally one).
        Only (F,) tokens + logprobs cross to the host."""
        groups: Dict[int, Tuple[jnp.ndarray, List[EnginePath]]] = {}
        for p in paths:
            assert p.logits_buf is not None, \
                "path has no boundary logits to sample from"
            groups.setdefault(id(p.logits_buf),
                              (p.logits_buf, []))[1].append(p)
        tc = self.tree_cfg
        for buf, ps in groups.values():
            F = len(ps)
            Fb = _bucket(F)
            rows = annotated_transfer(
                np.asarray([p.logits_row for p in ps] + [0] * (Fb - F),
                           np.int32),
                to="device", reason="fork-rows")
            tok, lp = fork_sample(buf, rows, self._next_key(),
                                  temperature=tc.temperature,
                                  top_p=tc.top_p)
            # one batched pull for the round's divergence draws
            tok, lp = annotated_transfer((tok, lp), reason="fork-draws")
            self.stats.host_bytes += tok.nbytes + lp.nbytes
            self.stats.fork_dispatches += 1
            lp = faults.corrupt_array("engine.fork_logprobs", lp)
            for j, p in enumerate(ps):
                p.pending_token = int(tok[j])
                p.pending_logprob = float(lp[j])
                # non-finite divergence draw: the boundary logits are
                # poisoned — mark for quarantine instead of decoding on
                if not np.isfinite(lp[j]):
                    p.numeric_bad = True
                    self.stats.quarantined_paths += 1

    def release_path(self, path: EnginePath) -> None:
        if path.released:
            return
        self.kv.release_table(path.table)
        path.table = []
        if path.slot >= 0:
            self.kv.slots.release(path.slot)
            path.slot = -1
        # drop the boundary-logits reference: a released path must not pin
        # its round's (Rb, V) device buffer for the rollout's lifetime
        path.logits_buf = None
        path.released = True

    def release_qslot(self, qslot: int) -> None:
        if qslot >= 0:
            self.qslot_alloc.append(qslot)

    def release_partial(self, paths: Sequence[EnginePath]) -> None:
        """Error-path cleanup for a partially constructed batch: when an
        ``OutOfPages`` (or a fault-injection kill point) unwinds mid-
        construction, every page/slot the batch acquired so far goes
        back to the pool.  Safe on half-built paths — empty tables,
        unset slots and already-released paths are all no-ops."""
        for path in paths:
            self.release_path(path)
        self._track_pages()

    # -- prefill ------------------------------------------------------------------

    @spanned("engine.prefill",
             lambda self, prompts, *a, **k: {"queries": len(prompts)})
    def prefill_queries(self, prompts: List[List[int]],
                        prefix_embeds: Optional[np.ndarray] = None,
                        enc_frames: Optional[np.ndarray] = None
                        ) -> List[EnginePath]:
        """Prefill each query once (the tree root's shared KV).

        prompts: per-query token lists.  prefix_embeds: (Q, P, d) VLM stub;
        enc_frames: (Q, S_enc, d_enc) audio stub.  Returns one root
        EnginePath per query with ``pending_token`` already sampled.
        """
        Q = len(prompts)
        n_pre = self.n_prefix
        max_sp = max(len(p) for p in prompts)
        Sp = _bucket(max_sp, 8)
        Qb = _bucket(Q)
        tokens = np.zeros((Qb, Sp), np.int32)
        lengths = np.zeros((Qb,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, : len(p)] = p
            lengths[i] = len(p) + n_pre
        lengths[Q:] = 1  # dummies

        paths: List[EnginePath] = []
        tables = np.zeros((Qb, self.MP), np.int32)
        slots = np.zeros((Qb,), np.int32)
        qslots = np.zeros((Qb,), np.int32)
        try:
            for i in range(Qb):
                if i < Q:
                    pth = EnginePath(table=[], slot=-1, qslot=-1,
                                     position=int(lengths[i]),
                                     pending_token=0, pending_logprob=0.0)
                    # appended before the allocs so the error path below
                    # can clean up the half-built root too
                    paths.append(pth)
                    self._ensure_capacity(pth, int(lengths[i]))
                    if self.has_rec:
                        pth.slot = self._alloc_slot()
                    if self.has_cross or n_pre:
                        pth.qslot = self.qslot_alloc.pop() \
                            if self.has_cross else -1
                    row = pth.table + [-1] * (self.MP - len(pth.table))
                    tables[i] = row
                    slots[i] = pth.slot if pth.slot >= 0 \
                        else self.scratch_slot
                    qslots[i] = max(pth.qslot, 0)
                else:
                    tables[i, 0] = self.garbage_page
                    tables[i, 1:] = -1
                    slots[i] = max(self.scratch_slot, 0)
        except Exception:
            # OutOfPages mid-batch: return the roots built so far (pages,
            # slots *and* popped query slots) before propagating
            for pth in paths:
                if pth.qslot >= 0:
                    self.release_qslot(pth.qslot)
            self.release_partial(paths)
            raise

        if prefix_embeds is not None:
            pe = np.zeros((Qb,) + prefix_embeds.shape[1:],
                          prefix_embeds.dtype)
            pe[:Q] = prefix_embeds
            prefix_embeds = pe
        if enc_frames is not None:
            ef = np.zeros((Qb,) + enc_frames.shape[1:], enc_frames.dtype)
            ef[:Q] = enc_frames
            enc_frames = ef

        fn = self.runner.get_prefill_fn(Qb, Sp, prefix_embeds is not None,
                                        enc_frames is not None)
        # one batched h2d push for the whole prefill pack
        (tokens, lengths, tables, slots, qslots, prefix_embeds,
         enc_frames) = annotated_transfer(
            (tokens, lengths, tables, slots, qslots, prefix_embeds,
             enc_frames), to="device", reason="prefill-pack")
        pools, rec, cross, logits = fn(
            self.params, self.kv.kv_pools, self.kv.rec_state,
            self.cross_pool, tokens, lengths,
            tables, slots, qslots,
            prefix_embeds, enc_frames)
        self.kv.kv_pools = pools
        self.kv.rec_state = rec
        self.cross_pool = cross
        logits = logits.astype(jnp.float32)   # stays on device
        for i, pth in enumerate(paths):
            pth.logits_buf = logits
            pth.logits_row = i
        self.sample_pending_batch(paths)
        self.stats.prefill_tokens += sum(len(p) + n_pre for p in prompts)
        return paths

    # -- fork ----------------------------------------------------------------------

    @spanned("engine.fork",
             lambda self, parents, **k: {"forks": len(parents)})
    def fork_paths(self, parents: Sequence[EnginePath], *,
                   resample: bool = True) -> List[EnginePath]:
        """Batched branch of a whole round: for every parent (repeat a
        parent to fork it several times) share every full page, COW the
        partial tail page, and copy recurrent state — all fork copies land
        in ONE jitted ``kv.apply_forks`` dispatch — then draw every child's
        divergence token in one on-device ``fork_sample`` dispatch."""
        children: List[EnginePath] = []
        page_src: List[int] = []
        page_dst: List[int] = []
        slot_src: List[int] = []
        slot_dst: List[int] = []
        try:
            for parent in parents:
                child = EnginePath(
                    table=self.kv.fork_table(parent.table),
                    slot=-1, qslot=parent.qslot, position=parent.position,
                    pending_token=parent.pending_token,
                    pending_logprob=parent.pending_logprob,
                    logits_buf=parent.logits_buf,
                    logits_row=parent.logits_row)
                # appended before the COW/slot allocs so the error path
                # below also releases the partially built child
                children.append(child)
                if parent.position % self.page_size != 0:
                    ps, pd = self._cow_pages(
                        child, [parent.position // self.page_size])
                    page_src += ps
                    page_dst += pd
                if parent.slot >= 0:
                    child.slot = self._alloc_slot()
                    slot_src.append(parent.slot)
                    slot_dst.append(child.slot)
        except Exception:
            # OutOfPages mid-round: drop every fork_table retain / COW
            # page / slot the round acquired so far, then propagate —
            # the parents stay intact (their refcounts were only added to)
            self.release_partial(children)
            raise
        if page_src or slot_src:
            try:
                self.kv.apply_forks(page_src, page_dst, slot_src, slot_dst)
            except Exception:
                # a failure inside the fork-copy dispatch (injected kill
                # point, device OOM) leaves the pools unrebound — no child
                # can hold copied K with stale V — but the round's fresh
                # COW pages / slots / table retains must go back, or the
                # half-applied fork leaks them for the rollout's lifetime
                self.release_partial(children)
                raise
            self.stats.fork_dispatches += 1
        self.stats.forks += len(children)
        self._track_pages()
        if resample:
            self.sample_pending_batch(
                [c for c in children if c.logits_buf is not None])
        return children

    def fork_path(self, parent: EnginePath, *, resample: bool = True
                  ) -> EnginePath:
        """Single-parent convenience wrapper over :meth:`fork_paths`."""
        return self.fork_paths([parent], resample=resample)[0]

    def fork_from_prefix(self, src: EnginePath, prefix_position: int,
                         replay_tokens: Optional[List[int]] = None
                         ) -> EnginePath:
        """Fallback fork: a new path whose context is the first
        ``prefix_position`` tokens of ``src``.

        Attention-only archs: share the prefix pages and run one re-feed
        decode step to recover boundary logits.  Recurrent archs: replay
        the prefix through prefill into COW'd pages (state cannot be
        recovered from the KV pool) — ``replay_tokens`` must then hold the
        full token sequence (prompt + generated prefix).
        """
        n_pages = -(-prefix_position // self.page_size)
        child = EnginePath(
            table=self.kv.fork_table(src.table[:n_pages]),
            slot=-1, qslot=src.qslot, position=prefix_position,
            pending_token=0, pending_logprob=0.0)
        try:
            self._fork_from_prefix_arm(child, prefix_position,
                                       replay_tokens)
        except Exception:
            # OutOfPages mid-fallback-fork: the shared-prefix retains and
            # any COW pages / slot must go back before propagating
            self.release_partial([child])
            raise
        self.sample_pending_batch([child])
        self.stats.forks += 1
        return child

    def _fork_from_prefix_arm(self, child: EnginePath,
                              prefix_position: int,
                              replay_tokens: Optional[List[int]]) -> None:
        if self.has_rec:
            assert replay_tokens is not None and \
                len(replay_tokens) >= prefix_position - self.n_prefix, \
                "fork_from_prefix on a recurrent arch needs the full " \
                "prompt+prefix token sequence in replay_tokens"
            child.slot = self._alloc_slot()
            # replay rewrites every position it will ever read, so COW here
            # is bookkeeping only: retarget the table to fresh pages and
            # skip the device copy of bytes the prefill immediately clobbers
            self._cow_pages(child, range(len(child.table)))
            self._replay_prefix(child, replay_tokens[: prefix_position
                                                     - self.n_prefix])
        else:
            assert replay_tokens is not None and \
                len(replay_tokens) >= prefix_position - self.n_prefix, \
                "fork_from_prefix on an attention arch needs replay_tokens" \
                " to re-feed the boundary token (got None / too short)"
            # COW the page holding the boundary token (position-1): _refeed
            # rewrites its KV, and prefill/decode reduction orders differ at
            # the ULP level — writing into a still-shared page would perturb
            # the source path's siblings.  Covers both the misaligned tail
            # and the page-aligned case (where the boundary token is the
            # last row of the final shared page).
            ps, pd = self._cow_pages(
                child, [(prefix_position - 1) // self.page_size])
            if ps:
                self.kv.apply_forks(ps, pd)
                self.stats.fork_dispatches += 1
            self._refeed(child, replay_tokens[prefix_position
                                              - self.n_prefix - 1])

    def _replay_prefix(self, child: EnginePath, tokens: List[int]) -> None:
        """Recurrent-arch fallback: prefill the prefix into the child's
        (COW'd) pages + slot; leaves boundary logits on the child."""
        Sp = _bucket(len(tokens), 8)
        toks = np.zeros((1, Sp), np.int32)
        toks[0, : len(tokens)] = tokens
        lengths = np.asarray([len(tokens) + self.n_prefix], np.int32)
        tables = np.full((1, self.MP), -1, np.int32)
        tables[0, : len(child.table)] = child.table
        slots = np.asarray([child.slot if child.slot >= 0
                            else self.scratch_slot], np.int32)
        qslots = np.asarray([max(child.qslot, 0)], np.int32)
        fn = self.runner.get_prefill_fn(1, Sp, False, False)
        toks, lengths, tables, slots, qslots = annotated_transfer(
            (toks, lengths, tables, slots, qslots), to="device",
            reason="replay-pack")
        pools, rec, cross, logits = fn(
            self.params, self.kv.kv_pools, self.kv.rec_state,
            self.cross_pool, toks, lengths, tables, slots, qslots,
            None, None)
        self.kv.kv_pools, self.kv.rec_state = pools, rec
        child.logits_buf = logits.astype(jnp.float32)   # stays on device
        child.logits_row = 0
        self.stats.replay_tokens += len(tokens)

    def _refeed(self, child: EnginePath, last_token: int) -> None:
        """Attention-arch fallback: one decode step re-feeding the final
        prefix token (identical KV values — benign write) to recover the
        boundary logits."""
        child.position -= 1
        child.pending_token = int(last_token)
        child.pending_logprob = 0.0
        # decode_segments(seg_len=1) rewrites the (identical) KV of the
        # re-fed token and leaves the boundary logits on the child.
        self.decode_segments([child], seg_len=1)
        self.stats.replay_tokens += 1

    # -- segment decode ----------------------------------------------------------

    @spanned("engine.decode", lambda self, paths, seg_len=None: {
        "rows": len(paths), "seg_len": seg_len or self.tree_cfg.segment_len})
    def decode_segments(self, paths: List[EnginePath],
                        seg_len: Optional[int] = None
                        ) -> List[SegmentResult]:
        """Generate one ``l``-token segment for every path (batched)."""
        l = seg_len or self.tree_cfg.segment_len
        R = len(paths)
        if R == 0:
            return []
        Rb = _bucket(R)
        tok0 = np.zeros((Rb,), np.int32)
        lp0 = np.zeros((Rb,), np.float32)
        pos0 = np.zeros((Rb,), np.int32)
        tables = np.full((Rb, self.MP), -1, np.int32)
        slots = np.full((Rb,), max(self.scratch_slot, 0), np.int32)
        qslots = np.zeros((Rb,), np.int32)
        for i, p in enumerate(paths):
            self._ensure_capacity(p, p.position + l)
            tok0[i] = p.pending_token
            lp0[i] = p.pending_logprob
            pos0[i] = p.position
            tables[i, : len(p.table)] = p.table
            if p.slot >= 0:
                slots[i] = p.slot
            qslots[i] = max(p.qslot, 0)
        tables[R:, 0] = self.garbage_page

        fn = self.runner.get_decode_fn(Rb, l)
        tok0, lp0, pos0, tables, slots, qslots = annotated_transfer(
            (tok0, lp0, pos0, tables, slots, qslots), to="device",
            reason="decode-pack")
        pools, rec, toks, lps, pend_tok, pend_lp, last_logits = fn(
            self.params, self.kv.kv_pools, self.kv.rec_state,
            self.cross_pool, tok0, lp0, pos0, tables, slots,
            qslots, self._next_key())
        self.kv.kv_pools = pools
        self.kv.rec_state = rec
        # steady-state host transfer: O(R*l) tokens/logprobs + O(R) pending
        # scalars, pulled in ONE batched device_get.  The (Rb, V) boundary
        # logits stay on device — forks sample from them via fork_sample.
        toks, lps, pend_tok, pend_lp = annotated_transfer(
            (toks, lps, pend_tok, pend_lp), reason="decode-segment")
        self.stats.host_bytes += (toks.nbytes + lps.nbytes
                                  + pend_tok.nbytes + pend_lp.nbytes)
        lps = faults.corrupt_array("engine.decode_logprobs", lps)

        results = []
        for i, p in enumerate(paths):
            p.position += l
            p.pending_token = int(pend_tok[i])
            p.pending_logprob = float(pend_lp[i])
            p.logits_buf = last_logits
            p.logits_row = i
            seg_t = [int(t) for t in toks[i]]
            seg_l = [float(v) for v in lps[i]]
            # numeric quarantine: a non-finite logprob in this row means
            # the model emitted non-finite logits for this path — flag the
            # segment so the sampler retires the path instead of training
            # on poisoned signal (docs/robustness.md)
            finite = bool(np.isfinite(lps[i]).all()
                          and np.isfinite(pend_lp[i]))
            if not finite:
                self.stats.quarantined_paths += 1
            results.append(SegmentResult(
                tokens=seg_t, logprobs=seg_l,
                seg_logprob=float(np.mean(seg_l)), finite=finite))
        self.stats.decode_tokens += R * l
        self.stats.segments += R
        return results

    # -- cross-kv (whisper) -------------------------------------------------------

    # handled inside prefill via enc_frames; decode gathers by qslot.


# ---------------------------------------------------------------------------
# model runner: device state + jitted device functions
# ---------------------------------------------------------------------------

class ModelRunner:
    """Device-execution half of the Scheduler / ModelRunner split.

    Owns the params, the paged KV state and the cross-attention pools,
    and takes its jitted prefill / decode / serve functions from
    ``programs`` (a private :class:`ProgramTable` unless one is given)
    under ``signature``, every static value those functions read.  It
    knows nothing about paths, forks or preemption — ``TreeEngine`` (tree
    rollouts) and ``repro.core.scheduler.Scheduler`` (continuous batching)
    are its two scheduling frontends.
    """

    def __init__(self, params, cfg: ModelConfig, tree_cfg: TreeConfig, *,
                 num_pages: int = 4096, page_size: Optional[int] = None,
                 max_slots: int = 256, max_queries: int = 64,
                 max_prompt_len: int = 512, enc_len: int = 64,
                 dtype=jnp.float32, seed: int = 0, fused_kv: bool = True,
                 paged_num_buffers: int = 2,
                 programs: Optional[ProgramTable] = None):
        self.params = params
        self.cfg = cfg
        self.tree_cfg = tree_cfg
        self.page_size = page_size or min(64, tree_cfg.segment_len)
        self.max_prompt_len = max_prompt_len
        self.dtype = dtype
        self.fused_kv = fused_kv
        # DMA ring depth of the pipelined paged kernels (bitwise-invariant
        # scheduling knob — benchmarks/profile_dma_compute.py sweeps it)
        self.paged_num_buffers = paged_num_buffers
        max_len = max_prompt_len + tree_cfg.max_response_len + enc_len
        self.MP = -(-max_len // self.page_size) + 1
        self.programs = programs if programs is not None else ProgramTable()
        self.kv = PagedKVState(cfg, num_pages, self.page_size, max_slots,
                               dtype, fused_kv=fused_kv,
                               programs=self.programs)
        # page 0 = garbage sink for padded-position writes; slot 0 = scratch
        self.garbage_page = self.kv.pool.alloc()
        assert self.garbage_page == 0
        self.scratch_slot = self.kv.slots.alloc() if self.kv.rec_state else -1
        self.has_rec = bool(self.kv.rec_state)
        self.has_cross = cfg.encoder is not None
        self.enc_len = enc_len
        self.cross_pool: Dict[int, Dict[str, jnp.ndarray]] = {}
        self.qslot_alloc: List[int] = list(range(max_queries - 1, -1, -1))
        if self.has_cross:
            hd = cfg.resolved_head_dim
            for i in range(cfg.num_layers):
                self.cross_pool[i] = {
                    "k": jnp.zeros((max_queries, enc_len, cfg.num_kv_heads,
                                    hd), dtype),
                    "v": jnp.zeros((max_queries, enc_len, cfg.num_kv_heads,
                                    hd), dtype),
                }
        self.n_prefix = (cfg.frontend.num_prefix_tokens
                         if cfg.frontend is not None
                         and cfg.frontend.kind == "vision" else 0)
        self.windows = tuple(
            cfg.sliding_window if (cfg.sliding_window > 0
                                   and not cfg.is_global_attn_layer(i))
            else 0 for i in range(cfg.num_layers))
        # every static value the jitted functions read; runners with equal
        # signatures share a table's programs bucket for bucket
        self.signature = (cfg, tree_cfg.temperature, tree_cfg.top_p,
                          self.page_size, jnp.dtype(dtype), fused_kv,
                          paged_num_buffers, self.n_prefix, self.has_cross,
                          enc_len, self.windows, num_pages, max_slots,
                          self.MP, max_queries)
        self._key = jax.random.PRNGKey(seed)

    def next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def close(self) -> None:
        """Drop the KV pools, recurrent state and cross-attention pools.
        Without it they live as long as the runner, and a runner held in
        a reference cycle (a wrapped method, a callback) lives until the
        cyclic collector runs, which host code that builds no programs
        rarely triggers."""
        self.kv.kv_pools = {}
        self.kv.rec_state = {}
        self.cross_pool = {}

    # =================== jitted device functions =================================

    def get_prefill_fn(self, Q: int, Sp: int, has_prefix: bool,
                       has_frames: bool):
        return self.programs.get(self.signature, "prefill",
                                 (Q, Sp, has_prefix, has_frames),
                                 lambda: self._build_prefill(Q, Sp))

    def _build_prefill(self, Q: int, Sp: int):
        cfg = self.cfg
        page = self.page_size
        n_pre = self.n_prefix
        pool_dtype = self.dtype
        fused = self.fused_kv

        def prefill_fn(params, pools, rec, cross, tokens, lengths, tables,
                       slots, qslots, prefix_embeds, enc_frames):
            B = tokens.shape[0]
            x = embed(params["embed"], tokens)            # (Q,Sp,d)
            if prefix_embeds is not None and cfg.encoder is None:
                x = jnp.concatenate(
                    [prefix_embeds.astype(x.dtype), x], axis=1)
            S = x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S), (B, S))
            mask = positions < lengths[:, None]            # (Q,S)
            pos_flat = jnp.arange(S)
            page_idx = pos_flat // page                    # (S,)
            offs = jnp.broadcast_to(pos_flat % page, (B, S))
            pids = jnp.where(mask, jnp.maximum(
                jnp.take_along_axis(
                    tables, jnp.broadcast_to(page_idx, (B, S)), axis=1), 0),
                0)

            enc_out = None
            if cfg.encoder is not None:
                from repro.models.model import encode
                enc_out = encode(params, cfg, enc_frames)
                x = x + sinusoidal_positions(S, cfg.d_model).astype(
                    x.dtype)[None]

            new_rec = dict(rec)
            new_pools = dict(pools)
            new_cross = dict(cross)
            last = lengths - 1
            for i, lp in enumerate(params["layers"]):
                kind = cfg.layer_kind(i)
                h = rmsnorm(lp["norm1"], x, cfg.norm_eps)
                if kind == "attn":
                    if cfg.attention_kind == "mla":
                        y, (ckv, k_rope) = attn.mla_forward(
                            lp["attn"], cfg, h, positions, i, return_kv=True)
                        if fused:
                            new_pools[i] = {
                                "kv": new_pools[i]["kv"].at[pids, offs].set(
                                    fuse_mla(ckv, k_rope)
                                    .astype(pool_dtype)),
                            }
                        else:
                            new_pools[i] = {
                                "ckv": new_pools[i]["ckv"]
                                .at[pids, offs].set(ckv.astype(pool_dtype)),
                                "k_rope": new_pools[i]["k_rope"]
                                .at[pids, offs].set(
                                    k_rope.astype(pool_dtype)),
                            }
                    else:
                        y, (k, v) = attn.gqa_forward(
                            lp["attn"], cfg, h, positions, i, return_kv=True)
                        if fused:
                            new_pools[i] = {
                                "kv": new_pools[i]["kv"].at[pids, offs].set(
                                    interleave_kv(k, v).astype(pool_dtype)),
                            }
                        else:
                            new_pools[i] = {
                                "k": new_pools[i]["k"].at[pids, offs].set(
                                    k.astype(pool_dtype)),
                                "v": new_pools[i]["v"].at[pids, offs].set(
                                    v.astype(pool_dtype)),
                            }
                elif kind == "mamba":
                    y, st = ssm.mamba_forward(lp["mamba"], cfg, h,
                                              mask=mask, last_idx=last)
                    new_rec[i] = {
                        "conv": new_rec[i]["conv"].at[slots].set(
                            st["conv"].astype(pool_dtype)),
                        "ssm": new_rec[i]["ssm"].at[slots].set(st["ssm"]),
                    }
                elif kind == "rwkv":
                    zero = {
                        "wkv": jnp.zeros(
                            (B, cfg.d_model // cfg.rwkv.head_dim,
                             cfg.rwkv.head_dim, cfg.rwkv.head_dim),
                            jnp.float32),
                        "shift": jnp.zeros((B, cfg.d_model), x.dtype),
                    }
                    y, st = ssm.rwkv6_time_mix(lp["rwkv"], cfg, h, zero,
                                               mask=mask, last_idx=last)
                    new_rec[i] = dict(
                        new_rec[i],
                        wkv=new_rec[i]["wkv"].at[slots].set(st["wkv"]),
                        shift=new_rec[i]["shift"].at[slots].set(
                            st["shift"].astype(pool_dtype)))
                x = x + y
                if cfg.encoder is not None:
                    hc = rmsnorm(lp["norm_cross"], x, cfg.norm_eps)
                    k_c, v_c = attn.cross_attn_kv(lp["cross"], cfg, enc_out)
                    x = x + attn.cross_attn_forward(lp["cross"], cfg, hc,
                                                    k_c, v_c)
                    new_cross[i] = {
                        "k": new_cross[i]["k"].at[qslots].set(
                            k_c.astype(pool_dtype)),
                        "v": new_cross[i]["v"].at[qslots].set(
                            v_c.astype(pool_dtype)),
                    }
                h = rmsnorm(lp["norm2"], x, cfg.norm_eps)
                if kind == "rwkv":
                    y, sh = ssm.rwkv6_channel_mix(
                        lp["ffn"], h, jnp.zeros((B, cfg.d_model), h.dtype),
                        last_idx=last)
                    new_rec[i] = dict(
                        new_rec[i],
                        shift_ffn=new_rec[i]["shift_ffn"].at[slots].set(
                            sh.astype(pool_dtype)))
                elif "ffn_moe" in lp:
                    y, _ = moe_mod.moe_forward(lp["ffn_moe"], cfg, h,
                                               cfg.act)
                else:
                    y = mlp(lp["ffn"], h, cfg.act)
                x = x + y
            x = rmsnorm(params["norm_f"], x, cfg.norm_eps)
            x_last = x[jnp.arange(B), last]
            logits = unembed(params["embed"], x_last, cfg.tie_embeddings)
            return new_pools, new_rec, new_cross, logits

        return jax.jit(prefill_fn)

    def get_decode_fn(self, R: int, l: int):
        return self.programs.get(self.signature, "decode", (R, l),
                                 lambda: self._build_decode(R, l))

    def get_serve_fn(self, R: int, l: int):
        return self.programs.get(self.signature, "serve", (R, l),
                                 lambda: self._build_serve(R, l))

    def _make_token_forward(self, R: int):
        """One decoding step for a (R,)-row batch, shared by the tree
        decode and the continuous-batching serve scan bodies: embed the
        incoming token, write its KV into the block-table page, run every
        layer through the paged kernels, return the next-token logits."""
        cfg = self.cfg
        page = self.page_size
        pool_dtype = self.dtype
        windows = self.windows
        has_cross = self.has_cross
        fused = self.fused_kv
        nbuf = self.paged_num_buffers

        def mla_paged_attn(lp_attn, q_nope, q_rope, pools_i, tables,
                           lengths):
            """Absorbed MLA decode via the paged Pallas kernel: absorb W_uk
            into the query, attend over the latent pages named by the block
            table (scalar-prefetch indirection — no dense (R, MP*page, r)
            gather), then up-project the latent aggregate with W_uv."""
            m = cfg.mla
            H = cfg.num_heads
            w_uk = lp_attn["w_uk"].reshape(m.kv_lora_rank, H,
                                           m.qk_nope_head_dim)
            q_lat = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            if fused:
                o_lat = kops.mla_fused_paged_attention(
                    q_lat, q_rope.astype(jnp.float32), pools_i["kv"],
                    tables, lengths, page_size=page,
                    scale=1.0 / (m.qk_head_dim ** 0.5), num_buffers=nbuf)
            else:
                o_lat = kops.mla_paged_attention(
                    q_lat, q_rope.astype(jnp.float32), pools_i["ckv"],
                    pools_i["k_rope"], tables, lengths, page_size=page,
                    scale=1.0 / (m.qk_head_dim ** 0.5))
            w_uv = lp_attn["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
            o = jnp.einsum("bhr,rhd->bhd", o_lat,
                           w_uv.astype(jnp.float32))
            return o.reshape(o.shape[0], -1)

        def token_forward(params, pools, rec_g, cross_g, tok, pos, tables):
            x = embed(params["embed"], tok)            # (R,d)
            if cfg.encoder is not None:
                pe = sinusoidal_positions(
                    cfg.max_position_embeddings, cfg.d_model)
                x = x + pe[pos].astype(x.dtype)
            lengths = pos + 1
            pids = jnp.take_along_axis(
                jnp.maximum(tables, 0), (pos // page)[:, None],
                axis=1)[:, 0]
            offs = pos % page
            new_rec_g = dict(rec_g)
            new_pools = dict(pools)
            for i, lp_ in enumerate(params["layers"]):
                kind = cfg.layer_kind(i)
                h = rmsnorm(lp_["norm1"], x, cfg.norm_eps)
                if kind == "attn":
                    if cfg.attention_kind == "mla":
                        x1 = h[:, None, :]
                        q_nope, q_rope = attn._mla_q(
                            lp_["attn"], cfg, x1, pos[:, None])
                        q_nope, q_rope = q_nope[:, 0], q_rope[:, 0]
                        ckv_t, kr_t = attn._mla_latents(
                            lp_["attn"], cfg, x1, pos[:, None])
                        pi = new_pools[i]
                        if fused:
                            pi = {
                                "kv": pi["kv"].at[pids, offs].set(
                                    fuse_mla(ckv_t[:, 0], kr_t[:, 0])
                                    .astype(pool_dtype)),
                            }
                        else:
                            pi = {
                                "ckv": pi["ckv"].at[pids, offs].set(
                                    ckv_t[:, 0].astype(pool_dtype)),
                                "k_rope": pi["k_rope"].at[pids, offs].set(
                                    kr_t[:, 0].astype(pool_dtype)),
                            }
                        new_pools[i] = pi
                        o = mla_paged_attn(lp_["attn"], q_nope, q_rope,
                                           pi, tables, lengths)
                        y = o.astype(x.dtype) @ lp_["attn"]["w_o"]
                    else:
                        x1 = h[:, None, :]
                        q, k, v = attn._gqa_qkv(lp_["attn"], cfg, x1,
                                                pos[:, None])
                        q, k, v = q[:, 0], k[:, 0], v[:, 0]
                        pi = new_pools[i]
                        if fused:
                            pi = {
                                "kv": pi["kv"].at[pids, offs].set(
                                    interleave_kv(k, v).astype(pool_dtype)),
                            }
                            new_pools[i] = pi
                            o = kops.fused_paged_attention(
                                q, pi["kv"], tables, lengths,
                                page_size=page, window=windows[i],
                                num_buffers=nbuf)
                        else:
                            pi = {
                                "k": pi["k"].at[pids, offs].set(
                                    k.astype(pool_dtype)),
                                "v": pi["v"].at[pids, offs].set(
                                    v.astype(pool_dtype)),
                            }
                            new_pools[i] = pi
                            o = kops.paged_attention(
                                q, pi["k"], pi["v"], tables, lengths,
                                page_size=page, window=windows[i])
                        y = o.reshape(R, -1) @ lp_["attn"]["w_o"]
                elif kind == "mamba":
                    y1, st = ssm.mamba_forward(
                        lp_["mamba"], cfg, h[:, None, :], new_rec_g[i])
                    y = y1[:, 0]
                    new_rec_g[i] = {
                        "conv": st["conv"].astype(pool_dtype),
                        "ssm": st["ssm"]}
                elif kind == "rwkv":
                    st_in = {"wkv": new_rec_g[i]["wkv"],
                             "shift": new_rec_g[i]["shift"]}
                    y1, st = ssm.rwkv6_time_mix(
                        lp_["rwkv"], cfg, h[:, None, :], st_in)
                    y = y1[:, 0]
                    new_rec_g[i] = dict(
                        new_rec_g[i], wkv=st["wkv"],
                        shift=st["shift"].astype(pool_dtype))
                x = x + y
                if has_cross:
                    hc = rmsnorm(lp_["norm_cross"], x, cfg.norm_eps)
                    hd = cfg.resolved_head_dim
                    qc = (hc @ lp_["cross"]["w_q"]).reshape(
                        R, cfg.num_heads, hd)
                    ck, cv = cross_g[i]["k"], cross_g[i]["v"]
                    enc_lengths = jnp.full((R,), ck.shape[1], jnp.int32)
                    oc = kops.decode_attention(qc, ck, cv, enc_lengths)
                    x = x + oc.reshape(R, -1) @ lp_["cross"]["w_o"]
                h = rmsnorm(lp_["norm2"], x, cfg.norm_eps)
                if kind == "rwkv":
                    y1, sh = ssm.rwkv6_channel_mix(
                        lp_["ffn"], h[:, None, :],
                        new_rec_g[i]["shift_ffn"])
                    y = y1[:, 0]
                    new_rec_g[i] = dict(
                        new_rec_g[i],
                        shift_ffn=sh.astype(pool_dtype))
                elif "ffn_moe" in lp_:
                    y, _ = moe_mod.moe_forward(
                        lp_["ffn_moe"], cfg, h[:, None, :], cfg.act)
                    y = y[:, 0]
                else:
                    y = mlp(lp_["ffn"], h, cfg.act)
                x = x + y
            xf = rmsnorm(params["norm_f"], x, cfg.norm_eps)
            logits = unembed(params["embed"], xf, cfg.tie_embeddings)
            return new_pools, new_rec_g, logits

        return token_forward

    def _build_decode(self, R: int, l: int):
        cfg = self.cfg
        temperature, top_p = self.tree_cfg.temperature, self.tree_cfg.top_p
        has_cross = self.has_cross
        token_forward = self._make_token_forward(R)

        def decode_fn(params, pools, rec, cross, tok0, lp0, pos0, tables,
                      slots, qslots, key):
            rec_g = {i: {k: v[slots] for k, v in st.items()}
                     for i, st in rec.items()}
            cross_g = None
            if has_cross:
                cross_g = {i: {k: v[qslots] for k, v in st.items()}
                           for i, st in cross.items()}

            def step(carry, key_t):
                pools, rec_g, tok, lp, pos, _ = carry
                new_pools, new_rec_g, logits = token_forward(
                    params, pools, rec_g, cross_g, tok, pos, tables)
                tnext, lpnext = sample_tokens(key_t, logits,
                                              temperature, top_p)
                new_carry = (new_pools, new_rec_g, tnext, lpnext, pos + 1,
                             logits.astype(jnp.float32))
                return new_carry, (tok, lp)

            keys = jax.random.split(key, l)
            V = (params["embed"]["embedding"].shape[0]
                 if cfg.tie_embeddings else
                 params["embed"]["lm_head"].shape[1])
            init = (pools, rec_g, tok0, lp0, pos0,
                    jnp.zeros((R, V), jnp.float32))
            (pools_f, rec_gf, pend_tok, pend_lp, _, last_logits), outs = \
                jax.lax.scan(step, init, keys)
            toks, lps = outs                                # (l, R)
            new_rec = {i: {k: rec[i][k].at[slots].set(rec_gf[i][k])
                           for k in rec[i]}
                       for i in rec}
            return (pools_f, new_rec, toks.T, lps.T, pend_tok, pend_lp,
                    last_logits)

        return jax.jit(decode_fn)

    def _build_serve(self, R: int, l: int):
        """Continuous-batching serve segment: like decode, but each scan
        step can *force* the consumed token (chunked prompt prefill mixed
        into the decode dispatch) and sampling is keyed per row by
        (request key, absolute position) instead of a per-round split —
        a request's token stream is a pure function of its own identity
        and context, bitwise independent of batch composition, arrival
        interleaving and preemption/replay.

        The logprob reported for a *forced* token is its log-probability
        under the previous step's distribution (exact mid-round; at the
        round's first step the carried logits are zeros, but callers only
        consume logprobs of generated tokens, whose values are exact).
        """
        assert not self.has_cross and self.n_prefix == 0, \
            "serve loop needs token-complete contexts (no cross-KV / " \
            "modality prefix)"
        cfg = self.cfg
        temperature, top_p = self.tree_cfg.temperature, self.tree_cfg.top_p
        token_forward = self._make_token_forward(R)

        def serve_fn(params, pools, rec, tok0, lp0, pos0, tables, slots,
                     forced_tok, forced_on, row_keys):
            rec_g = {i: {k: v[slots] for k, v in st.items()}
                     for i, st in rec.items()}

            def step(carry, xs):
                pools, rec_g, tok, lp, pos, prev_logits = carry
                f_tok, f_on = xs
                tok = jnp.where(f_on, f_tok, tok)
                prev_lsm = jax.nn.log_softmax(
                    prev_logits / max(temperature, 1e-6), axis=-1)
                lp = jnp.where(
                    f_on,
                    jnp.take_along_axis(prev_lsm, f_tok[:, None],
                                        axis=-1)[:, 0],
                    lp)
                new_pools, new_rec_g, logits = token_forward(
                    params, pools, rec_g, None, tok, pos, tables)
                keys = jax.vmap(jax.random.fold_in)(row_keys, pos + 1)
                tnext, lpnext = sample_rows(keys, logits,
                                            temperature, top_p)
                new_carry = (new_pools, new_rec_g, tnext, lpnext, pos + 1,
                             logits.astype(jnp.float32))
                return new_carry, (tok, lp)

            V = (params["embed"]["embedding"].shape[0]
                 if cfg.tie_embeddings else
                 params["embed"]["lm_head"].shape[1])
            init = (pools, rec_g, tok0, lp0, pos0,
                    jnp.zeros((R, V), jnp.float32))
            (pools_f, rec_gf, pend_tok, pend_lp, _, _), outs = \
                jax.lax.scan(step, init,
                             (forced_tok.T, forced_on.T))
            toks, lps = outs                                # (l, R)
            new_rec = {i: {k: rec[i][k].at[slots].set(rec_gf[i][k])
                           for k in rec[i]}
                       for i in rec}
            return pools_f, new_rec, toks.T, lps.T, pend_tok, pend_lp

        return jax.jit(serve_fn)

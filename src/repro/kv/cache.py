"""Paged KV cache with tree-structured prefix sharing.

The device side is a set of fixed-size pools — by default one **fused**
array per attention layer with K/V head-interleaved on the head axis
(``(num_pages, page_size, 2*n_kv, head_dim)``, layout contract in
``repro.kv.layout``) so one page DMA ships both halves; with
``fused_kv=False`` the legacy split K / V pools (the parity oracle) —
plus recurrent-state slot arrays for SSM/hybrid layers.  The host side is a page
allocator with **refcounts**: forking a search path at a segment boundary
copies the child's *block table* (a Python list of page ids) and bumps the
refcount of every shared page — KV data of full pages is never copied (the
paper's prefix amortization).  A branch at a non-page-aligned boundary
copies-on-write at most the one partial tail page.

Recurrent state (Mamba conv/ssm, RWKV wkv/shift) *is* copied on fork — it is
a running reduction, not a prefix (DESIGN.md §4).  Both kinds of fork copy
(COW page rows, slot rows) are collected per branching round and applied by
:meth:`PagedKVState.apply_forks` in a single jitted multi-layer dispatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


class OutOfPages(RuntimeError):
    """KV page / slot pool exhaustion, carrying allocator diagnostics.

    The allocator fills in pool occupancy; the engine/sampler *annotate*
    the in-flight exception with live-path and per-query page counts so a
    real exhaustion is debuggable from the exception text alone (under
    the pressure protocol — `docs/robustness.md` — one of these escaping
    a rollout is itself a bug report)."""

    def __init__(self, msg: str, *, pages_in_use: Optional[int] = None,
                 num_pages: Optional[int] = None):
        super().__init__(msg)
        self.base_msg = msg
        self.pages_in_use = pages_in_use
        self.num_pages = num_pages
        self.live_paths: Optional[int] = None
        self.per_query_pages: Optional[Dict[int, int]] = None
        self.radix_pages: Optional[int] = None
        self.radix_evictable: Optional[int] = None

    def annotate(self, *, live_paths: Optional[int] = None,
                 per_query_pages: Optional[Dict[int, int]] = None,
                 radix_pages: Optional[int] = None,
                 radix_evictable: Optional[int] = None
                 ) -> "OutOfPages":
        if live_paths is not None:
            self.live_paths = live_paths
        if per_query_pages is not None:
            self.per_query_pages = dict(per_query_pages)
        if radix_pages is not None:
            self.radix_pages = radix_pages
        if radix_evictable is not None:
            self.radix_evictable = radix_evictable
        return self

    def __str__(self) -> str:
        parts = [self.base_msg]
        if self.pages_in_use is not None and self.num_pages is not None:
            parts.append(f"pages_in_use={self.pages_in_use}"
                         f"/{self.num_pages}")
        if self.live_paths is not None:
            parts.append(f"live_paths={self.live_paths}")
        if self.per_query_pages:
            per_q = ", ".join(f"q{q}:{n}" for q, n in
                              sorted(self.per_query_pages.items()))
            parts.append(f"per_query_pages={{{per_q}}}")
        if self.radix_pages is not None:
            ev = 0 if self.radix_evictable is None else self.radix_evictable
            parts.append(f"radix_pages={self.radix_pages}(evictable {ev})")
        return " | ".join(parts)


# Fault-injection hook (see repro.core.faults).  FaultInjector installs
# its `fires` callable here on arm — a module global rather than an
# import, because repro.core.engine imports this module at package init.
fault_hook = None


def bucket_pow2(n: int, minimum: int = 1) -> int:
    """Round up to the next power of two — THE jit-shape bucketing policy
    (engine batch/seq buckets and apply_forks pad buckets share it)."""
    return max(minimum, 1 << (max(n, 1) - 1).bit_length())


class ProgramTable:
    """Jitted engine programs, kept for as long as the table lives.

    Keyed by ``(signature, kind, bucket)``: ``signature`` holds every
    static value the program's closure reads, ``kind`` names the program
    (``prefill``, ``decode``, ``serve``, ``fork``) and ``bucket`` its
    shape bucket.  Owners with equal signatures get the same jitted
    callable for a bucket, so a fresh engine over the same statics traces,
    lowers and compiles nothing again; owners whose statics differ never
    meet.  A kept program captures static values only, never an engine, a
    pool or parameters, so the table pins no device state.
    """

    def __init__(self) -> None:
        self._programs: Dict[tuple, Any] = {}
        self.hits = 0       # lookups answered by a kept program
        self.misses = 0     # lookups that built one

    def get(self, signature: tuple, kind: str, bucket: tuple,
            build: Callable[[], Any]) -> Any:
        key = (signature, kind, bucket)
        fn = self._programs.get(key)
        if fn is None:
            fn = self._programs[key] = build()
            self.misses += 1
        else:
            self.hits += 1
        return fn

    def entries(self, signature: tuple, kind: str) -> Dict[tuple, Any]:
        """``{bucket: program}`` of one signature's ``kind``."""
        return {b: fn for (s, k, b), fn in self._programs.items()
                if k == kind and s == signature}


@dataclasses.dataclass
class PagePool:
    """Host-side page allocator with refcounts."""

    num_pages: int

    def __post_init__(self):
        self.refcount = np.zeros(self.num_pages, dtype=np.int32)
        self.free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._in_use = 0          # incremental |{p: refcount[p] > 0}|
        self.peak_in_use = 0      # high-water mark (pool-sizing signal)

    def alloc(self) -> int:
        if fault_hook is not None and fault_hook("page_pool.alloc"):
            raise OutOfPages("injected page exhaustion",
                             pages_in_use=self._in_use,
                             num_pages=self.num_pages)
        if not self.free:
            raise OutOfPages("pool exhausted",
                             pages_in_use=self._in_use,
                             num_pages=self.num_pages)
        pid = self.free.pop()
        assert self.refcount[pid] == 0
        self.refcount[pid] = 1
        self._in_use += 1
        if self._in_use > self.peak_in_use:
            self.peak_in_use = self._in_use
        return pid

    def retain(self, pid: int) -> None:
        assert self.refcount[pid] > 0
        self.refcount[pid] += 1

    def release(self, pid: int) -> None:
        assert self.refcount[pid] > 0
        self.refcount[pid] -= 1
        if self.refcount[pid] == 0:
            self.free.append(pid)
            self._in_use -= 1

    @property
    def pages_in_use(self) -> int:
        # maintained incrementally: alloc/release are on the per-token hot
        # path and an O(num_pages) refcount scan here dominated them.
        return self._in_use

    @property
    def num_free(self) -> int:
        return len(self.free)

    @property
    def watermark(self) -> float:
        """Pool occupancy in [0, 1] — the pressure signal the engine and
        branching heuristic consult (`docs/robustness.md`)."""
        return self._in_use / max(self.num_pages, 1)


class SlotAllocator:
    """Fixed pool of per-path slots (recurrent state / scratch rows)."""

    def __init__(self, num_slots: int):
        self.num_slots = num_slots
        self.free: List[int] = list(range(num_slots - 1, -1, -1))

    def alloc(self) -> int:
        if not self.free:
            raise OutOfPages(
                f"slots exhausted ({self.in_use}/{self.num_slots} slots)")
        return self.free.pop()

    def release(self, slot: int) -> None:
        self.free.append(slot)

    @property
    def in_use(self) -> int:
        return self.num_slots - len(self.free)

    @property
    def watermark(self) -> float:
        return self.in_use / max(self.num_slots, 1)


class PagedKVState:
    """Device arrays + host bookkeeping for the tree engine.

    Layout (``fused_kv=True``, the default — ``repro.kv.layout``):
      kv_pools: per attn layer {"kv": (P, page, 2*n_kv, hd)} with heads
                ``[K0,V0,K1,V1,...]`` (MLA: {"kv": (P, page, r + rd)} with
                ``[ckv | k_rope]`` on the feature axis) — one array per
                layer, so a page is one DMA and a fork COW copy can never
                split K from V.
    Legacy layout (``fused_kv=False``, parity oracle):
      kv_pools: per attn layer {"k": (P, page, n_kv, hd), "v": ...}
                (MLA layers: {"ckv": (P, page, r), "k_rope": (P, page, rd)})
    Either way:
      rec_state: per recurrent layer, slot-indexed state arrays
                 (S_max, ...) — slot dim first.
    """

    def __init__(self, cfg: ModelConfig, num_pages: int, page_size: int,
                 max_slots: int, dtype=jnp.float32, fused_kv: bool = True,
                 programs: Optional[ProgramTable] = None):
        self.cfg = cfg
        self.page_size = page_size
        self.pool = PagePool(num_pages)
        self.slots = SlotAllocator(max_slots)
        self.dtype = dtype
        self.fused_kv = fused_kv
        hd = cfg.resolved_head_dim
        self.kv_pools: Dict[int, Dict[str, jnp.ndarray]] = {}
        self.rec_state: Dict[int, Dict[str, jnp.ndarray]] = {}
        for i in range(cfg.num_layers):
            kind = cfg.layer_kind(i)
            if kind == "attn":
                if cfg.attention_kind == "mla":
                    m = cfg.mla
                    if fused_kv:
                        self.kv_pools[i] = {
                            "kv": jnp.zeros(
                                (num_pages, page_size,
                                 m.kv_lora_rank + m.qk_rope_head_dim),
                                dtype),
                        }
                    else:
                        self.kv_pools[i] = {
                            "ckv": jnp.zeros((num_pages, page_size,
                                              m.kv_lora_rank), dtype),
                            "k_rope": jnp.zeros((num_pages, page_size,
                                                 m.qk_rope_head_dim),
                                                dtype),
                        }
                elif fused_kv:
                    self.kv_pools[i] = {
                        "kv": jnp.zeros((num_pages, page_size,
                                         2 * cfg.num_kv_heads, hd), dtype),
                    }
                else:
                    self.kv_pools[i] = {
                        "k": jnp.zeros((num_pages, page_size,
                                        cfg.num_kv_heads, hd), dtype),
                        "v": jnp.zeros((num_pages, page_size,
                                        cfg.num_kv_heads, hd), dtype),
                    }
            elif kind == "mamba":
                mc = cfg.mamba
                d_in = mc.expand * cfg.d_model
                self.rec_state[i] = {
                    "conv": jnp.zeros((max_slots, mc.d_conv - 1, d_in), dtype),
                    "ssm": jnp.zeros((max_slots, d_in, mc.d_state),
                                     jnp.float32),
                }
            elif kind == "rwkv":
                rc = cfg.rwkv
                H = cfg.d_model // rc.head_dim
                self.rec_state[i] = {
                    "wkv": jnp.zeros((max_slots, H, rc.head_dim, rc.head_dim),
                                     jnp.float32),
                    "shift": jnp.zeros((max_slots, cfg.d_model), dtype),
                    "shift_ffn": jnp.zeros((max_slots, cfg.d_model), dtype),
                }
        # whisper cross-attention KV: per request, shared by every branch
        self.cross_kv: Optional[tuple] = None
        # jitted fork-copy dispatches, one per (page-, slot-count) bucket;
        # a fork program reads only the pools' shapes, which these
        # statics fix, so pools of equal signature share it
        self.programs = programs if programs is not None else ProgramTable()
        self.signature = (cfg, num_pages, page_size, max_slots,
                          jnp.dtype(dtype), fused_kv)

    # -- host bookkeeping ---------------------------------------------------

    def fork_table(self, table: List[int]) -> List[int]:
        """Child block table sharing every page of the parent prefix."""
        for pid in table:
            self.pool.retain(pid)
        return list(table)

    def release_table(self, table: List[int]) -> None:
        for pid in table:
            self.pool.release(pid)

    # -- batched fork application -------------------------------------------

    @staticmethod
    def _pad_pairs(src: List[int], dst: List[int]) -> tuple:
        """Pad (src, dst) to a power-of-two bucket so jit caches a few
        shapes, not one per round.  Padding repeats the first real pair:
        duplicate scatter updates to one index are order-unspecified in
        JAX, but duplicates of the *same* (src, dst) write identical bytes,
        so the result stays deterministic whatever rows the caller uses."""
        # imported late: repro.core's __init__ pulls in engine, which
        # imports this module — at call time the cycle has resolved
        from repro.core.guard import annotated_transfer

        n = len(src)
        if n == 0:
            return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                    0)
        nb = bucket_pow2(n)
        pad = nb - n
        src_d, dst_d = annotated_transfer(
            (np.asarray(list(src) + [src[0]] * pad, np.int32),
             np.asarray(list(dst) + [dst[0]] * pad, np.int32)),
            to="device", reason="fork-tables")
        return (src_d, dst_d, nb)

    def _get_fork_fn(self, n_pages: int, n_slots: int):
        return self.programs.get(self.signature, "fork", (n_pages, n_slots),
                                 lambda: _build_fork_fn(n_pages, n_slots))

    def apply_forks(self, page_src: List[int], page_dst: List[int],
                    slot_src: List[int] = (), slot_dst: List[int] = ()
                    ) -> None:
        """Apply a whole branching round's fork copies in ONE jitted
        dispatch: COW page rows in every attention layer's pool and
        recurrent-state rows in every SSM/RWKV layer's slot arrays.

        The sources must still hold their pre-fork contents when this runs
        (the engine allocates fresh dst pages/slots, so a round's copies
        never alias), which is what lets dozens of per-fork-per-layer
        ``v.at[dst].set(v[src])`` dispatches collapse into one call.

        Atomicity: the pools/rec trees are rebound only after the jitted
        copy returns, so a failure here (pool OOM inside the dispatch, or
        the ``kv.apply_forks`` injection site below) leaves device state
        untouched — no fork can observe copied K with stale V, on either
        layout.  The caller still owns the *host* rollback: the freshly
        allocated dst pages/slots must go back via ``release_partial``
        (``TreeEngine.fork_paths`` does).
        """
        if fault_hook is not None and fault_hook("kv.apply_forks"):
            raise OutOfPages("injected apply_forks failure",
                             pages_in_use=self.pool.pages_in_use,
                             num_pages=self.pool.num_pages)
        if not self.rec_state:
            slot_src, slot_dst = [], []
        if not self.kv_pools:
            page_src, page_dst = [], []
        if not page_src and not slot_src:
            return
        psrc, pdst, npg = self._pad_pairs(list(page_src), list(page_dst))
        ssrc, sdst, nsl = self._pad_pairs(list(slot_src), list(slot_dst))
        fn = self._get_fork_fn(npg, nsl)
        if npg and nsl:
            self.kv_pools, self.rec_state = fn(self.kv_pools, self.rec_state,
                                               psrc, pdst, ssrc, sdst)
        elif npg:
            self.kv_pools = fn(self.kv_pools, psrc, pdst)
        else:
            self.rec_state = fn(self.rec_state, ssrc, sdst)

    # -- stats ---------------------------------------------------------------

    def kv_bytes_per_token(self) -> int:
        """Bytes of KV written per generated token (all attn layers)."""
        total = 0
        for pools in self.kv_pools.values():
            for arr in pools.values():
                per_tok = int(np.prod(arr.shape[2:])) * arr.dtype.itemsize
                total += per_tok
        return total


def _build_fork_fn(n_pages: int, n_slots: int):
    """Jitted multi-layer copy, shaped by which state kinds fork this
    round.  The function only takes (and returns) the pytrees it
    mutates — an untouched pool routed through jit would come back as
    a fresh output buffer, i.e. a full pool copy per round."""
    def copy_rows(tree, src, dst):
        return {i: {k: v.at[dst].set(v[src]) for k, v in st.items()}
                for i, st in tree.items()}

    if n_pages and n_slots:
        def fork_fn(pools, rec, psrc, pdst, ssrc, sdst):
            return (copy_rows(pools, psrc, pdst),
                    copy_rows(rec, ssrc, sdst))
        donate = (0, 1)
    elif n_pages:
        def fork_fn(pools, psrc, pdst):
            return copy_rows(pools, psrc, pdst)
        donate = (0,)
    else:
        def fork_fn(rec, ssrc, sdst):
            return copy_rows(rec, ssrc, sdst)
        donate = (0,)
    # donate the pools/rec buffers (the caller rebinds them to the
    # result) so XLA scatters the few forked rows in place instead
    # of copying whole (num_pages, ...) arrays each round; CPU has
    # no donation support and would warn per dispatch.
    if jax.default_backend() == "cpu":
        donate = ()
    return jax.jit(fork_fn, donate_argnums=donate)

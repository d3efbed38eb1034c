"""RL post-training loop — the paper's three main configurations (Table 1):

  grpo        sequential sampling + GRPO advantage (Eq. 2)
  grpo_tree   TreePO sampling     + GRPO advantage ("GRPO w/ TreePO Sampling")
  treepo      TreePO sampling     + tree advantage (Eq. 5, + variants)

Pipeline per step (paper §3.1): oversample queries (3×bsz) → rollout →
verifiable reward → DAPO dynamic-sampling filter (0 < #correct < G) →
advantage → K epochs of the clipped token-level PG update (Eq. 1) with
AdamW (lr 1e-6, 10 warmup steps) — all from a base (untrained) model,
the "RL-zero" setting the paper emphasizes.

The training half is a device-resident hot path (the twin of the
device-resident decode loop):

* rewards are memoized per trajectory at sampling time (``score_fn``) —
  each path is decoded + verified exactly once, ever;
* the advantage for ALL kept queries is ONE jitted
  ``batch_treepo_advantage`` dispatch over padded (Q, G[, J]) tensors
  whose ancestor rows were recorded incrementally during sampling;
* the host packs only the compact batch — (N, L) tokens + rollout
  logprobs, (N,) lengths and per-trajectory advantages; response masks,
  token-broadcast advantages and the REINFORCE++ global normalization
  are derived on device inside the update;
* all K ppo epochs run in ONE jitted call per (N, L) bucket
  (``lax.scan`` carry, donated params/opt-state buffers);
* with ``TrainConfig.pack_sequences`` the batch is *sequence-packed*
  (``repro.rl.packing``): multiple short trajectories share one (N, L)
  row (first-fit-decreasing), the host ships only (N, L) tokens +
  logprobs and three (N, S) per-segment tables, and the jitted update
  derives segment-masked attention, per-segment RoPE resets (and
  SSM/RWKV state resets — packing is exact for every arch, hybrids
  included), masks and advantages on device — shrinking the pad-token
  fraction the tree's mixed-depth trajectories otherwise burn;
* the rollout-logprobs plane is donated per (N, L) bucket alongside
  params/opt-state, so the largest f32 batch input is reused in place
  instead of staying live next to the update's scratch (the returned
  alias is dropped immediately — only ``_donated_lp_buckets`` records
  which buckets donate, for tests/observability).

The previous per-tree / per-epoch host loop is kept as
``build_batch_legacy`` / ``update_legacy`` — the parity reference for
tests and the "before" side of ``benchmarks/train_hotpath.py``; the
unpacked ``build_batch`` / ``update`` pair plays the same oracle role
for the packed path.
"""
from __future__ import annotations

import dataclasses
import enum
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainConfig, TreeConfig
from repro.core import advantage as adv_mod
from repro.core import faults
from repro.core.engine import TreeEngine
from repro.core.guard import annotated_transfer, span
from repro.core.loss import token_logprobs_from_logits
from repro.core.sampler import sample_sequential, sample_trees
from repro.core.tree import (
    Path,
    QueryTree,
    Status,
    ancestor_matrix,
    batch_group_tensors,
)
from repro.data.reward import reward_fn
from repro.data.synthetic_math import MathTaskGenerator
from repro.data.tokenizer import ByteTokenizer
from repro.kv.cache import ProgramTable
from repro.models.model import forward, init_params
from repro.optim import (
    AdamWState,
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    warmup_constant_schedule,
)
from repro.rl.packing import (
    PackedRolloutBatch,
    bucket_segments,
    fill_packed_rows,
    first_fit_decreasing,
    packed_row_tensors,
    packing_supported,
)
from repro.rl.update import make_pg_loss, make_ppo_update


class TrainerMode(str, enum.Enum):
    GRPO = "grpo"
    GRPO_TREE = "grpo_tree"
    TREEPO = "treepo"


@dataclasses.dataclass
class RolloutBatch:
    """Compact host-side batch for the PG update.

    Only these arrays cross to the device (``host_pack_bytes``); the
    dense (N, L) response mask and token-broadcast advantages are
    derived on device inside the jitted update.  ``response_mask`` /
    ``advantages`` below are lazy *inspection* views for tests, metrics
    and the legacy comparison — the hot path never materializes them.
    """

    tokens: np.ndarray          # (N, L) prompt+response, right-padded
    prompt_lens: np.ndarray     # (N,) int32 prompt token counts
    resp_lens: np.ndarray       # (N,) int32 response token counts
    logprobs_old: np.ndarray    # (N, L) rollout logprobs (0 elsewhere)
    adv_traj: np.ndarray        # (N,) per-trajectory advantage (pre-norm)
    rewards: np.ndarray         # (N,)
    num_queries: int = 0
    mean_response_len: float = 0.0
    leaf_rate: float = 0.0
    host_pack_bytes: int = 0    # bytes shipped host->device for the update
    padded_rows: int = 0        # Nb: row-bucket the update really runs

    @classmethod
    def empty(cls) -> "RolloutBatch":
        return cls(np.zeros((0, 1), np.int32), np.zeros((0,), np.int32),
                   np.zeros((0,), np.int32), np.zeros((0, 1), np.float32),
                   np.zeros((0,), np.float32), np.zeros((0,), np.float32))

    @property
    def response_mask(self) -> np.ndarray:
        """(N, L) dense view: 1 on generated tokens."""
        return _response_mask_from_lens(self.prompt_lens, self.resp_lens,
                                        self.tokens.shape[1])

    @property
    def advantages(self) -> np.ndarray:
        """(N, L) dense view: per-trajectory advantage broadcast over its
        response tokens (before global normalization)."""
        return self.adv_traj[:, None] * self.response_mask

    @property
    def padded_token_fraction(self) -> float:
        """Fraction of the token grid the jitted update really runs
        (``max(N, padded_rows)`` × L — row-bucket padding included)
        occupied by pad tokens — the waste sequence packing
        (PackedRolloutBatch) shrinks."""
        n, L = self.tokens.shape
        n = max(n, self.padded_rows)
        if n == 0 or L == 0:
            return 0.0
        used = int((self.prompt_lens + self.resp_lens).sum())
        return 1.0 - used / float(n * L)


@dataclasses.dataclass
class LegacyRolloutBatch:
    """Dense batch produced by the pre-refactor host loop (parity /
    benchmark reference only)."""

    tokens: np.ndarray
    response_mask: np.ndarray
    logprobs_old: np.ndarray
    advantages: np.ndarray
    rewards: np.ndarray
    num_queries: int = 0
    host_pack_bytes: int = 0


def _bucket_len(n: int, quantum: int = 64) -> int:
    return max(quantum, -(-n // quantum) * quantum)


def _response_mask_from_lens(prompt_lens, resp_lens, length: int, xp=np):
    """(N, L) mask with 1 on generated tokens, derived from per-row
    lengths — the ONE definition shared by the on-device update
    (xp=jnp) and the host-side inspection view (xp=np)."""
    pos = xp.arange(length)[None, :]
    lo = prompt_lens[:, None]
    hi = (prompt_lens + resp_lens)[:, None]
    return ((pos >= lo) & (pos < hi)).astype(xp.float32)


def _bucket_rows(n: int, quantum: int = 4, pow2_from: int = 32) -> int:
    """Pad the batch dimension to a bucket so the per-(N, L) update
    compile cache stays small: fine-grained (multiples of ``quantum``)
    for small batches — padding a 4-row batch to 8 would double the
    fwd/bwd compute — and powers of two beyond ``pow2_from``."""
    if n <= pow2_from:
        return max(quantum, -(-n // quantum) * quantum)
    b = pow2_from
    while b < n:
        b *= 2
    return b


class RLTrainer:
    """Single-replica RL trainer (the distributed variant lives in
    repro.launch: same update function under pjit)."""

    def __init__(self, cfg: ModelConfig, train_cfg: TrainConfig,
                 tree_cfg: TreeConfig,
                 mode: TrainerMode = TrainerMode.TREEPO, *,
                 seed: int = 0, engine_kwargs: Optional[Dict] = None,
                 data_seed: int = 0, min_difficulty: int = 1,
                 max_difficulty: int = 2):
        self.cfg = cfg
        self.train_cfg = train_cfg
        self.tree_cfg = tree_cfg
        self.mode = TrainerMode(mode)
        self.tok = ByteTokenizer()
        if cfg.vocab_size < self.tok.vocab_size:
            raise ValueError("model vocab too small for the byte tokenizer")
        if train_cfg.pack_sequences and not packing_supported(cfg):
            # the gate is universally true today (segment-reset kernels);
            # kept so a future non-resettable layer kind fails loudly
            raise ValueError(
                f"pack_sequences is not exact for {cfg.name} "
                "(repro.rl.packing.packing_supported) — train unpacked")
        key = jax.random.PRNGKey(seed)
        self.params = init_params(key, cfg)
        self.opt_state = adamw_init(self.params)
        self.lr_fn = warmup_constant_schedule(train_cfg.learning_rate,
                                              train_cfg.warmup_steps)
        self.gen = MathTaskGenerator(data_seed, min_difficulty,
                                     max_difficulty)
        self.engine_kwargs = dict(engine_kwargs or {})
        # the jitted engine programs every engine of this trainer shares:
        # each step's fresh engine finds the buckets earlier steps built
        self.programs = ProgramTable()
        self._update_fns: Dict[Tuple[int, int], Any] = {}
        self._packed_update_fns: Dict[Tuple[int, int, int], Any] = {}
        self._legacy_update_fns: Dict[Tuple[int, int], Any] = {}
        # buckets whose jitted update donated the rollout-logprobs plane
        # (keys only — retaining the returned alias would pin one
        # (Nb, L) f32 buffer per bucket and undo the donation's point;
        # in-place reuse is proven by the compile-time aliasing tests)
        self._donated_lp_buckets: set = set()
        self.step = 0
        self.metrics_log: List[Dict[str, float]] = []
        self._rng = np.random.default_rng(seed)
        import random as _random
        self._pyrng = _random.Random(seed)

    # -- crash-safe state (docs/robustness.md) -----------------------------------

    def state_dict(self) -> Dict[str, Any]:
        """Complete resumable training state.

        Covers every source of run-to-run divergence: params, optimizer
        moments, the step counter, the metrics cursor (how many rows of
        the JSONL stream were already emitted), and all three host RNGs —
        ``_rng`` (numpy; also seeds each rollout engine's device keys, so
        capturing it captures device sampling), ``_pyrng`` (tree
        branching), and ``gen.rng`` (task generation).  RNG states are
        pickled to bytes: numpy's PCG64 state carries 128-bit ints that
        overflow msgpack, and ``random.Random`` state is a nested tuple —
        an opaque bytes blob round-trips both exactly.
        """
        import pickle

        return {
            "params": self.params,
            "opt_state": self.opt_state,
            "step": int(self.step),
            "metrics_cursor": len(self.metrics_log),
            "np_rng": pickle.dumps(self._rng.bit_generator.state),
            "py_rng": pickle.dumps(self._pyrng.getstate()),
            "gen_rng": pickle.dumps(self.gen.rng.getstate()),
        }

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Restore :meth:`state_dict` output — the next ``train_step``
        is bit-identical to the one an uninterrupted run would take."""
        import pickle

        self.params = state["params"]
        # the checkpoint skeleton round-trips NamedTuples as plain tuples
        self.opt_state = AdamWState(*state["opt_state"])
        self.step = int(state["step"])
        del self.metrics_log[int(state["metrics_cursor"]):]
        self._rng.bit_generator.state = pickle.loads(state["np_rng"])
        self._pyrng.setstate(pickle.loads(state["py_rng"]))
        self.gen.rng.setstate(pickle.loads(state["gen_rng"]))

    # -- engine ----------------------------------------------------------------

    def _make_engine(self) -> TreeEngine:
        """Fresh engine view over the *current* params (on-policy rollout):
        fresh pools, allocator and seed; the programs are the trainer's."""
        return TreeEngine(self.params, self.cfg, self.tree_cfg,
                          seed=int(self._rng.integers(2 ** 31)),
                          programs=self.programs, **self.engine_kwargs)

    # -- rollout ---------------------------------------------------------------

    def _sample_queries(self, n: int):
        samples = self.gen.batch(n)
        prompts = [self.tok.encode(s.query, bos=True) for s in samples]
        return samples, prompts

    def _score_path(self, tree: QueryTree, path: Path) -> float:
        """Terminal reward for one finished LEAF trajectory (invoked once
        per path, at finish time — the memoized score)."""
        return reward_fn(self.tok.decode(path.tokens), tree.target,
                         shaping=self.train_cfg.reward_shaping)

    def rollout(self, num_queries: int, progress: float = 0.0
                ) -> Tuple[List[QueryTree], TreeEngine]:
        samples, prompts = self._sample_queries(num_queries)
        with span("rl.engine_build") as sp:
            engine = self._make_engine()
            if sp:
                # the program table's lookups in the rollouts so far
                sp.set(hits=self.programs.hits, misses=self.programs.misses)
        targets = [s.answer for s in samples]
        if self.mode == TrainerMode.GRPO:
            trees, _ = sample_sequential(engine, prompts, targets,
                                         rng=self._pyrng,
                                         progress=progress,
                                         score_fn=self._score_path)
        else:
            trees, _ = sample_trees(engine, prompts, targets,
                                    rng=self._pyrng, progress=progress,
                                    score_fn=self._score_path)
        return trees, engine

    # -- reward + advantage ------------------------------------------------------

    def _tree_rewards(self, tree: QueryTree) -> np.ndarray:
        """Memoized per-path rewards (scored at sampling time via
        ``score_fn``; this only fills in paths from trees sampled without
        one — tests / external callers)."""
        for p in tree.finished:
            if p.reward is None:
                p.reward = 0.0 if p.status == Status.FAILED else \
                    self._score_path(tree, p)
        return tree.rewards()

    @property
    def _advantage_variant(self) -> str:
        return (self.train_cfg.advantage_kind
                if self.mode == TrainerMode.TREEPO else "grpo")

    @property
    def _use_global_norm(self) -> bool:
        return (self.train_cfg.global_norm
                and self.mode == TrainerMode.TREEPO
                and self.train_cfg.advantage_kind != "grpo")

    def _kept_trees(self, trees: List[QueryTree]
                    ) -> List[Tuple[QueryTree, np.ndarray]]:
        """Reward + DAPO dynamic-sampling filter (rewards memoized)."""
        kept = []
        for tree in trees:
            if not tree.finished:
                continue
            rewards = self._tree_rewards(tree)
            if self.train_cfg.dynamic_sampling and rewards.std() <= 1e-6:
                continue  # DAPO: drop all-correct / all-wrong groups
            kept.append((tree, rewards))
        return kept

    def _advantage_rows(self, trees: List[QueryTree]):
        """Reward + DAPO filter + ONE batched advantage dispatch.

        Returns (kept, rows) with rows = [(prompt, resp, logprobs,
        reward, advantage), ...] — the per-trajectory material both the
        unpacked and the packed pack layouts are built from.
        """
        kept = self._kept_trees(trees)
        if not kept:
            return kept, []
        # bucket Q and pad G to the width cap so the jitted advantage
        # dispatch compiles once per bucket, not once per (Q, G) combo
        anc, rew_qg, gmask = batch_group_tensors(
            [t for t, _ in kept], self.tree_cfg.max_depth,
            group_pad=self.tree_cfg.max_width,
            query_pad=_bucket_rows(len(kept)))
        rew_qg, anc, gmask = annotated_transfer(
            (rew_qg, anc, gmask), to="device", reason="advantage-pack")
        adv_qg = annotated_transfer(adv_mod.batch_treepo_advantage(
            rew_qg, anc, gmask,
            variant=self._advantage_variant, use_global_norm=False),
            reason="advantage-rows")

        rows = []
        for qi, (tree, rewards) in enumerate(kept):
            for gi, (p, r) in enumerate(zip(tree.finished, rewards)):
                rows.append((tree.prompt_tokens, p.tokens, p.logprobs,
                             float(r), float(adv_qg[qi, gi])))
        return kept, rows

    def build_batch(self, trees: List[QueryTree]) -> RolloutBatch:
        """Reward, dynamic-sampling filter, ONE batched advantage
        dispatch, compact fixed-shape pack."""
        kept, rows = self._advantage_rows(trees)
        if not rows:
            return RolloutBatch.empty()
        L = _bucket_len(max(len(pr) + len(t) for pr, t, *_ in rows))
        N = len(rows)
        tokens = np.full((N, L), ByteTokenizer.PAD, np.int32)
        prompt_lens = np.zeros((N,), np.int32)
        resp_lens = np.zeros((N,), np.int32)
        lp_old = np.zeros((N, L), np.float32)
        adv_traj = np.zeros((N,), np.float32)
        rew = np.zeros((N,), np.float32)
        n_leaves = 0
        for i, (prompt, resp, lps, r, a) in enumerate(rows):
            n_p, n_r = len(prompt), len(resp)
            tokens[i, : n_p] = prompt
            tokens[i, n_p: n_p + n_r] = resp
            prompt_lens[i] = n_p
            resp_lens[i] = n_r
            lp_old[i, n_p: n_p + n_r] = lps
            adv_traj[i] = a
            rew[i] = r
        for tree, _ in kept:
            n_leaves += tree.num_leaves
        # what update() will actually ship: the ROW-PADDED (Nb, L)
        # buffers, not the unpadded (N, L) pack built here
        Nb = _bucket_rows(N)
        pack_bytes = Nb * (tokens.itemsize * L + lp_old.itemsize * L +
                           prompt_lens.itemsize + resp_lens.itemsize +
                           adv_traj.itemsize)
        return RolloutBatch(
            tokens=tokens, prompt_lens=prompt_lens, resp_lens=resp_lens,
            logprobs_old=lp_old, adv_traj=adv_traj, rewards=rew,
            num_queries=len(kept),
            mean_response_len=float(resp_lens.mean()),
            leaf_rate=n_leaves / max(sum(len(t.finished)
                                         for t, _ in kept), 1),
            host_pack_bytes=pack_bytes, padded_rows=Nb)

    def build_batch_packed(self, trees: List[QueryTree]
                           ) -> PackedRolloutBatch:
        """Sequence-packed twin of :meth:`build_batch`: same rewards /
        filter / batched advantage, then first-fit-decreasing packing of
        the trajectories into shared (N, L) rows with (N, S) per-segment
        tables (``repro.rl.packing``) instead of one row each."""
        kept, rows = self._advantage_rows(trees)
        if not rows:
            return PackedRolloutBatch.empty()
        totals = [len(pr) + len(t) for pr, t, *_ in rows]
        # pack into the SAME bucket length the unpacked layout would use,
        # so packing strictly reduces N at equal L
        L = _bucket_len(max(totals))
        packing_rows = first_fit_decreasing(totals, L)
        N = len(packing_rows)
        S = bucket_segments(max(len(r) for r in packing_rows))
        tokens, seg_plens, seg_rlens, placements = fill_packed_rows(
            [pr for pr, *_ in rows], [t for _, t, *_ in rows],
            packing_rows, L, num_rows=N, seg_slots=S,
            pad_token=ByteTokenizer.PAD)
        lp_old = np.zeros((N, L), np.float32)
        seg_adv = np.zeros((N, S), np.float32)
        seg_rew = np.zeros((N, S), np.float32)
        for i, s, j, off in placements:
            prompt, _, lps, r, a = rows[j]
            lp_old[i, off + len(prompt): off + len(prompt) + len(lps)] = lps
            seg_adv[i, s] = a
            seg_rew[i, s] = r
        n_leaves = sum(t.num_leaves for t, _ in kept)
        # what update_packed() will actually ship: the ROW-PADDED (Nb, ·)
        # buffers, not the unpadded pack built here
        Nb = _bucket_rows(N)
        pack_bytes = Nb * (tokens.itemsize * L + lp_old.itemsize * L +
                           S * (seg_plens.itemsize + seg_rlens.itemsize +
                                seg_adv.itemsize))
        return PackedRolloutBatch(
            tokens=tokens, logprobs_old=lp_old,
            seg_prompt_lens=seg_plens, seg_resp_lens=seg_rlens,
            seg_adv=seg_adv, seg_rewards=seg_rew,
            num_queries=len(kept), num_trajectories=len(rows),
            mean_response_len=float(np.mean([len(t) for _, t, *_ in rows])),
            leaf_rate=n_leaves / max(sum(len(t.finished)
                                         for t, _ in kept), 1),
            host_pack_bytes=pack_bytes, padded_rows=Nb)

    # -- update -----------------------------------------------------------------

    def _get_update_fn(self, N: int, L: int):
        """One jitted K-epoch update per (N, L) bucket: derives the dense
        mask/advantages on device, runs global normalization there, scans
        the ppo epochs, and donates the params/opt-state buffers plus the
        rollout-logprobs plane (aliased back out as the 3rd result)."""
        key = (N, L)
        if key not in self._update_fns:
            base_update = make_ppo_update(self.cfg, self.train_cfg,
                                          lr_fn=self.lr_fn,
                                          donate_logprobs=True)
            apply_global = self._use_global_norm

            def update(params, opt_state, tokens, prompt_lens, resp_lens,
                       lp_old, adv_traj, step):
                rmask = _response_mask_from_lens(
                    prompt_lens, resp_lens, tokens.shape[1], xp=jnp)
                advs = adv_traj[:, None] * rmask
                if apply_global:
                    advs = adv_mod.global_normalize(advs, rmask)
                batch = {"tokens": tokens, "response_mask": rmask,
                         "logprobs_old": lp_old, "advantages": advs}
                return base_update(params, opt_state, batch, step)

            self._update_fns[key] = jax.jit(update,
                                            donate_argnums=(0, 1, 5))
        return self._update_fns[key]

    def update(self, batch: RolloutBatch) -> Dict[str, float]:
        """All K ppo epochs in one jitted dispatch (per (N, L) bucket)."""
        N = batch.tokens.shape[0]
        if N == 0:
            return {"skipped": 1.0}
        L = batch.tokens.shape[1]
        Nb = _bucket_rows(N)
        tokens = np.full((Nb, L), ByteTokenizer.PAD, np.int32)
        tokens[:N] = batch.tokens
        prompt_lens = np.zeros((Nb,), np.int32)
        prompt_lens[:N] = batch.prompt_lens
        resp_lens = np.zeros((Nb,), np.int32)   # padded rows: empty mask
        resp_lens[:N] = batch.resp_lens
        lp_old = np.zeros((Nb, L), np.float32)
        lp_old[:N] = batch.logprobs_old
        # fault-injection site: poisoning one response-position logprob
        # NaNs the loss/grads inside the jitted scan, which the
        # nonfinite guard must absorb (tests/test_faults.py)
        lp_old = faults.corrupt_array("trainer.batch_logprobs", lp_old,
                                      col=int(batch.prompt_lens[0]))
        adv_traj = np.zeros((Nb,), np.float32)
        adv_traj[:N] = batch.adv_traj
        fn = self._get_update_fn(Nb, L)
        pack = annotated_transfer(
            (tokens, prompt_lens, resp_lens, lp_old, adv_traj,
             np.asarray(self.step, np.int32)),
            to="device", reason="update-pack")
        self.params, self.opt_state, _, m = fn(
            self.params, self.opt_state, *pack)
        self._donated_lp_buckets.add((Nb, L))
        m = annotated_transfer(m, reason="update-metrics")
        return {k: float(v) for k, v in m.items()}

    def _get_packed_update_fn(self, N: int, L: int, S: int):
        """One jitted K-epoch update per (N, L, S) bucket over the
        sequence-packed compact layout: segment-ids / RoPE positions /
        masks / advantages (+ optional global norm) all derived on
        device by ``repro.rl.update`` with ``packed=True``.  Flat
        arguments so exactly params / opt-state / rollout logprobs are
        donated (a donated dict would drag the int32 tables along)."""
        key = (N, L, S)
        if key not in self._packed_update_fns:
            base = make_ppo_update(self.cfg, self.train_cfg,
                                   lr_fn=self.lr_fn, packed=True,
                                   use_global_norm=self._use_global_norm,
                                   donate_logprobs=True)

            def update(params, opt_state, tokens, lp_old, seg_plens,
                       seg_rlens, seg_adv, step):
                batch = {"tokens": tokens, "logprobs_old": lp_old,
                         "seg_prompt_lens": seg_plens,
                         "seg_resp_lens": seg_rlens, "seg_adv": seg_adv}
                return base(params, opt_state, batch, step)

            self._packed_update_fns[key] = jax.jit(
                update, donate_argnums=(0, 1, 3))
        return self._packed_update_fns[key]

    def update_packed(self, batch: PackedRolloutBatch) -> Dict[str, float]:
        """All K ppo epochs in one jitted dispatch per (N, L, S) bucket
        over a sequence-packed batch (rows padded with zero-width
        segments, invisible to the loss)."""
        N = batch.tokens.shape[0]
        if N == 0:
            return {"skipped": 1.0}
        L = batch.tokens.shape[1]
        S = batch.seg_prompt_lens.shape[1]
        Nb = _bucket_rows(N)
        tokens = np.full((Nb, L), ByteTokenizer.PAD, np.int32)
        tokens[:N] = batch.tokens
        lp_old = np.zeros((Nb, L), np.float32)
        lp_old[:N] = batch.logprobs_old
        # fault-injection site (see update()): poison the first response
        # token of row 0's first packed segment
        lp_old = faults.corrupt_array(
            "trainer.batch_logprobs", lp_old,
            col=int(batch.seg_prompt_lens[0, 0]))
        seg_plens = np.zeros((Nb, S), np.int32)   # padded rows: 0-width segs
        seg_plens[:N] = batch.seg_prompt_lens
        seg_rlens = np.zeros((Nb, S), np.int32)
        seg_rlens[:N] = batch.seg_resp_lens
        seg_adv = np.zeros((Nb, S), np.float32)
        seg_adv[:N] = batch.seg_adv
        fn = self._get_packed_update_fn(Nb, L, S)
        pack = annotated_transfer(
            (tokens, lp_old, seg_plens, seg_rlens, seg_adv,
             np.asarray(self.step, np.int32)),
            to="device", reason="update-pack")
        self.params, self.opt_state, _, m = fn(
            self.params, self.opt_state, *pack)
        self._donated_lp_buckets.add((Nb, L, S))
        m = annotated_transfer(m, reason="update-metrics")
        return {k: float(v) for k, v in m.items()}

    # -- legacy reference path ---------------------------------------------------
    #
    # The pre-refactor host loop: per-tree unjitted advantage calls, dense
    # (N, L) host packing (mask + broadcast advantages + host-side global
    # norm) and one jitted dispatch per ppo epoch.  Kept verbatim as the
    # parity oracle for tests and the "before" side of
    # benchmarks/train_hotpath.py.  Not used by train_step.

    def _tree_advantages_legacy(self, tree: QueryTree,
                                rewards: np.ndarray) -> np.ndarray:
        variant = self._advantage_variant
        if variant == "grpo":
            r_dev = annotated_transfer(rewards, to="device",
                                       reason="legacy-advantage")
            return annotated_transfer(adv_mod.grpo_advantage(r_dev),
                                      reason="legacy-advantage")
        anc = ancestor_matrix(tree.finished, self.tree_cfg.max_depth)
        r_dev, anc_dev = annotated_transfer(
            (rewards, anc), to="device", reason="legacy-advantage")
        return annotated_transfer(
            adv_mod.treepo_advantage(r_dev, anc_dev, variant=variant),
            reason="legacy-advantage")

    def build_batch_legacy(self, trees: List[QueryTree]
                           ) -> LegacyRolloutBatch:
        kept: List[Tuple[QueryTree, np.ndarray, np.ndarray]] = []
        for tree, rewards in self._kept_trees(trees):
            advs = self._tree_advantages_legacy(tree, rewards)
            kept.append((tree, rewards, advs))
        if not kept:
            return LegacyRolloutBatch(
                np.zeros((0, 1), np.int32), np.zeros((0, 1), np.float32),
                np.zeros((0, 1), np.float32), np.zeros((0, 1), np.float32),
                np.zeros((0,), np.float32))
        rows = []
        for tree, rewards, advs in kept:
            for p, r, a in zip(tree.finished, rewards, advs):
                rows.append((tree.prompt_tokens, p.tokens, p.logprobs,
                             float(r), float(a)))
        L = _bucket_len(max(len(pr) + len(t) for pr, t, *_ in rows))
        N = len(rows)
        tokens = np.full((N, L), ByteTokenizer.PAD, np.int32)
        rmask = np.zeros((N, L), np.float32)
        lp_old = np.zeros((N, L), np.float32)
        advsb = np.zeros((N, L), np.float32)
        rew = np.zeros((N,), np.float32)
        for i, (prompt, resp, lps, r, a) in enumerate(rows):
            n_p, n_r = len(prompt), len(resp)
            tokens[i, : n_p] = prompt
            tokens[i, n_p: n_p + n_r] = resp
            rmask[i, n_p: n_p + n_r] = 1.0
            lp_old[i, n_p: n_p + n_r] = lps
            advsb[i, n_p: n_p + n_r] = a
            rew[i] = r
        if self._use_global_norm:
            advs_dev, rmask_dev = annotated_transfer(
                (advsb, rmask), to="device", reason="legacy-globalnorm")
            advsb = annotated_transfer(
                adv_mod.global_normalize(advs_dev, rmask_dev),
                reason="legacy-globalnorm")
        pack_bytes = (tokens.nbytes + rmask.nbytes + lp_old.nbytes +
                      advsb.nbytes)
        return LegacyRolloutBatch(
            tokens=tokens, response_mask=rmask, logprobs_old=lp_old,
            advantages=advsb, rewards=rew, num_queries=len(kept),
            host_pack_bytes=pack_bytes)

    def _get_legacy_update_fn(self, N: int, L: int):
        key = (N, L)
        if key not in self._legacy_update_fns:
            loss_fn = make_pg_loss(self.cfg, self.train_cfg)
            tc = self.train_cfg

            def update(params, opt_state, tokens, rmask, lp_old, advs,
                       step):
                batch = {"tokens": tokens, "response_mask": rmask,
                         "logprobs_old": lp_old, "advantages": advs}
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
                grads, gnorm = clip_by_global_norm(grads,
                                                   tc.max_grad_norm)
                lr = self.lr_fn(step)
                new_params, new_opt = adamw_update(
                    params, grads, opt_state, lr=lr, beta1=tc.beta1,
                    beta2=tc.beta2, eps=tc.eps,
                    weight_decay=tc.weight_decay)
                metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
                return new_params, new_opt, metrics

            self._legacy_update_fns[key] = jax.jit(update)
        return self._legacy_update_fns[key]

    def update_legacy(self, batch: LegacyRolloutBatch) -> Dict[str, float]:
        """Pre-refactor update: one jitted dispatch per ppo epoch, no
        donation, dense host-packed inputs re-shipped every epoch."""
        if batch.tokens.shape[0] == 0:
            return {"skipped": 1.0}
        N, L = batch.tokens.shape
        fn = self._get_legacy_update_fn(N, L)
        metrics: Dict[str, float] = {}
        for _ in range(self.train_cfg.ppo_epochs):
            # the legacy inefficiency under measurement is the re-ship
            # per epoch — annotated so the guard can tally its cost
            pack = annotated_transfer(
                (batch.tokens, batch.response_mask, batch.logprobs_old,
                 batch.advantages, np.asarray(self.step, np.int32)),
                to="device", reason="legacy-epoch-pack")
            self.params, self.opt_state, m = fn(
                self.params, self.opt_state, *pack)
            m = annotated_transfer(m, reason="update-metrics")
            metrics = {k: float(v) for k, v in m.items()}
        return metrics

    # -- outer loop ---------------------------------------------------------------

    def train_step(self, num_queries: Optional[int] = None,
                   progress: float = 0.0) -> Dict[str, float]:
        """One full RL iteration: oversampled rollout → filter → update.

        ``num_queries``: queries per *attempt* (default: batch_size); the
        paper oversamples 3× and resamples up to 2 extra rounds if dynamic
        sampling starves the batch.
        """
        with span("rl.step", step=self.step):
            return self._train_step(num_queries, progress)

    def _train_step(self, num_queries: Optional[int],
                    progress: float) -> Dict[str, float]:
        t0 = time.time()
        nq = num_queries or self.train_cfg.batch_size
        all_trees: List[QueryTree] = []
        sample_tokens = 0
        rounds = 0
        target_queries = nq
        while rounds <= self.train_cfg.max_resample_rounds:
            want = (target_queries - self._count_kept(all_trees))
            if want <= 0:
                break
            n = want * (self.train_cfg.oversample_factor
                        if rounds == 0 else 1)
            trees, engine = self.rollout(n, progress)
            all_trees.extend(trees)
            sample_tokens += engine.stats.model_tokens
            # free the rollout's KV pools now, for the update, and not
            # whenever the engine happens to be collected
            engine.close()
            rounds += 1
            if not self.train_cfg.dynamic_sampling:
                break
        packed = self.train_cfg.pack_sequences
        with span("rl.pack"):
            batch = (self.build_batch_packed(all_trees) if packed
                     else self.build_batch(all_trees))
        with span("rl.update"):
            metrics = (self.update_packed(batch) if packed
                       else self.update(batch))
        self.step += 1
        rewards = batch.rewards
        metrics.update(
            step=self.step,
            reward_mean=float(rewards.mean()) if rewards.size else 0.0,
            num_trajectories=float(rewards.size),
            num_queries_kept=float(batch.num_queries),
            response_len=batch.mean_response_len,
            leaf_rate=batch.leaf_rate,
            host_pack_bytes=float(batch.host_pack_bytes),
            padded_token_fraction=batch.padded_token_fraction,
            sample_model_tokens=float(sample_tokens),
            wall_time=time.time() - t0,
        )
        self.metrics_log.append(metrics)
        return metrics

    def _count_kept(self, trees: List[QueryTree]) -> int:
        """Number of kept queries so far — memoized rewards make this a
        cache lookup, not a re-decode of every accumulated tree."""
        return len(self._kept_trees(trees))

    # -- behavior-cloning warmup ----------------------------------------------------
    #
    # The paper trains from the *pretrained* Qwen2.5-7B base model, which
    # already emits \boxed{} answers under few-shot prompting.  Our toy model
    # starts from random weights, so a short supervised warmup on synthetic
    # CoT traces stands in for "base model with a prior" (recorded as a
    # deviation in DESIGN.md §8).  RL proper then starts from this
    # checkpoint — still no *RL* signal is used here.

    def bc_warmup(self, steps: int = 100, batch_size: int = 16,
                  lr: float = 3e-3,
                  packed: Optional[bool] = None) -> Dict[str, float]:
        """Supervised CoT warmup.  ``packed=None`` follows
        ``TrainConfig.pack_sequences``: with packing on, the (query, cot)
        rows are FFD-binned into shared (N, L) rows and the CE loss runs
        over segment-masked attention + per-segment resets — the same
        token set and normalization as the dense layout, on fewer rows."""
        cfg = self.cfg
        packed = self.train_cfg.pack_sequences if packed is None else packed

        def ce_from(lp, m, aux):
            loss = -(lp * m).sum() / jnp.maximum(m.sum(), 1.0)
            if cfg.moe is not None:
                loss = loss + cfg.moe.aux_loss_coef * aux
            return loss

        def ce_loss(params, tokens, mask):
            logits, aux = forward(params, cfg, tokens)
            lp = token_logprobs_from_logits(logits[:, :-1], tokens[:, 1:])
            return ce_from(lp, mask[:, 1:], aux)

        def ce_loss_packed(params, tokens, seg_plens, seg_rlens):
            sid, pos, rmask = packed_row_tensors(
                seg_plens, seg_rlens, tokens.shape[1], xp=jnp)
            logits, aux = forward(params, cfg, tokens, positions=pos,
                                  segment_ids=sid)
            lp = token_logprobs_from_logits(logits[:, :-1], tokens[:, 1:])
            # boundary guard: never score a token against another
            # segment's last token (mirrors the packed PG loss)
            m = rmask[:, 1:] * (sid[:, 1:] == sid[:, :-1]).astype(
                jnp.float32)
            return ce_from(lp, m, aux)

        def _step(loss_fn):
            def run(params, opt_state, *batch):
                loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
                grads, _ = clip_by_global_norm(grads, 1.0)
                new_params, new_opt = adamw_update(params, grads,
                                                   opt_state, lr=lr)
                return new_params, new_opt, loss
            return jax.jit(run, donate_argnums=(0, 1))

        bc_step = _step(ce_loss_packed if packed else ce_loss)

        L = None
        last = 0.0
        for it in range(steps):
            samples = self.gen.batch(batch_size)
            rows = []
            for s in samples:
                ids = (self.tok.encode(s.query, bos=True),
                       self.tok.encode(" " + s.cot, eos=True))
                rows.append(ids)
            maxlen = max(len(a) + len(b) for a, b in rows)
            if L is None or maxlen > L:
                L = _bucket_len(maxlen)
            if packed:
                lens = [len(q) + len(c) for q, c in rows]
                packing_rows = first_fit_decreasing(lens, L)
                toks, seg_plens, seg_rlens, _ = fill_packed_rows(
                    [q for q, _ in rows], [c for _, c in rows],
                    packing_rows, L,
                    num_rows=_bucket_rows(len(packing_rows)),
                    seg_slots=bucket_segments(
                        max(len(r) for r in packing_rows)),
                    pad_token=ByteTokenizer.PAD)
                pack = annotated_transfer(
                    (toks, seg_plens, seg_rlens), to="device",
                    reason="bc-pack")
                self.params, self.opt_state, loss = bc_step(
                    self.params, self.opt_state, *pack)
            else:
                toks = np.full((batch_size, L), ByteTokenizer.PAD,
                               np.int32)
                mask = np.zeros((batch_size, L), np.float32)
                for i, (q, c) in enumerate(rows):
                    toks[i, : len(q)] = q
                    toks[i, len(q): len(q) + len(c)] = c
                    mask[i, len(q): len(q) + len(c)] = 1.0
                pack = annotated_transfer((toks, mask), to="device",
                                          reason="bc-pack")
                self.params, self.opt_state, loss = bc_step(
                    self.params, self.opt_state, *pack)
            last = float(annotated_transfer(loss, reason="bc-loss"))
        # reset optimizer state for the RL phase (fresh moments)
        self.opt_state = adamw_init(self.params)
        return {"bc_loss": last, "bc_steps": float(steps),
                "bc_packed": float(packed)}

    # -- evaluation ----------------------------------------------------------------

    def evaluate(self, num_queries: int = 16, k: int = 4,
                 seed: int = 1234) -> Dict[str, float]:
        """maj@k accuracy on held-out synthetic tasks (paper's val metric)."""
        gen = MathTaskGenerator(seed, self.gen.min_difficulty,
                                self.gen.max_difficulty)
        samples = gen.batch(num_queries)
        prompts = [self.tok.encode(s.query, bos=True) for s in samples]
        eval_tree_cfg = dataclasses.replace(
            self.tree_cfg, max_width=k,
            init_divergence_low=k, init_divergence_high=k,
            branch_factor=1, fallback=False)
        engine = TreeEngine(self.params, self.cfg, eval_tree_cfg,
                            seed=seed, programs=self.programs,
                            **self.engine_kwargs)
        trees, _ = sample_trees(engine, prompts,
                                [s.answer for s in samples],
                                eval_tree_cfg, rng=__import__(
                                    "random").Random(seed))
        from collections import Counter
        from repro.data.reward import extract_boxed, verify_answer
        correct = 0
        any_correct = 0
        for tree, s in zip(trees, samples):
            answers = []
            got_one = False
            for p in tree.finished:
                a = extract_boxed(self.tok.decode(p.tokens))
                if a is not None:
                    answers.append(a)
                    if verify_answer(a, s.answer):
                        got_one = True
            any_correct += int(got_one)
            if answers:
                maj = Counter(answers).most_common(1)[0][0]
                correct += int(verify_answer(maj, s.answer))
        return {"maj_acc": correct / num_queries,
                "pass_any": any_correct / num_queries}

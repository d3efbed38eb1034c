"""Tree-based sampling — Algorithm 1 of the paper, host-side orchestration.

One call to :func:`sample_trees` turns a batch of queries into ``w``
trajectories each by driving the :class:`~repro.core.engine.TreeEngine`
through segment-synchronous rounds:

  1. prefill every query once (the shared tree root),
  2. init divergence (fixed or randomized 2..8 root forks),
  3. loop: batched segment decode over *all* queries' active paths →
     early-stop / leaf classification → branching-budget assignment
     (with budget transfer + heuristics) → DFS fallback for starved
     queries,
  4. finish when every query has ``w`` trajectories (or budgets exhaust).

Sequential (non-tree) sampling — the paper's baseline — is the same
machinery with ``branch_factor=1`` and ``init_divergence == w``: ``w``
independent rollouts that share only the prompt KV.

Training-side hooks: an optional ``score_fn`` scores each trajectory the
moment it finishes (memoized on ``Path.reward`` — one reward evaluation
per trajectory, ever), and every finished path records its padded
ancestor row incrementally on the tree, so the trainer packs the batched
(Q, G, J) advantage inputs without per-tree reconstruction.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.configs.base import TreeConfig
from repro.core import branching as br
from repro.core.early_stop import segment_stop_reason, truncate_at_eos
from repro.core.engine import TreeEngine
from repro.core.fallback import pick_fallback
from repro.core.guard import span
from repro.core.tree import Path, QueryTree, Status, new_node_id
from repro.kv.cache import OutOfPages

# scores a finished LEAF trajectory (FAILED paths are pinned to 0.0)
ScoreFn = Callable[[QueryTree, Path], float]


@dataclasses.dataclass
class SamplerReport:
    num_queries: int = 0
    num_trajectories: int = 0
    num_leaves: int = 0
    num_failed: int = 0
    num_fallbacks: int = 0
    decode_rounds: int = 0
    # fault-tolerance accounting (docs/robustness.md)
    num_preempted: int = 0      # paths retracted under KV pressure
    num_regenerated: int = 0    # preempted paths replayed back in
    num_quarantined: int = 0    # paths retired on non-finite logits


def _finish_path(tree: QueryTree, path: Path, status: Status,
                 reason: str, engine: TreeEngine,
                 score_fn: Optional[ScoreFn] = None) -> None:
    path.status = status
    path.finish_reason = reason
    if status == Status.FAILED:
        path.reward = 0.0             # failed trajectories earn nothing
    elif score_fn is not None:
        path.reward = float(score_fn(tree, path))
    tree.add_finished(path)
    if path.ep is not None:
        # finished paths never sample again (fallback forks read only their
        # KV pages), so drop the boundary-logits reference now rather than
        # pinning the round's (Rb, V) device buffer until end of rollout
        path.ep.logits_buf = None
    if status == Status.FAILED and path.ep is not None:
        # failed paths are never fallback sources: free their pages now
        engine.release_path(path.ep)


def _process_segment(tree: QueryTree, path: Path, seg_tokens: List[int],
                     seg_logprobs: List[float], seg_logprob: float,
                     tree_cfg: TreeConfig, engine: TreeEngine,
                     score_fn: Optional[ScoreFn] = None, *,
                     finite: bool = True,
                     report: Optional[SamplerReport] = None) -> None:
    if not finite:
        # numeric quarantine: the engine pulled non-finite logprobs for
        # this row — retire the path WITHOUT extending it (the segment's
        # tokens came from poisoned logits); siblings are unaffected
        if report is not None:
            report.num_quarantined += 1
        _finish_path(tree, path, Status.FAILED, "nonfinite", engine,
                     score_fn)
        return
    seg_tokens, seg_logprobs = truncate_at_eos(seg_tokens, seg_logprobs)
    path.tokens.extend(seg_tokens)
    path.logprobs.extend(seg_logprobs)
    path.depth += 1
    path.node_ids.append(new_node_id())
    path.seg_bounds.append(len(path.tokens))
    path.seg_logprob = seg_logprob
    path.seg_logprobs.append(seg_logprob)
    tree.total_segments += 1

    reason = segment_stop_reason(
        seg_tokens, path.tokens,
        max_ngram=tree_cfg.repetition_ngram,
        count=tree_cfg.repetition_count)
    if reason in ("eos", "boxed"):
        _finish_path(tree, path, Status.LEAF, reason, engine, score_fn)
    elif reason == "repetition":
        _finish_path(tree, path, Status.FAILED, reason, engine, score_fn)
    elif path.depth >= tree_cfg.max_depth:
        _finish_path(tree, path, Status.LEAF, "length", engine, score_fn)
    else:
        tree.active.append(path)


def _branch_tree(tree: QueryTree, tree_cfg: TreeConfig, engine: TreeEngine,
                 rng: random.Random, progress: float,
                 score_fn: Optional[ScoreFn] = None,
                 report: Optional[SamplerReport] = None) -> None:
    """Apply the depth budget to this tree's active paths (paper §2.2:
    budget transfer evens dead paths' allowance over the survivors).

    After a DFS fallback round the active list can be *mixed-depth*
    (fallback children restart at their fork depth), so the budget is
    computed per depth group — one global ``active[0].depth`` budget
    would over- or under-allocate every other depth.

    Pressure-aware term (docs/robustness.md): each depth group's budget
    passes through ``branching.throttle_budget`` — above the soft KV
    watermark the extra fan-out shrinks, at the hard watermark only
    continuations survive — and the round's total new forks are hard-
    capped by the pages/slots the pool can actually absorb (a fork costs
    at most one COW page + one recurrent slot).
    """
    if not tree.active:
        return
    budgets = br.mixed_depth_budgets(
        tree_cfg, [p.depth for p in tree.active], tree.init_div,
        tree.num_trajectories)
    pressure_fn = getattr(engine, "pressure", None)
    if pressure_fn is not None:
        pressure = pressure_fn()
        counts: Dict[int, int] = {}
        for p in tree.active:
            counts[p.depth] = counts.get(p.depth, 0) + 1
        budgets = {d: br.throttle_budget(tree_cfg, b, counts[d], pressure)
                   for d, b in budgets.items()}
    fork_cap = _fork_capacity(engine, tree_cfg)
    # collect the round's forks, then branch them in ONE engine call:
    # one jitted page/slot-copy dispatch + one on-device fork_sample.
    survivors: List[Tuple[Path, int]] = []
    parents = []
    for depth in sorted(budgets, reverse=True):
        group = [p for p in tree.active if p.depth == depth]
        forks = br.assign_branches(
            tree_cfg, [p.seg_logprob for p in group], budgets[depth], rng,
            progress)
        for path, k in zip(group, forks):
            if k <= 0:
                # width budget exhausted: prune (counts as failed, no reward)
                _finish_path(tree, path, Status.FAILED, "budget", engine,
                             score_fn)
                continue
            k = min(k, 1 + max(fork_cap - len(parents), 0))
            survivors.append((path, k))
            parents.extend([path.ep] * (k - 1))
    children = engine.fork_paths(parents)
    new_active: List[Path] = []
    ci = 0
    for path, k in survivors:
        new_active.append(path)
        for _ in range(k - 1):
            new_active.append(path.clone_for_branch(children[ci]))
            ci += 1
    tree.active = _quarantine_nonfinite(tree, new_active, engine, score_fn,
                                        report)


def _fork_capacity(engine: TreeEngine, tree_cfg: TreeConfig) -> int:
    """Upper bound on new forks the pool can absorb right now: one COW
    page each, reserving one path's next decode segment, and one slot
    each on recurrent archs.  Engines without allocator surface (host-
    side unit-test fakes) are unconstrained."""
    pages_free_fn = getattr(engine, "pages_free", None)
    if pages_free_fn is None:
        return 1 << 30
    reserve = -(-tree_cfg.segment_len // engine.page_size) + 1
    cap = max(pages_free_fn() - reserve, 0)
    if getattr(engine, "has_rec", False):
        cap = min(cap, len(engine.kv.slots.free))
    return cap


def _quarantine_nonfinite(tree: QueryTree, paths: List[Path],
                          engine: TreeEngine,
                          score_fn: Optional[ScoreFn],
                          report: Optional[SamplerReport]) -> List[Path]:
    """Drop paths whose divergence draw came back non-finite (flagged by
    the engine in ``sample_pending_batch``)."""
    kept: List[Path] = []
    for p in paths:
        if p.ep is not None and getattr(p.ep, "numeric_bad", False):
            if report is not None:
                report.num_quarantined += 1
            _finish_path(tree, p, Status.FAILED, "nonfinite", engine,
                         score_fn)
        else:
            kept.append(p)
    return kept


def _fallback_tree(tree: QueryTree, tree_cfg: TreeConfig,
                   engine: TreeEngine, rng: random.Random,
                   guard: int, n_prefix: int,
                   report: SamplerReport) -> None:
    """DFS fallback: refill a starved query from its finished leaves."""
    if tree.active or not tree_cfg.fallback:
        return
    needed = tree_cfg.max_width - tree.num_trajectories
    while needed > 0 and tree.total_segments < guard:
        picked = pick_fallback(tree, rng)
        if picked is None:
            return
        src, j = picked
        prefix_count = src.seg_bounds[j]
        prefix_position = n_prefix + len(tree.prompt_tokens) + prefix_count
        replay = list(tree.prompt_tokens) + src.tokens[:prefix_count]
        # KV-pressure guard: a fallback fork costs one COW page (attention)
        # or a full prefix replay into fresh pages (recurrent) plus one
        # decode segment — don't start one the pool can't finish
        pages_free_fn = getattr(engine, "pages_free", None)
        if pages_free_fn is not None:
            reserve = -(-tree_cfg.segment_len // engine.page_size) + 1
            prefix_pages = -(-prefix_position // engine.page_size)
            need = (prefix_pages if engine.has_rec else 1) + reserve
            if pages_free_fn() < need or (
                    engine.has_rec and not engine.kv.slots.free):
                return
        child_ep = engine.fork_from_prefix(src.ep, prefix_position, replay)
        # the child's last segment is the *prefix* segment j, so the next
        # branching round's uncertainty heuristic must see that segment's
        # mean logprob — not the source leaf's final-segment value
        child = Path(
            query_idx=tree.query_idx,
            depth=j,
            node_ids=src.node_ids[: j + 1],
            tokens=src.tokens[:prefix_count],
            logprobs=src.logprobs[:prefix_count],
            ep=child_ep,
            seg_bounds=src.seg_bounds[: j + 1],
            seg_logprob=(src.seg_logprobs[j - 1]
                         if len(src.seg_logprobs) >= j >= 1
                         else src.seg_logprob),
            seg_logprobs=src.seg_logprobs[:j],
        )
        tree.active.extend(
            _quarantine_nonfinite(tree, [child], engine, None, report))
        report.num_fallbacks += 1
        needed -= 1


def _release_leaf_kv(trees: List[QueryTree], engine: TreeEngine,
                     needed: int) -> int:
    """Graceful-degradation victim #1: finished leaves retain their KV
    only to seed DFS fallback, so under pool pressure that retention is
    the cheapest thing to give up (the leaf trajectory itself is kept —
    only future fallback quality degrades).  Frees pages until ``needed``
    is met or no retained leaf KV remains; returns pages freed."""
    freed = 0
    for tree in trees:
        for p in tree.finished:
            if freed >= needed:
                return freed
            if p.ep is not None and not p.ep.released:
                before = engine.kv.pool.pages_in_use
                engine.release_path(p.ep)
                freed += before - engine.kv.pool.pages_in_use
    return freed


def _decode_pages_needed(engine: TreeEngine, ep, seg_len: int) -> int:
    pages = -(-(ep.position + seg_len) // engine.page_size)
    return max(pages - len(ep.table), 0)


def _admit_for_decode(trees: List[QueryTree],
                      batch: List[Tuple[QueryTree, Path]],
                      engine: TreeEngine, tree_cfg: TreeConfig,
                      report: SamplerReport,
                      score_fn: Optional[ScoreFn]
                      ) -> List[Tuple[QueryTree, Path]]:
    """Admission control before a decode round: if the round's worst-case
    page demand exceeds the free pool, first reclaim finished leaves'
    retained KV, then retract the lowest-value active paths — deepest
    first, lowest ``seg_logprob`` as tiebreak (the same value ordering
    the paper's heuristics rank by).  Retracted paths keep their host
    tokens and are parked on ``tree.preempted`` for regeneration; on
    archs whose context is not token-reconstructable (modality prefix /
    cross-KV) they are finished FAILED("preempted") instead.  At least
    one path is always admitted so the rollout makes progress."""
    seg = tree_cfg.segment_len
    demand = sum(_decode_pages_needed(engine, p.ep, seg) for _, p in batch)
    free = engine.pages_free()
    if demand > free:
        free += _release_leaf_kv(trees, engine, demand - free)
    if demand <= free:
        return batch
    order = sorted(range(len(batch)),
                   key=lambda i: (-batch[i][1].depth,
                                  batch[i][1].seg_logprob))
    admitted = set(range(len(batch)))
    for i in order:
        if demand <= free or len(admitted) <= 1:
            break
        tree, path = batch[i]
        admitted.discard(i)
        demand -= _decode_pages_needed(engine, path.ep, seg)
        report.num_preempted += 1
        if engine.can_restore:
            free += engine.preempt_path(path.ep)
            path.ep = None
            tree.preempted.append(path)
        else:
            before = engine.kv.pool.pages_in_use
            _finish_path(tree, path, Status.FAILED, "preempted", engine,
                         score_fn)
            free += before - engine.kv.pool.pages_in_use
    return [batch[i] for i in sorted(admitted)]


def _regenerate_tree(tree: QueryTree, engine: TreeEngine,
                     tree_cfg: TreeConfig, guard: int,
                     report: SamplerReport,
                     score_fn: Optional[ScoreFn],
                     force: bool = False) -> int:
    """Re-admit preempted paths once the pool has headroom: replay their
    full token history into fresh pages (``TreeEngine.restore_path``),
    highest-value first (shallowest / best seg_logprob — the reverse of
    the retraction order).  Normally regeneration waits for occupancy to
    come back under the soft watermark; ``force`` (used when a tree
    would otherwise stall with an empty frontier) admits one path as
    long as its replay + one decode segment physically fit."""
    regen = 0
    while tree.preempted and tree.total_segments < guard:
        idx = min(range(len(tree.preempted)),
                  key=lambda i: (tree.preempted[i].depth,
                                 -tree.preempted[i].seg_logprob))
        path = tree.preempted[idx]
        tokens = list(tree.prompt_tokens) + path.tokens
        pages = -(-(engine.n_prefix + len(tokens) + tree_cfg.segment_len)
                  // engine.page_size)
        if engine.has_rec and not engine.kv.slots.free:
            break
        below_soft = (engine.kv.pool.pages_in_use + pages
                      <= tree_cfg.kv_watermark_soft
                      * engine.kv.pool.num_pages)
        if not (below_soft or (force and pages <= engine.pages_free())):
            break
        tree.preempted.pop(idx)
        path.ep = engine.restore_path(tokens)
        report.num_regenerated += 1
        for p in _quarantine_nonfinite(tree, [path], engine, score_fn,
                                       report):
            tree.active.append(p)
            regen += 1
        if force:
            break
    return regen


def sample_trees(engine: TreeEngine, prompts: List[List[int]],
                 targets: List[str], tree_cfg: Optional[TreeConfig] = None,
                 *, rng: Optional[random.Random] = None,
                 progress: float = 0.0,
                 prefix_embeds=None, enc_frames=None,
                 guard_factor: int = 4,
                 score_fn: Optional[ScoreFn] = None,
                 ) -> Tuple[List[QueryTree], SamplerReport]:
    """Run Algorithm 1 for a batch of queries.  Returns the query trees
    (finished paths = trajectories) and a sampling report."""
    tree_cfg = tree_cfg or engine.tree_cfg
    rng = rng or random.Random(0)
    report = SamplerReport(num_queries=len(prompts))
    guard = tree_cfg.max_width * tree_cfg.max_depth * guard_factor

    trees = [QueryTree(query_idx=i, prompt_tokens=list(p), target=t,
                       max_depth=tree_cfg.max_depth)
             for i, (p, t) in enumerate(zip(prompts, targets))]

    # under allocation pressure the engine retries a failed page/slot
    # alloc after this callback reclaims finished leaves' retained KV —
    # never in-flight paths, which only admission control may retract
    engine.set_pressure_cb(
        lambda needed: _release_leaf_kv(trees, engine, needed))
    qslot_of: Dict[int, int] = {}
    try:
        # 1-2. prefill + init divergence ----------------------------------
        with span("sampler.prefill", queries=len(prompts)):
            roots = engine.prefill_queries(prompts,
                                           prefix_embeds=prefix_embeds,
                                           enc_frames=enc_frames)
            for tree, root_ep in zip(trees, roots):
                qslot_of[tree.query_idx] = root_ep.qslot
                n_init = min(br.init_divergence(tree_cfg, rng),
                             tree_cfg.max_width)
                tree.init_div = n_init
                eps = [root_ep] + engine.fork_paths(
                    [root_ep] * (n_init - 1))
                tree.active = _quarantine_nonfinite(
                    tree,
                    [Path(query_idx=tree.query_idx, depth=0,
                          node_ids=[tree.root_id], tokens=[], logprobs=[],
                          ep=ep)
                     for ep in eps],
                    engine, score_fn, report)

        # 3. segment-synchronous search loop ------------------------------
        while True:
            batch = [(tree, p) for tree in trees for p in tree.active]
            if not batch:
                # frontier empty but retracted paths remain: force-revive
                # one per tree so pressure preemption cannot strand work
                if not any(_regenerate_tree(tree, engine, tree_cfg, guard,
                                            report, score_fn, force=True)
                           for tree in trees if tree.preempted):
                    break
                continue
            with span("sampler.round") as sp:
                for tree in trees:
                    tree.active = []
                batch = _admit_for_decode(trees, batch, engine, tree_cfg,
                                          report, score_fn)
                forks0 = engine.stats.forks
                results = engine.decode_segments([p.ep for _, p in batch])
                report.decode_rounds += 1
                for (tree, path), res in zip(batch, results):
                    _process_segment(tree, path, res.tokens, res.logprobs,
                                     res.seg_logprob, tree_cfg, engine,
                                     score_fn, finite=res.finite,
                                     report=report)
                for tree in trees:
                    _branch_tree(tree, tree_cfg, engine, rng, progress,
                                 score_fn, report)
                    _fallback_tree(tree, tree_cfg, engine, rng, guard,
                                   engine.n_prefix, report)
                    if tree.preempted:
                        _regenerate_tree(tree, engine, tree_cfg, guard,
                                         report, score_fn)
                if sp:
                    sp.set(rows=len(batch),
                           forks=engine.stats.forks - forks0)

        # preempted paths the budget never recovered for: graceful
        # degradation means they are dropped as failed trajectories, not
        # an escaped OutOfPages
        for tree in trees:
            for p in tree.preempted:
                _finish_path(tree, p, Status.FAILED, "preempted", engine,
                             score_fn)
            tree.preempted = []
    except OutOfPages as e:
        # annotate the in-flight exhaustion so it is debuggable from the
        # exception alone (this should be unreachable under the pressure
        # protocol — reaching it is itself the bug report)
        per_query = {
            t.query_idx: len({pid for p in (t.active + t.finished)
                              if p.ep is not None
                              for pid in p.ep.table})
            for t in trees}
        raise e.annotate(
            live_paths=sum(len(t.active) for t in trees),
            per_query_pages=per_query)
    finally:
        engine.set_pressure_cb(None)

    # 4. release device resources ------------------------------------------
    for tree in trees:
        for p in tree.finished:
            if p.ep is not None:
                engine.release_path(p.ep)
        report.num_trajectories += tree.num_trajectories
        report.num_leaves += tree.num_leaves
        report.num_failed += sum(1 for p in tree.finished
                                 if p.status == Status.FAILED)
    for qslot in qslot_of.values():
        engine.release_qslot(qslot)
    return trees, report


def sequential_tree_cfg(tree_cfg: TreeConfig) -> TreeConfig:
    """The paper's sequential baseline expressed in tree terms: ``w``
    independent rollouts, no branching, no fallback, no early stop
    transfer (repetition stop retained — both samplers use it)."""
    return dataclasses.replace(
        tree_cfg,
        branch_factor=1,
        init_divergence_low=tree_cfg.max_width,
        init_divergence_high=tree_cfg.max_width,
        fallback=False,
        budget_transfer=False,
    )


def sample_sequential(engine: TreeEngine, prompts: List[List[int]],
                      targets: List[str],
                      tree_cfg: Optional[TreeConfig] = None, **kw
                      ) -> Tuple[List[QueryTree], SamplerReport]:
    """Vanilla i.i.d. rollout baseline driven through the same engine."""
    tree_cfg = sequential_tree_cfg(tree_cfg or engine.tree_cfg)
    return sample_trees(engine, prompts, targets, tree_cfg, **kw)

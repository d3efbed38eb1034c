"""RL cells: whole TreePO RL steps back to back through the program's
``RLTrainer.train_step`` (tree rollout through the paged engine, tree
advantage, sequence-packed PPO update).

Set-up builds one trainer on the benchmark's weights and drives it through
the mix's first steps, recording what the reference needs (each step's
trajectories and loss, the first gradient as AdamW holds it, the change of
the parameters after the last set-up step); the window then runs further
steps of that same trainer.  After the window the reference re-runs those
first steps in float32 at the highest matmul precision and the readings
are compared (``readings``).
"""
from __future__ import annotations

import functools
import time
import types
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, reference, weights
from bench.traffic import generate

KERNELS = ("flash_attention_pallas", "fused_paged_attention_pallas")


def _trainer_class():
    from repro.rl.trainer import RLTrainer

    class BenchTrainer(RLTrainer):
        """The program's trainer with the benchmark's prompts and reward,
        and host spans and records around its layers."""

        def bench_attach(self, ctx):
            self.bench = ctx
            self.run = ctx.run

        def _sample_queries(self, n):
            pairs = generate.math_prompts(self.bench.mix,
                                           self.run.prompt_rng, n)
            return ([types.SimpleNamespace(answer=a) for _, a in pairs],
                    [p for p, _ in pairs])

        def _score_path(self, tree, path):
            return compare.token_reward(self.bench.seed, path.tokens)

        def _make_engine(self):
            engine = super()._make_engine()
            decode = engine.decode_segments
            run = self.run

            def counted(paths, seg_len=None):
                l = seg_len or engine.tree_cfg.segment_len
                run.decode_calls.append(
                    (len(paths), l, [p.position for p in paths]))
                return decode(paths, seg_len)

            engine.decode_segments = counted
            return engine

        def rollout(self, num_queries, progress=0.0):
            with self.run.spans("rollout"):
                trees, engine = super().rollout(num_queries, progress)
            self.run.engine_stats.append(engine.stats)
            self.run.prompts.append([t.prompt_tokens for t in trees])
            return trees, engine

        def build_batch_packed(self, trees):
            with self.run.spans("pack"):
                from repro.core.tree import Status
                self.run.steps.append([
                    {"query": t.query_idx, "prompt": list(t.prompt_tokens),
                     "tokens": list(p.tokens), "logprobs": list(p.logprobs),
                     "failed": p.status == Status.FAILED}
                    for t in trees for p in t.finished])
                batch = super().build_batch_packed(trees)
            self.run.batches.append(
                (batch.tokens.shape, batch.seg_prompt_lens.copy(),
                 batch.seg_resp_lens.copy()))
            return batch

        def update_packed(self, batch):
            with self.run.spans("update"):
                return super().update_packed(batch)

    return BenchTrainer


def build(ctx):
    """Set-up: the trainer on the seed's weights, through the first steps."""
    from repro.configs.base import TrainConfig, TreeConfig
    from repro.optim import adamw_init
    from repro.rl.trainer import TrainerMode

    mix, seed = ctx.mix, ctx.seed
    tree_cfg = TreeConfig(**mix["tree"])
    Q, W = mix["queries_per_step"], tree_cfg.max_width
    train_cfg = TrainConfig(batch_size=Q, group_size=W, **mix["train"])
    P = mix["prompt_tokens"]
    page = tree_cfg.segment_len
    per_path = -(-(P + tree_cfg.max_response_len) // page)
    engine_kwargs = dict(num_pages=2 * Q * W * per_path + 1, page_size=page,
                         max_slots=64, max_queries=Q, max_prompt_len=P)
    run = ctx.run
    run.prompt_rng = generate.run_rng(seed, "prompts")
    trainer = _trainer_class()(ctx.cfg, train_cfg, tree_cfg,
                               TrainerMode.TREEPO, seed=ctx.seed32,
                               engine_kwargs=engine_kwargs)
    trainer.bench_attach(ctx)
    trainer.params = weights.make_params(ctx.p, seed)
    trainer.opt_state = adamw_init(trainer.params)
    prog = {"loss": []}
    for k in range(mix["setup_steps"]):
        m = trainer.train_step(num_queries=Q)
        if "loss" not in m or m.get("skipped_nonfinite", 0.0):
            raise RuntimeError(f"set-up step {k + 1} did not update: {m}")
        prog["loss"].append(m["loss"])
        if k == 0:
            prog["grad"] = np.asarray(compare.leaf_norms(
                trainer.opt_state.m)) / (1.0 - train_cfg.beta1)
    prog["change"] = np.asarray(compare.leaf_diff_norms(
        trainer.params, weights.make_params(ctx.p, seed)))
    prog["steps"] = [list(s) for s in run.steps]
    ctx.train_cfg, ctx.tree_cfg = train_cfg, tree_cfg
    return trainer, prog


def window(ctx, trainer) -> dict:
    """Whole RL steps back to back until ``seconds`` have passed."""
    Q = ctx.mix["queries_per_step"]
    run = ctx.run
    for name in ("steps", "batches", "decode_calls", "engine_stats",
                 "prompts"):
        getattr(run, name).clear()
    metrics: List[Dict[str, float]] = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        metrics.append(trainer.train_step(num_queries=Q))
    elapsed = time.perf_counter() - t0
    failed = sum(1 for m in metrics
                 if "loss" not in m or m.get("skipped_nonfinite", 0.0))
    return {"elapsed": elapsed, "metrics": metrics, "failed": failed}


def reference_run(ctx, steps: List[List[dict]], dtype=jnp.float32) -> dict:
    """The reference's own run of the recorded set-up steps (at ``dtype``,
    see ``reference.forward``): its weights
    from the seed, each step's advantages from its own rewards and the
    trajectories' tokens, on-policy loss and gradients of every trajectory
    in its own row, global-norm clipping and AdamW as the mix states."""
    p, mix, tc = ctx.p, ctx.mix, ctx.train_cfg
    tree = mix["tree"]
    params = weights.make_params(p, ctx.seed)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    rows_per_block = 4
    L = max(len(r["prompt"]) + len(r["tokens"]) for s in steps for r in s)
    L = -(-L // 64) * 64

    def block_loss(params, toks, resp, adv, count):
        lp = reference.token_logprobs(params, p, toks, dtype)
        ratio = jnp.exp(lp - jax.lax.stop_gradient(lp))
        obj = jnp.minimum(ratio * adv[:, None],
                          jnp.clip(ratio, 1 - tc.clip_eps_low,
                                   1 + tc.clip_eps_high) * adv[:, None])
        return -(obj * resp).sum() / count, lp

    grad_fn = jax.jit(jax.value_and_grad(block_loss, has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def adamw(params, grads, m, v, t, lr):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, tc.max_grad_norm / jnp.maximum(norm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda a, g: tc.beta1 * a + (1 - tc.beta1) * g,
                         m, grads)
        v = jax.tree.map(lambda a, g: tc.beta2 * a + (1 - tc.beta2) * g * g,
                         v, grads)
        bc1, bc2 = 1 - tc.beta1 ** t, 1 - tc.beta2 ** t
        new = jax.tree.map(
            lambda w, a, b: (w.astype(jnp.float32) - lr * (
                (a / bc1) / (jnp.sqrt(b / bc2) + tc.eps)
                + tc.weight_decay * w.astype(jnp.float32))).astype(w.dtype),
            params, m, v)
        return new, m, v, grads

    out = {"loss": [], "adv_scale": [], "logprobs": []}
    for k, rows in enumerate(steps):
        rewards = np.array([0.0 if r["failed"] else
                            compare.token_reward(ctx.seed, r["tokens"])
                            for r in rows])
        adv = compare.tree_advantages(rows, rewards, tree["segment_len"],
                                      tree["max_depth"])
        lengths = np.array([len(r["tokens"]) for r in rows])
        adv = adv * compare.global_norm_scale(adv, lengths)
        count = float(lengths.sum())
        grads = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32),
                             params)
        loss = 0.0
        lps = []
        for b in range(0, len(rows), rows_per_block):
            blk = rows[b:b + rows_per_block]
            toks = np.zeros((rows_per_block, L), np.int32)
            resp = np.zeros((rows_per_block, L - 1), np.float32)
            a = np.zeros((rows_per_block,), np.float32)
            for i, r in enumerate(blk):
                n_p, n_r = len(r["prompt"]), len(r["tokens"])
                toks[i, :n_p] = r["prompt"]
                toks[i, n_p:n_p + n_r] = r["tokens"]
                resp[i, n_p - 1:n_p - 1 + n_r] = 1.0
                a[i] = adv[b + i]
            (lv, lp), g = grad_fn(params, toks, resp, a, count)
            loss += float(lv)
            grads = jax.tree.map(lambda x, y: x + y.astype(jnp.float32),
                                 grads, g)
            lp = np.asarray(lp)
            for i, r in enumerate(blk):
                n_p, n_r = len(r["prompt"]), len(r["tokens"])
                lps.append(lp[i, n_p - 1:n_p - 1 + n_r])
        lr = tc.learning_rate * min((k + 1) / max(tc.warmup_steps, 1), 1.0)
        params, m, v, clipped = adamw(params, grads, m, v,
                                      jnp.float32(k + 1), jnp.float32(lr))
        if k == 0:
            out["grad"] = np.asarray(compare.leaf_norms(clipped))
        out["loss"].append(loss)
        out["adv_scale"].append(float(np.abs(np.repeat(adv, lengths)).mean()))
        out["logprobs"].append(lps)
        del grads, clipped
    p0 = weights.make_params(p, ctx.seed)
    out["change"] = np.asarray(compare.leaf_diff_norms(params, p0))
    return out


def readings(prog: dict, ref: dict, got: dict = None) -> Dict[str, float]:
    """The numbers compared, of ``got`` (the program's readings, or a
    control's) against the float32 reference ``ref``:

    * ``loss``: the largest gap of a set-up step's loss, over the step's
      mean |token advantage| (the scale of the loss);
    * ``grad``: the worst leaf's gap of the first gradient's norm;
    * ``change``: the worst leaf's gap of the parameters' change after the
      set-up steps, over leaves the reference's first gradient moves
      (at least a thousandth of the median leaf's);
    * ``rollout_logprob``: the largest gap of a sampled token's
      log-probability as the rollout reported it.
    """
    got = got if got is not None else prog
    loss = max(abs(a - b) / max(s, 1e-6) for a, b, s in
               zip(got["loss"], ref["loss"], ref["adv_scale"]))
    grad = compare.worst_leaf_gap(got["grad"], ref["grad"])
    keep = ref["grad"] >= 1e-3 * np.median(ref["grad"])
    change = compare.worst_leaf_gap(got["change"], ref["change"], keep)
    lp_gap = max(float(np.max(np.abs(np.asarray(g) - np.asarray(r))))
                 for gs, rs in zip(got["logprobs"], ref["logprobs"])
                 for g, r in zip(gs, rs))
    return {"loss": loss, "grad": grad, "change": change,
            "rollout_logprob": lp_gap}


def program_logprobs(prog: dict) -> List[List[List[float]]]:
    return [[r["logprobs"] for r in rows] for rows in prog["steps"]]

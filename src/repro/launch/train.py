"""RL training driver (the paper's Table-1 experiment at selectable scale).

Runs the full TreePO pipeline — BC warmup (base-model stand-in), tree
rollout, reward, advantage, PG update — on the local devices.  ``--arch``
selects any assigned architecture (reduced ``-smoke`` variants train on
CPU; full configs are exercised via ``repro.launch.dryrun``).

Crash-safe resume (docs/robustness.md): with ``--ckpt-dir``, checkpoints
carry the *complete* trainer state (params, optimizer moments, step, all
host RNGs, metrics cursor) via ``RLTrainer.state_dict``; ``--resume``
restarts from the newest one and continues the SAME run — remaining
steps reproduce what the uninterrupted run would have logged.  The JSONL
metrics log is appended to on resume, with a ``resumed_from`` field on
post-resume rows.

Examples:
  python -m repro.launch.train --arch qwen2.5-7b-smoke --mode treepo \\
      --steps 20 --bc-steps 150
  python -m repro.launch.train --arch olmoe-1b-7b-smoke --mode grpo_tree
  python -m repro.launch.train --arch qwen2.5-7b-smoke --steps 200 \\
      --ckpt-dir runs/ck --log runs/metrics.jsonl --resume
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.configs.base import TrainConfig, TreeConfig
from repro.core import faults
from repro.launch.compile_cache import enable_compile_cache
from repro.rl.trainer import RLTrainer, TrainerMode


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-7b-smoke")
    ap.add_argument("--mode", default="treepo",
                    choices=["grpo", "grpo_tree", "treepo"])
    ap.add_argument("--advantage", default="treepo",
                    choices=["grpo", "treepo", "treepo_size_weighted",
                             "treepo_subgroup_reject", "treepo_no_root"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--bc-steps", type=int, default=120)
    ap.add_argument("--queries", type=int, default=4)
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--segment", type=int, default=16)
    ap.add_argument("--branch-heuristic", default="uniform")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--keep-ckpts", type=int, default=3,
                    help="retain only the newest N checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the newest checkpoint in "
                         "--ckpt-dir (no-op if none exists)")
    ap.add_argument("--log", default=None, help="JSONL metrics path")
    ap.add_argument("--eval-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    tree_cfg = TreeConfig(
        max_depth=args.depth, segment_len=args.segment,
        max_width=args.width, branch_factor=2,
        init_divergence_low=2, init_divergence_high=2,
        temperature=0.9, branch_heuristic=args.branch_heuristic)
    train_cfg = TrainConfig(
        batch_size=args.queries, group_size=args.width,
        oversample_factor=2, max_resample_rounds=1,
        learning_rate=args.lr, advantage_kind=args.advantage,
        reward_shaping=0.1)
    trainer = RLTrainer(
        cfg, train_cfg, tree_cfg, TrainerMode(args.mode), seed=args.seed,
        engine_kwargs=dict(num_pages=4096, page_size=args.segment,
                           max_slots=256, max_queries=64,
                           max_prompt_len=256),
        min_difficulty=1, max_difficulty=2)

    resumed_from = None
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        resumed_from = latest_step(args.ckpt_dir)
        trainer.load_state_dict(load_checkpoint(args.ckpt_dir, resumed_from))
        print(f"resumed from step {resumed_from} ({args.ckpt_dir})")

    print(f"arch={cfg.name} params={cfg.num_params():,} mode={args.mode} "
          f"devices={jax.devices()}")
    if args.bc_steps and resumed_from is None:
        # BC warmup happens exactly once per run; its effect on params
        # lives inside the checkpoint, so a resume must not repeat it
        w = trainer.bc_warmup(steps=args.bc_steps)
        print(f"bc warmup: loss={w['bc_loss']:.4f}")

    def checkpoint(step: int) -> None:
        save_checkpoint(args.ckpt_dir, step, trainer.state_dict(),
                        keep_last=args.keep_ckpts)

    # append on resume: the pre-crash rows are the same run's history
    logf = open(args.log, "a" if resumed_from is not None else "w") \
        if args.log else None
    start = trainer.step
    for i in range(start, args.steps):
        faults.kill_point("train.step")
        m = trainer.train_step(num_queries=args.queries,
                               progress=i / max(args.steps - 1, 1))
        line = (f"step {m['step']:4d} loss={m.get('loss', float('nan')):.4f} "
                f"reward={m['reward_mean']:.3f} "
                f"len={m['response_len']:.0f} leaf={m['leaf_rate']:.2f} "
                f"tokens={m['sample_model_tokens']:.0f}")
        if (i + 1) % args.eval_every == 0 or i == args.steps - 1:
            ev = trainer.evaluate(num_queries=8, k=4)
            m.update(ev)
            line += f" maj@4={ev['maj_acc']:.2f} pass={ev['pass_any']:.2f}"
        print(line, flush=True)
        if logf:
            if resumed_from is not None:
                m = dict(m, resumed_from=resumed_from)
            logf.write(json.dumps(m) + "\n")
            logf.flush()
        if args.ckpt_dir and m["step"] % args.ckpt_interval == 0:
            checkpoint(m["step"])
    if args.ckpt_dir:
        checkpoint(trainer.step)
    if logf:
        logf.close()


if __name__ == "__main__":
    main()

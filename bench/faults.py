"""Faults planted underneath the timed path, for the tests and the chip
readings that show a broken program reads as not ``correct``:

* ``state_unchanged``: a step returns its state unchanged (RL: the
  optimizer step leaves parameters and moments as they were; serve: the
  serve program hands back the KV pool it was given);
* ``half_batch``: half of the batch left out (RL: the loss is the mean over
  the first half of the update's rows; serve: the first half of a
  dispatch's rows come back as token 0);
* ``token_altered``: a token altered where it is produced (the first token
  of every decoded segment, or the last of every served one, plus one).

There is no exchange between chips to leave out: every cell runs on one.
"""
from __future__ import annotations

import contextlib
from unittest import mock

FAULTS = ("state_unchanged", "half_batch", "token_altered")


def _rl_patches(fault: str):
    import repro.rl.update as update
    from repro.core.engine import TreeEngine

    if fault == "state_unchanged":
        return [mock.patch.object(
            update, "adamw_update",
            lambda params, grads, state, **kw: (params, state))]
    if fault == "half_batch":
        loss = update.dapo_pg_loss

        def half(lp_new, lp_old, adv, mask, **kw):
            n = mask.shape[0]
            return loss(lp_new, lp_old, adv, mask.at[n // 2:].set(0.0),
                        **kw)

        return [mock.patch.object(update, "dapo_pg_loss", half)]
    decode = TreeEngine.decode_segments

    def altered(self, paths, seg_len=None):
        out = decode(self, paths, seg_len)
        for r in out:
            r.tokens[0] = (r.tokens[0] + 1) % self.cfg.vocab_size
        return out

    return [mock.patch.object(TreeEngine, "decode_segments", altered)]


def _serve_patches(fault: str):
    from repro.core.engine import ModelRunner

    get = ModelRunner.get_serve_fn

    def wrapped(self, R, l):
        fn = get(self, R, l)

        def run(params, pools, *args):
            out = list(fn(params, pools, *args))
            if fault == "state_unchanged":
                out[0] = pools
            elif fault == "half_batch":
                out[2] = out[2].at[:R // 2].set(0)
            else:
                out[2] = out[2].at[:, -1].add(1) % self.cfg.vocab_size
            return tuple(out)

        return run

    return [mock.patch.object(ModelRunner, "get_serve_fn", wrapped)]


@contextlib.contextmanager
def planted(kind: str, fault: str):
    """Plant ``fault`` in the program for a cell of ``kind``."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    patches = (_rl_patches if kind == "rl" else _serve_patches)(fault)
    with contextlib.ExitStack() as stack:
        for p in patches:
            stack.enter_context(p)
        yield

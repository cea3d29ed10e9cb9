"""Training driver: synthetic data, sharded step, checkpointing, fault
tolerance.  On this CPU container it trains reduced/smoke configs for real;
on a pod the same driver runs the full configs (the step function and
shardings are exactly the dry-run ones).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch butterfly-lm-100m \
      --reduce --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduce --steps 20

(DP gradient compression lives in repro/optim/compression.py and is applied
inside shard_map over the data axis — see tests/test_sharding.py for the
multi-device path.)
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, reduced
from repro.core.policy import FactorizationPolicy, uniform_policy
from repro.data.synthetic import embeddings_batch, lm_batch
from repro.launch.compile_cache import use_compile_cache
from repro.runtime.fault_tolerance import (
    PreemptionHandler,
    StragglerWatchdog,
    run_fault_tolerant,
)
from repro.train.train_step import TrainConfig, init_train_state, make_train_step

logging.basicConfig(level=logging.INFO, format="%(asctime)s %(message)s")
log = logging.getLogger("repro.train")


def make_batch_fn(cfg, batch, seq, seed=0):
    def fn(step: int):
        if cfg.input_mode == "tokens":
            tok, lab = lm_batch(step, batch, seq, cfg.vocab_size, seed)
            return jnp.asarray(tok), jnp.asarray(lab)
        emb, lab = embeddings_batch(step, batch, seq, cfg.d_model,
                                    cfg.vocab_size, seed)
        return jnp.asarray(emb, cfg.dtype), jnp.asarray(lab)
    return fn


def resolve_policy(args) -> FactorizationPolicy | None:
    """--policy-json (a FactorizationPolicy.to_dict file) wins over --fact
    (uniform kind at the classic sites); None keeps the config's policy."""
    if args.policy_json:
        with open(args.policy_json) as f:
            return FactorizationPolicy.from_dict(json.load(f))
    if args.fact:
        return uniform_policy(args.fact, block_size=args.fact_block)
    return None


def train(cfg, *, steps: int, batch: int, seq: int, lr: float = 1e-3,
          microbatch: int = 0, ckpt_dir: str, ckpt_every: int = 25,
          resume: bool = False, max_failures: int = 3) -> list[float]:
    """Train ``cfg`` for ``steps`` steps on synthetic data; returns the
    per-step losses.  ``max_failures`` bounds how many failed steps are
    restored from the last checkpoint and replayed before the error is
    raised; 0 raises on the first."""
    tc = TrainConfig(lr=lr, microbatch=microbatch,
                     schedule="warmup_cosine", warmup=max(steps // 10, 5),
                     total_steps=steps)

    state = init_train_state(cfg, tc, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(cfg, tc))
    batch_fn = make_batch_fn(cfg, batch, seq)
    mgr = CheckpointManager(os.path.join(ckpt_dir, cfg.name), keep=3)
    watchdog = StragglerWatchdog()
    preemption = PreemptionHandler().install()

    start = 0
    if resume and mgr.latest_step() is not None:
        start, state = mgr.restore(state, policy=cfg.fact)
        log.info("resumed from step %d", start)

    losses = []

    def one_step(step: int, state):
        inp, lab = batch_fn(step)
        state, metrics = step_fn(state, inp, lab)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % 10 == 0 or step == start:
            log.info("step %d loss %.4f grad_norm %.3f", step, loss,
                     float(metrics["grad_norm"]))
        return state

    t0 = time.time()
    def restore_or_restart():
        # a failure before the first checkpoint restarts fresh instead of
        # masking the original error with FileNotFoundError
        if mgr.latest_step() is None:
            log.warning("no checkpoint yet; restarting from step %d", start)
            return start, init_train_state(cfg, tc, jax.random.PRNGKey(0))
        return mgr.restore(state, policy=cfg.fact)

    final_step, state = run_fault_tolerant(
        one_step, state, start, steps,
        save_fn=lambda s, st: mgr.save(s, st, blocking=False,
                                       policy=cfg.fact),
        restore_fn=restore_or_restart,
        checkpoint_every=ckpt_every, max_failures=max_failures,
        watchdog=watchdog, preemption=preemption)
    mgr.wait()
    dt = time.time() - t0
    log.info("done: %d steps in %.1fs (%.3fs/step), loss %.4f -> %.4f",
             final_step - start, dt, dt / max(final_step - start, 1),
             losses[0] if losses else float("nan"),
             np.mean(losses[-5:]) if losses else float("nan"))
    log.info("step-time stats: %s", watchdog.stats())
    mgr.save(final_step, state, policy=cfg.fact)
    preemption.uninstall()
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="butterfly-lm-100m")
    ap.add_argument("--reduce", action="store_true",
                    help="use the reduced smoke config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fact", default="",
                    help="uniform factorization kind at the classic sites "
                         "(butterfly|pixelfly|...)")
    ap.add_argument("--fact-block", type=int, default=32)
    ap.add_argument("--policy-json", default="",
                    help="path to a FactorizationPolicy JSON (per-site rules;"
                         " overrides --fact)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = reduced(cfg)
    policy = resolve_policy(args)
    if policy is not None:
        cfg = cfg.with_fact(policy)
    use_compile_cache()
    train(cfg, steps=args.steps, batch=args.batch, seq=args.seq, lr=args.lr,
          microbatch=args.microbatch, ckpt_dir=args.ckpt_dir,
          ckpt_every=args.ckpt_every, resume=args.resume)


if __name__ == "__main__":
    main()

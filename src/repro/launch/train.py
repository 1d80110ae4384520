"""Fault-tolerant training driver.

Production behaviours exercised here (test-verified in tests/):
  * deterministic stateless data cursor -> bitwise resume after a crash
  * atomic checkpointing every N steps with retention
  * straggler watchdog: per-step deadline logging (on a real multi-host
    cluster this is the signal to evict/replace the slow host; on this
    single-host container it logs)
  * --simulate-failure-at N: hard-exit mid-run to exercise restart
  * elastic rescale: checkpoints restore onto any mesh shape

Usage (CPU-scale example; the 100M-param end-to-end config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..configs import get_config
from ..data import DataConfig, SyntheticLM
from ..models import make_model
from ..optim import adamw
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override width (e.g. ~100M-param runs)")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--step-deadline-s", type=float, default=120.0)
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model)
    if args.layers:
        cfg = dataclasses.replace(cfg, num_layers=args.layers)

    model = make_model(cfg)
    data = SyntheticLM(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                  input_kind=cfg.input_kind,
                                  d_model=cfg.d_model))
    mgr = CheckpointManager(args.ckpt_dir, keep=3)

    @jax.jit
    def train_step(params, opt, batch, step):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        lr = adamw.warmup_cosine(step, peak_lr=args.lr, warmup=20,
                                 total=args.steps)
        params, opt, metrics = adamw.apply_update(params, grads, opt, lr=lr)
        metrics["loss"] = loss
        return params, opt, metrics

    # ---- resume or init ----
    start = 0
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw.init_state(params)
    if mgr.latest_step() is not None:
        tmpl = {"params": params, "opt": opt}
        restored, manifest = mgr.restore(tmpl)
        params, opt = restored["params"], restored["opt"]
        start = manifest["extra"]["data_step"]
        print(f"[resume] from step {start}")

    t_run = time.time()
    for step in range(start, args.steps):
        if args.simulate_failure_at is not None and step == args.simulate_failure_at:
            print(f"[failure-injection] dying at step {step}", flush=True)
            os._exit(42)
        t0 = time.time()
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        params, opt, metrics = train_step(params, opt, batch,
                                          jnp.asarray(step))
        dt = time.time() - t0
        if dt > args.step_deadline_s:
            print(f"[straggler] step {step} took {dt:.1f}s "
                  f"(deadline {args.step_deadline_s}s) - on a cluster this "
                  f"host would be flagged for replacement", flush=True)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} {dt:.2f}s",
                  flush=True)
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            mgr.save(step + 1, {"params": params, "opt": opt},
                     extra={"data_step": step + 1,
                            "arch": cfg.name, "loss": float(metrics["loss"])})
    print(f"[done] {args.steps - start} steps in {time.time() - t_run:.1f}s")
    return params


if __name__ == "__main__":
    main()

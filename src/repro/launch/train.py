"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
      --steps 50 --seq 256 --batch 8

Runs end-to-end on CPU with reduced configs; the same code path drives the
production mesh (the dry-run proves every full arch x shape lowers and
compiles on it). Features exercised here:
  * jitted train_step with gradient accumulation
  * checkpoint/restart (--resume; --fail-at N simulates a mid-run crash and
    recovers from the latest checkpoint — the fault-tolerance drill)
  * int8 error-feedback gradient compression (--compress)
  * deterministic restart-safe data pipeline
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import ARCH_IDS, OptimConfig, get_config, get_reduced
from repro.data.pipeline import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step, make_train_state
from repro.models.api import ModelSpec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="simulate a crash at this step (recovery drill)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    spec = ModelSpec(cfg)
    optim = OptimConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps,
                        compress_grads=args.compress)
    step_fn = jax.jit(
        build_train_step(spec, optim, accum_steps=args.accum), donate_argnums=0
    )
    state = make_train_state(spec, jax.random.PRNGKey(args.seed),
                             compress=args.compress)
    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir)

    start = 0
    if args.resume and ckpt.latest_step() is not None:
        state, extra, start = ckpt.restore(state)
        data.state.step = int(extra.get("data_step", start))
        print(f"[train] resumed from step {start}")

    print(f"[train] arch={cfg.name} params={spec.param_count():,} "
          f"accum={args.accum} compress={args.compress}")
    t0 = time.time()
    for step in range(start, args.steps):
        if args.fail_at and step == args.fail_at:
            print(f"[train] SIMULATED FAILURE at step {step} — restart with "
                  f"--resume to recover")
            raise SystemExit(42)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        data.state.step = step + 1
        state, metrics = step_fn(state, batch)
        if step % 5 == 0 or step == args.steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            print(f"[train] step {step:4d} loss={m['loss']:.4f} "
                  f"gnorm={m['grad_norm']:.3f} lr={m['lr']:.2e} "
                  f"({(time.time()-t0):.1f}s)")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, state, extra={"data_step": data.state.step})
    ckpt.wait()
    print("[train] done")


if __name__ == "__main__":
    main()

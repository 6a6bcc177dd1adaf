"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On real TPU pods this process runs per-host under the standard JAX
distributed bootstrap; on CPU it drives the reduced config end-to-end (the
same step function the dry-run lowers at full scale).  ``main`` returns
the loss of every step.
"""

from __future__ import annotations

import argparse
import os

import jax

from repro.config import reduced
from repro.configs import ALL_ARCHS, EXTRA_ARCHS, get
from repro.data.pipeline import SyntheticTokens
from repro.models import build_model
from repro.runtime.fault import StragglerMonitor, run_with_recovery
from repro.train.loop import Trainer
from repro.train.optimizer import AdamWConfig


def main(argv=None) -> dict:
    """Train on synthetic tokens; returns ``{"losses", "straggles"}``
    with one float loss per step run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="exanest-lm-100m",
                    choices=ALL_ARCHS + EXTRA_ARCHS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default="results/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--quantize-opt", action="store_true")
    args = ap.parse_args(argv)

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                          decay_steps=args.steps,
                          quantize_states=args.quantize_opt)
    trainer = Trainer(model, opt_cfg)
    state = trainer.init_state(jax.random.PRNGKey(0))
    data = SyntheticTokens(cfg, batch=args.batch, seq=args.seq)
    step_fn = trainer.make_step()
    mon = StragglerMonitor()
    losses = []

    def one_step(st, i):
        st, metrics = step_fn(st, data.batch_at(i))
        losses.append(float(metrics["loss"]))
        if i % 10 == 0:
            print(f"step {i} loss {losses[-1]:.4f}")
        return st

    os.makedirs(args.ckpt_dir, exist_ok=True)
    state, log = run_with_recovery(state, one_step, args.steps,
                                   ckpt_dir=args.ckpt_dir,
                                   ckpt_every=args.ckpt_every, straggler=mon)
    print(f"done: {args.steps} steps, straggles={log['straggles']}")
    return {"losses": losses, "straggles": log["straggles"]}


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()

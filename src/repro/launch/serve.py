"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Drives the slot-based continuous-batching engine with synthetic requests
and reports per-request latency in *engine steps* (submit -> done) — the
same quantity the simulated lane (``repro.serve.sim`` +
``benchmarks/serve_sweep.py``) reports in simulated microseconds, so the
real engine and the simulator publish comparable distributions.
``main`` returns the summary it prints, so a caller (``chip_smoke.py``)
can check it.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.config import reduced
from repro.configs import ALL_ARCHS, EXTRA_ARCHS, get
from repro.models import build_model
from repro.serve.engine import ServeEngine


def main(argv=None) -> dict:
    """Serve synthetic requests; returns ``{"requests", "done", "tokens",
    "steps", "seconds", "vocab_size", "outputs"}`` with ``outputs`` the
    generated token list per request id."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="exanest-lm-100m",
                    choices=ALL_ARCHS + EXTRA_ARCHS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--window", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed for the synthetic requests "
                         "(deterministic token streams per seed)")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args(argv)

    cfg = get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, slots=args.slots, window=args.window)
    rng = np.random.default_rng(args.seed)
    rids = [eng.submit(
        list(rng.integers(0, cfg.vocab_size, size=args.prompt_len)),
        max_new_tokens=args.max_new)
        for _ in range(args.requests)]
    t0 = time.perf_counter()
    steps = eng.run_until_idle(max_steps=10000)
    dt = time.perf_counter() - t0
    done = sum(eng.result(r) is not None for r in rids)
    toks = sum(len(eng.result(r) or []) for r in rids)
    print(f"served {done}/{args.requests} requests, {toks} tokens in "
          f"{steps} engine steps, {dt:.2f}s ({toks/max(dt,1e-9):.1f} tok/s)")
    summary = {"requests": args.requests, "done": done, "tokens": toks,
               "steps": steps, "seconds": dt, "vocab_size": cfg.vocab_size,
               "outputs": {r: eng.result(r) for r in rids}}
    stats = eng.request_steps()
    if stats:
        lat = np.sort(np.array([d - s for s, d in stats.values()],
                               dtype=np.float64))
        print(f"latency (submit->done, engine steps): "
              f"p50={np.quantile(lat, 0.5):.0f} "
              f"p90={np.quantile(lat, 0.9):.0f} "
              f"p99={np.quantile(lat, 0.99):.0f} max={lat.max():.0f}")
        for rid in sorted(stats)[:8]:
            s, d = stats[rid]
            print(f"  request {rid}: submit@{s} done@{d} "
                  f"({d - s} steps, {len(eng.result(rid) or [])} tokens)")
    return summary


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache
    use_compile_cache()
    main()

"""Run the system's main paths once on a TPU and check what comes out.

Default (one chip), every phase in this one process on ``jax.devices()[0]``:

* **serve** — exanest-lm-100m at full width through
  ``repro.launch.serve.main`` (8 requests, 4 slots, window 128, 16 new
  tokens): every request done, every token inside the vocabulary; then,
  for one prompt, the incremental decode logits against a full forward
  pass of the same tokens.
* **train** — 5 steps of batch 8 x 128 through
  ``repro.launch.train.main``: every loss finite.
* **replay** — the HPCG weak iteration at 512 ranks under 64 sampled
  link-fault sets, as one batched scenario replay on the jax scan engine
  (kernels on the TPU, float64 under the scoped x64 context), against
  the same call on the numpy engine within ``AGREEMENT_RTOL``.

``--chips 4`` runs only the multi-chip phase: the GSPMD-sharded train
step on a ``("data", "model") = (2, 2)`` mesh against the one-chip step
on the same batch.

With no TPU the script exits non-zero before any phase.  On success the
last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero without it.

    python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import shutil
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "exanest-lm-100m"
SEED = 0
#: serve shape: requests, slots, KV window, new tokens per request
REQUESTS, SLOTS, WINDOW, MAX_NEW = 8, 4, 128, 16
#: the one prompt whose incremental decode is checked against prefill
CHECK_PROMPT_LEN = 32
#: decode-vs-prefill tolerance (tests/test_models_smoke.py)
DECODE_TOL = 2e-2
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 5, 8, 128
#: replay shape: ranks and sampled fault sets (benchmarks/faults_sweep.py)
REPLAY_RANKS, REPLAY_SETS = 512, 64
#: compiled-vs-reference contract of every replay (faults_sweep.py)
AGREEMENT_RTOL = 1e-9
#: sharded-vs-one-chip train step tolerance (tests/test_distributed.py)
STEP_TOL = 2e-2
OUT = ROOT / "results" / "chip_smoke"


class CompileClock:
    """Sums JAX's compile events (trace, lowering, backend compile or
    cache fetch) between two :meth:`take` calls."""

    def __init__(self):
        self.secs = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.secs += secs
            self.compiles += event.endswith("backend_compile_duration")

    def take(self) -> tuple[float, int]:
        out = (self.secs, self.compiles)
        self.secs, self.compiles = 0.0, 0
        return out


def _check(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def _config(arch: str, reduced_cfg: bool):
    from repro.config import reduced
    from repro.configs import get
    cfg = get(arch)
    return reduced(cfg) if reduced_cfg else cfg


def serve_phase(arch: str = ARCH, reduced_cfg: bool = False):
    from repro.launch import serve
    argv = ["--arch", arch, "--requests", str(REQUESTS),
            "--slots", str(SLOTS), "--window", str(WINDOW),
            "--max-new", str(MAX_NEW), "--seed", str(SEED)]
    s = serve.main(argv + (["--reduced"] if reduced_cfg else []))
    toks = [t for out in s["outputs"].values() for t in out or []]
    print(f"serve: {s['done']}/{s['requests']} requests done, "
          f"{s['tokens']} tokens in {s['steps']} engine steps, "
          f"{s['seconds']} s")
    _check(s["done"] == s["requests"] == REQUESTS,
           f"{s['done']}/{s['requests']} requests done")
    _check(len(toks) == REQUESTS * MAX_NEW, f"{len(toks)} tokens served")
    _check(all(0 <= t < s["vocab_size"] for t in toks),
           "a served token lies outside the vocabulary")

    # The decode path (KV cache, per-row positions) is held to the full
    # forward pass in float32 at full matmul precision.  In bfloat16 the
    # two compiled programs round at different points, which on the chip
    # moves logits of scale ~4.5 by ~0.06; that gap is printed, not
    # checked.
    cfg = _config(arch, reduced_cfg)
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, size=CHECK_PROMPT_LEN).astype(np.int32)
    for dtype in ("bfloat16", "float32"):
        inc, full = _decode_vs_forward(
            dataclasses.replace(cfg, dtype=dtype), prompt)
        gap = float(np.max(np.abs(inc - full)))
        print(f"serve: {dtype} decode vs full forward over "
              f"{CHECK_PROMPT_LEN} tokens: max |diff| {gap} (logit scale "
              f"{float(np.max(np.abs(full)))}, same argmax "
              f"{bool(inc.argmax() == full.argmax())})")
    np.testing.assert_allclose(inc, full, rtol=DECODE_TOL, atol=DECODE_TOL)


def _decode_vs_forward(cfg, prompt) -> tuple[np.ndarray, np.ndarray]:
    """Last-position logits of ``prompt`` fed token by token through
    ``decode_step`` in the serve engine's calling convention (full slot
    row, per-row positions), and of one ``prefill`` over all of it."""
    from repro.models import build_model
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with jax.default_matmul_precision("highest"):
        decode = jax.jit(model.decode_step)
        cache = model.init_cache(SLOTS, WINDOW)
        for i, t in enumerate(prompt):
            row = np.zeros(SLOTS, np.int32)
            row[0] = t
            pos = np.zeros(SLOTS, np.int32)
            pos[0] = i
            lg, cache = decode(params, cache, {"token": jnp.asarray(row),
                                               "pos": jnp.asarray(pos)})
        full = jax.jit(model.prefill)(
            params, {"tokens": jnp.asarray(prompt[None])})[0]
        return (np.asarray(lg[0, 0], np.float32),
                np.asarray(full[0, 0], np.float32))


def train_phase(arch: str = ARCH, reduced_cfg: bool = False):
    from repro.launch import train
    ckpt = OUT / "ckpt"
    argv = ["--arch", arch, "--steps", str(TRAIN_STEPS),
            "--batch", str(TRAIN_BATCH), "--seq", str(TRAIN_SEQ),
            "--ckpt-dir", str(ckpt)]
    try:
        r = train.main(argv + (["--reduced"] if reduced_cfg else []))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    print(f"train: losses {r['losses']}")
    _check(len(r["losses"]) == TRAIN_STEPS, f"{len(r['losses'])} losses")
    _check(all(np.isfinite(r["losses"])), "a train loss is not finite")


def replay_phase(nranks: int = REPLAY_RANKS, n_sets: int = REPLAY_SETS):
    from repro.core.exanet.apps import ALL_APPS
    from repro.core.exanet.faults import batch_fault_axes, sample_fault_spec
    from repro.core.exanet.scan_engine import JaxScanEngine
    from repro.core.machine import ExanetMachine
    machine = ExanetMachine()
    prog = ALL_APPS["hpcg"]().emit_iteration("weak", nranks)
    topo = machine._mpi_for(nranks).topo
    rng = np.random.default_rng(nranks)
    specs = [sample_fault_spec(rng, topo, n_slow_links=2, n_lossy_links=1,
                               extra_latency_us=5.0) for _ in range(n_sets)]
    axes = batch_fault_axes(specs, prog)

    eng = JaxScanEngine()
    walls = []
    for _ in range(2):  # cold (compiles), then warm
        t0 = time.perf_counter()
        got = machine.cost_program_scenarios(prog, **axes, engine=eng)
        walls.append(time.perf_counter() - t0)
    per_replay = sum(eng.dispatches.values()) // 2
    kinds = collections.Counter(k for k, _, _ in eng.dispatches)
    t0 = time.perf_counter()
    ref = machine.cost_program_scenarios(prog, **axes, engine="numpy")
    numpy_wall = time.perf_counter() - t0

    rel = 0.0
    for g, r in zip(got, ref, strict=True):
        rel = max(rel, abs(g.latency_us - r.latency_us)
                  / max(abs(r.latency_us), 1e-12))
        for x, y in zip(r.clocks, g.clocks, strict=True):
            rel = max(rel, abs(y - x) / max(abs(x), 1e-12))
    lat = np.array([r.latency_us for r in ref])
    print(f"replay: hpcg weak {nranks} ranks x {n_sets} fault sets; "
          f"jax cold {walls[0]} s, warm {walls[1]} s, numpy {numpy_wall} s")
    print(f"replay: {per_replay} scan-kernel dispatches per replay over "
          f"{len(eng.dispatches)} kernel programs {dict(kinds)}; outputs on "
          f"{sorted(str(d) for d in eng.devices)}")
    print(f"replay: jax vs numpy max rel gap {rel} (limit {AGREEMENT_RTOL}); "
          f"latency p50 {float(np.median(lat))} us, max {float(lat.max())} us")
    _check(bool(eng.devices) and all(d.platform == jax.devices()[0].platform
                                     for d in eng.devices),
           f"scan kernel outputs live on {eng.devices}")
    _check(rel <= AGREEMENT_RTOL,
           f"jax-vs-numpy gap {rel} exceeds {AGREEMENT_RTOL}")


def sharded_train_phase(arch: str = ARCH, reduced_cfg: bool = False):
    """GSPMD train step on a (data, model) = (2, 2) mesh vs the one-chip
    step (what ``launch/dryrun.py`` lowers at pod scale)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.config import ShapeConfig
    from repro.data.pipeline import SyntheticTokens
    from repro.launch.mesh import make_mesh, make_parallel_ctx
    from repro.models import build_model
    from repro.parallel.sharding import (batch_specs, opt_state_specs,
                                         param_specs)
    from repro.train.loop import make_train_step
    from repro.train.optimizer import AdamWConfig, adamw_init

    devs = jax.devices()
    _check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    cfg = _config(arch, reduced_cfg)
    model = build_model(cfg)
    opt_cfg = AdamWConfig()
    params = model.init(jax.random.PRNGKey(0))
    opt = adamw_init(params, opt_cfg)
    batch = SyntheticTokens(cfg, batch=TRAIN_BATCH,
                            seq=TRAIN_SEQ).batch_at(0)

    t0 = time.perf_counter()
    p_ref, _, m_ref = jax.jit(make_train_step(model, opt_cfg, None))(
        params, opt, batch)
    loss_ref = float(m_ref["loss"])
    one_wall = time.perf_counter() - t0

    mesh = make_mesh((2, 2), ("data", "model"))
    pctx = make_parallel_ctx(mesh)
    shape = ShapeConfig("chip_smoke", TRAIN_SEQ, TRAIN_BATCH, "train")

    def shard(specs):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

    pshard = shard(param_specs(params, cfg, pctx))
    oshard = shard(opt_state_specs(opt, params, cfg, pctx))
    bshard = shard(batch_specs(cfg, shape, pctx))
    t0 = time.perf_counter()
    with mesh:
        step = jax.jit(make_train_step(model, opt_cfg, pctx),
                       in_shardings=(pshard, oshard, bshard))
        args = (jax.device_put(params, pshard), jax.device_put(opt, oshard),
                jax.device_put(batch, bshard))
        compiled = step.lower(*args).compile()
        p_new, _, m_new = compiled(*args)
        loss_new = float(m_new["loss"])
    mesh_wall = time.perf_counter() - t0

    tok = args[2]["tokens"]
    hlo = compiled.as_text()
    n_sharded = sum(len(x.sharding.device_set) == 4 and
                    not x.sharding.is_fully_replicated
                    for x in jax.tree_util.tree_leaves(p_new))
    gap = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32))))
              for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                              jax.tree_util.tree_leaves(p_new), strict=True))
    print(f"sharded: mesh {dict(mesh.shape)}; token batch shard "
          f"{tok.sharding.shard_shape(tok.shape)} of {tok.shape}; "
          f"{n_sharded}/{len(jax.tree_util.tree_leaves(p_new))} param leaves "
          f"sharded on the mesh; the step holds "
          f"{hlo.count(' all-reduce(')} all-reduce and "
          f"{hlo.count(' all-gather(')} all-gather ops")
    print(f"sharded: loss one-chip {loss_ref} vs mesh {loss_new} "
          f"(|diff| {abs(loss_ref - loss_new)}, limit {STEP_TOL}); updated "
          f"params max |diff| {gap}; one-chip step {one_wall} s, mesh step "
          f"{mesh_wall} s (both incl. compile)")
    _check(tok.sharding.shard_shape(tok.shape)[0] == TRAIN_BATCH // 2,
           "the batch is not split over the data axis")
    _check(abs(loss_ref - loss_new) < STEP_TOL,
           f"loss {loss_ref} vs {loss_new}")
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_new)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=STEP_TOL, atol=STEP_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-vs-one-chip train step")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({len(devs)} device(s)); no phase run",
              file=sys.stderr)
        return 2
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}")
    try:
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's src/ tree is missing next to this "
              f"script ({e}); no phase run", file=sys.stderr)
        return 2
    cache_dir = pathlib.Path(use_compile_cache())
    print(f"compile cache: {cache_dir}")
    OUT.mkdir(parents=True, exist_ok=True)

    phases = ([("sharded_train", sharded_train_phase)] if args.chips == 4
              else [("serve", serve_phase), ("train", train_phase),
                    ("replay", replay_phase)])
    clock = CompileClock()
    failed = []
    for name, fn in phases:
        print(f"== {name}", flush=True)
        clock.take()
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001 — report it, run the next phase
            traceback.print_exc()
            failed.append(name)
        wall = time.perf_counter() - t0
        compile_s, n_compiles = clock.take()
        print(f"{name}: {'FAILED' if name in failed else 'ok'}, wall {wall} s,"
              f" compile {compile_s} s over {n_compiles} compiles", flush=True)
    n_cached = sum(1 for _ in cache_dir.glob("*")) if cache_dir.is_dir() else 0
    print(f"compile cache: {n_cached} entries in {cache_dir}")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

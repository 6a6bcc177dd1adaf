"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), in seconds per step:

  compute    = HLO_FLOPs_per_device / peak_bf16_flops
  memory     = HLO_bytes_per_device / hbm_bw
  collective = collective_bytes_per_device / (ici_links x ici_link_bw)
               [+ cross-pod bytes / dcn_bw on the multi-pod mesh]

``cost_analysis()`` supplies FLOPs/bytes (already per-device under SPMD);
collective bytes are NOT in cost_analysis, so we parse the optimized HLO
text and sum operand sizes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op. Ops whose replica
groups cross the pod axis are charged to DCN on the multi-pod mesh.
"""

from __future__ import annotations

import re

from repro.roofline.hw import V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (optimized) HLO text.

    Works on the op's full line: `%out = TYPE[dims] op-name(%a, %b, ...)`.
    We count the OUTPUT tuple/array bytes per op — a uniform proxy for the
    data a chip injects into the fabric for that op (operand lists repeat
    shapes; outputs are unambiguous in text form).
    """
    out: dict = {k: 0 for k in _COLLECTIVES}
    out["ops"] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+?)\s+([\w\-]+)\(", ls)
        if not m:
            continue
        op = m.group(2)
        # normalize fusion/start-done variants: all-reduce-start etc.
        base = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):
                base = k
                break
        if base is None:
            continue
        shapes_txt = m.group(1)
        byts = sum(_shape_bytes(sm) for sm in _SHAPE_RE.finditer(shapes_txt))
        out[base] += byts
        out["ops"][base] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def roofline_terms(flops: float, bytes_hbm: float, coll_bytes: float,
                   *, cross_pod_bytes: float = 0.0, hw=V5E) -> dict:
    compute_s = flops / hw.peak_bf16_flops
    memory_s = bytes_hbm / hw.hbm_bw
    ici_s = coll_bytes / (hw.ici_links * hw.ici_link_bw)
    dcn_s = cross_pod_bytes / hw.dcn_bw
    collective_s = ici_s + dcn_s
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s, "ici_s": ici_s, "dcn_s": dcn_s}
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    terms["bottleneck"] = dom
    total = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    terms["step_bound_s"] = total
    terms["roofline_fraction"] = compute_s / total if total > 0 else 0.0
    return terms


def model_flops_per_step(meta: dict, shape_kind: str, tokens: int) -> float:
    """MODEL_FLOPS: 6*N*D for dense training (fwd+bwd), 2*N*D inference;
    N = active params (MoE uses activated experts only)."""
    n = meta.get("active_params_b", 0.0) * 1e9
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n * tokens


def lm_serve_step_cost(cfg, *, n_decode: float, decode_kv: float,
                       n_prefill: float = 0.0, prefill_kv: float = 0.0,
                       dtype_bytes: int = 2) -> dict:
    """Closed-form cost of ONE continuous-batching serving step for an
    :class:`~repro.config.ArchConfig` — the config-derived twin of what
    :mod:`repro.roofline.hlo_cost` measures on compiled HLO, cheap enough
    to evaluate per simulated step for any config (compiling a real
    deepseek-7b decode graph to read its HLO would dwarf the simulation).

    A step advances ``n_decode`` in-flight requests by one token (KV
    context ``decode_kv`` each, the batch mean) and pushes ``n_prefill``
    new prompt tokens through (on top of ``prefill_kv`` already-cached
    tokens; causal attention is charged at the mean context
    ``prefill_kv + n_prefill/2``).  FLOPs use the 2*N-per-token rule of
    :func:`model_flops_per_step` plus the KV-length-dependent attention
    term that rule omits; HBM bytes charge one weight sweep per step
    (shared by every token in the batch — the continuous-batching
    economy) plus KV reads/writes (an MLA config caches its compressed
    latent and rope key).  Returned collective payloads are
    whole-model totals; tensor-parallel sharding (the /nranks) is the
    caller's concern (:mod:`repro.serve.sim`).
    """
    P = float(cfg.param_count())
    L, hd = cfg.n_layers, cfg.resolved_head_dim
    if cfg.mla is not None:
        # MLA caches one latent and one rope key per token and layer; the
        # absorbed decode scores against both and sums the latent
        ml = cfg.mla
        kv_tok = L * (ml.kv_lora_rank + ml.qk_rope_head_dim) * dtype_bytes
        attn_fl_tok = 2.0 * L * cfg.n_heads * (2 * ml.kv_lora_rank
                                               + ml.qk_rope_head_dim)
    else:
        kv_tok = L * 2.0 * cfg.n_kv_heads * hd * dtype_bytes  # bytes/token
        attn_fl_tok = 4.0 * L * cfg.n_heads * hd          # flops/token/ctx
    nd, npf = float(n_decode), float(n_prefill)
    tokens = nd + npf
    pf_ctx = prefill_kv + npf / 2.0
    flops = (nd * (2.0 * P + attn_fl_tok * decode_kv)
             + npf * (2.0 * P + attn_fl_tok * pf_ctx))
    hbm = 0.0
    if tokens > 0:
        hbm += P * dtype_bytes                       # one weight sweep
        hbm += nd * decode_kv * kv_tok               # decode KV reads
        hbm += npf * pf_ctx * kv_tok                 # prefill KV reads
        hbm += tokens * kv_tok                       # KV writes
    return {
        "flops": flops,
        "hbm_bytes": hbm,
        # per-token activation gather payload (one hidden vector each)
        "act_bytes": tokens * cfg.d_model * dtype_bytes,
        # KV shards migrated for the newly-prefilled tokens
        "kv_bytes": npf * kv_tok,
        "kv_bytes_per_token": kv_tok,
    }


def lm_train_step_cost(cfg, *, seq_len: int, batch: int,
                       dtype_bytes: int = 2,
                       grad_dtype_bytes: int = 2) -> dict:
    """Closed-form cost of ONE data-parallel training step for an
    :class:`~repro.config.ArchConfig` — the train-side twin of
    :func:`lm_serve_step_cost`, and the analytic cross-anchor for the
    synthetic-HLO estimate (:func:`repro.roofline.hlo_cost.synth_train_hlo`
    through the same while-rollup cost model real dry-run artifacts use).

    FLOPs follow the 6N rule split as 2N forward + 4N backward per token
    (N = active params; MoE charges top-k + shared experts only) plus the
    context-dependent attention term that rule omits, charged at the mean
    causal context ``seq_len/2`` forward and twice that backward.  HBM
    bytes charge one weight sweep forward, two backward (read weights,
    write gradients) and one optimizer pass over master weights;
    ``grad_bytes`` is the full data-parallel gradient volume one rank
    contributes to the sync — bucketing/sharding is the caller's concern
    (:mod:`repro.train.cosim`).
    """
    Na = float(cfg.active_param_count())
    P = float(cfg.param_count())
    L, hd = cfg.n_layers, cfg.resolved_head_dim
    tokens = float(seq_len) * float(batch)
    attn_fl_tok = 4.0 * L * cfg.n_heads * hd      # flops/token/ctx-token
    fwd = tokens * (2.0 * Na + attn_fl_tok * seq_len / 2.0)
    bwd = 2.0 * fwd
    act_tok = cfg.n_layers * cfg.d_model * dtype_bytes
    return {
        "tokens": tokens,
        "fwd_flops": fwd,
        "bwd_flops": bwd,
        "flops": fwd + bwd,
        "grad_bytes": P * grad_dtype_bytes,
        "param_bytes": P * dtype_bytes,
        "hbm_bytes": 4.0 * P * dtype_bytes + 2.0 * tokens * act_tok,
        "act_bytes_per_token": act_tok,
    }


def serve_step_calibration(cfg, *, measured_step_us: float,
                           n_decode: float, decode_kv: float,
                           n_prefill: float = 0.0, prefill_kv: float = 0.0,
                           dtype_bytes: int = 2,
                           rate_flops_per_us: float,
                           bw_bytes_per_us: float,
                           overhead_us: float = 0.0) -> dict:
    """Measured-vs-predicted anchor for :func:`lm_serve_step_cost`: fold a
    measured per-step time (e.g. ``launch/serve.py``'s wall-clock over
    engine steps) back onto the roofline prediction for the same step
    state and report the ratio — the single calibration constant that
    would make the closed form match the measurement
    (``BENCH_serve.json``'s ``calibration`` row)."""
    c = lm_serve_step_cost(cfg, n_decode=n_decode, decode_kv=decode_kv,
                           n_prefill=n_prefill, prefill_kv=prefill_kv,
                           dtype_bytes=dtype_bytes)
    predicted = overhead_us + max(c["flops"] / rate_flops_per_us,
                                  c["hbm_bytes"] / bw_bytes_per_us)
    return {
        "measured_step_us": float(measured_step_us),
        "predicted_step_us": float(predicted),
        "measured_over_predicted": float(measured_step_us) / predicted,
        "predicted_flops": c["flops"],
        "predicted_hbm_bytes": c["hbm_bytes"],
    }


def roofline_from_compiled(compiled, meta: dict, hw=V5E) -> dict:
    """Roofline terms from the compiled artifact.

    XLA's aggregate ``cost_analysis()`` counts while-loop bodies ONCE
    (verified; see EXPERIMENTS.md §Dry-run), so scan-over-layers models
    under-report by ~n_layers. We therefore use the HLO-text cost model
    with known_trip_count rollup (repro.roofline.hlo_cost) as the primary
    source, and record raw cost_analysis for comparison.
    """
    from repro.roofline.hlo_cost import analyze_hlo
    ca = compiled.cost_analysis() or {}
    raw_flops = float(ca.get("flops", 0.0))
    raw_bytes = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    h = analyze_hlo(hlo)
    flops, byts, coll = h["flops"], h["bytes"], h["collectives"]
    multi = meta.get("mesh", "").startswith("2x")
    # cross-pod traffic: on the multi-pod mesh the gradient all-reduce over
    # the pod axis moves the FSDP-sharded gradient once across DCN
    cross = coll["all-reduce"] * 0.5 / 16.0 if multi else 0.0
    from repro.config import SHAPES
    shape = SHAPES[meta["shape"]]
    tokens = (shape.global_batch * shape.seq_len
              if shape.kind != "decode" else shape.global_batch)
    n_dev = 512 if multi else 256
    terms = roofline_terms(flops, byts, coll["total"],
                           cross_pod_bytes=cross, hw=hw)
    model_fl = model_flops_per_step(meta, shape.kind, tokens) / n_dev
    return {
        "hlo_cost": {"flops": flops, "bytes": byts},
        "cost_analysis_raw": {"flops": raw_flops,
                              "bytes_accessed": raw_bytes,
                              "note": "while bodies counted once by XLA"},
        "collectives": coll,
        "roofline": terms,
        "model_flops_per_device": model_fl,
        "useful_flops_ratio": (model_fl / flops) if flops else 0.0,
    }

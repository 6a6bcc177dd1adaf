"""Mixture-of-Experts FFN with expert-parallel (EP) dispatch.

The paper's interconnect exists to make exactly this pattern cheap: many
endpoints exchanging medium-size messages over a torus (§4.1-4.4). On TPU we
express the dispatch as an ``all_to_all`` inside ``shard_map`` — the ExaNet
analog of RDMA'ing token blocks between QFDBs.

Axis layout (matches tokens being DP-sharded over ``data``):
* **EP over `data`**: experts are sharded along the same axis that shards
  tokens, so each shard dispatches only ITS tokens (no duplicated compute),
  via all_to_all over `data` (within each pod replica group);
* **TP over `model`**: each expert's FFN hidden dim is column-sharded; one
  psum over `model` combines the partial w_out contraction at the end.

Two-level capacity buffers keep every shape static:
1. route: top-k over a replicated router (:func:`route`, node-limited
   where the config groups its experts);
2. pack per-destination-shard capacity buffers (scatter by running index);
3. ``all_to_all`` tokens + metadata to expert shards;
4. pack again into per-local-expert buffers; batched expert GEMMs
   (E_local, C, d) x (E_local, d, f_shard) — MXU-shaped, no one-hot
   dispatch einsum (for 256 experts that would dwarf the expert FLOPs);
5. ``all_to_all`` back, combine with routing weights, psum the TP partials.

Tokens that overflow a capacity buffer are dropped (classic capacity-factor
semantics); ``capacity_factor`` controls the overhead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ArchConfig
from repro.models.layers import dense_init, dtype_of


def init_moe(key, cfg: ArchConfig, d: int) -> dict:
    m = cfg.moe
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (m.n_experts, d, m.d_expert), dt,
                             scale=d ** -0.5),
        "w_up": dense_init(ks[2], (m.n_experts, d, m.d_expert), dt,
                           scale=d ** -0.5),
        "w_out": dense_init(ks[3], (m.n_experts, m.d_expert, d), dt,
                            scale=m.d_expert ** -0.5),
    }
    if m.n_shared_experts:
        ff = m.d_shared * m.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kk[0], (d, ff), dt),
            "w_up": dense_init(kk[1], (d, ff), dt),
            "w_out": dense_init(kk[2], (ff, d), dt),
        }
    return p



@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _qa2a(x, axis):
    """int8-quantized all_to_all (per-slot scales) with a quantized adjoint:
    both the dispatch and its gradient cross the wire in int8 + f32 scales
    (DeepSeek-V3 fp8-dispatch analog). x: (ep, cap, d)."""
    return _qa2a_fwd(x, axis)[0]


def _qa2a_impl(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                                keepdims=True) / 127.0, 1e-20)
    q8 = jnp.round(x.astype(jnp.float32) / scale).astype(jnp.int8)
    q8 = jax.lax.all_to_all(q8, axis, 0, 0, tiled=False)
    q8 = q8.reshape(x.shape)
    s = jax.lax.all_to_all(scale.astype(jnp.float32), axis, 0, 0,
                           tiled=False).reshape(x.shape[:-1] + (1,))
    return (q8.astype(x.dtype) * s.astype(x.dtype)).astype(x.dtype)


def _qa2a_fwd(x, axis):
    return _qa2a_impl(x, axis), None


def _qa2a_bwd(axis, _, g):
    return (_qa2a_impl(g, axis).astype(g.dtype),)


_qa2a.defvjp(_qa2a_fwd, _qa2a_bwd)


def route(logits, bias, m) -> jnp.ndarray:
    """Expert ids ``(T, top_k)`` a router picks from ``logits`` ``(T, E)``
    under the :class:`~repro.config.MoEConfig` ``m``.

    Softmax routers select on the logits (the softmax keeps their order).
    Sigmoid routers follow DeepSeek-V3's ``noaux_tc``: the scores
    ``s = sigmoid(logits)`` plus the per-expert ``bias`` (``None``: zero)
    rank the experts; with ``n_group > 1`` the experts fall into
    ``n_group`` contiguous groups, a group's score is the sum of its two
    best, and only the ``topk_group`` best groups stay eligible.  The
    ``top_k`` best eligible experts are returned, best first.  The bias
    only selects: combine weights come from the scores alone."""
    scores = logits if m.router_softmax else jax.nn.sigmoid(logits)
    sel = scores if bias is None else scores + bias
    if m.n_group > 1:
        T, E = sel.shape
        size = E // m.n_group
        grouped = sel.reshape(T, m.n_group, size)
        gscore = jnp.sum(jax.lax.top_k(grouped, min(2, size))[0], axis=-1)
        _, gids = jax.lax.top_k(gscore, m.topk_group)
        keep = jnp.zeros((T, m.n_group), bool).at[
            jnp.arange(T)[:, None], gids].set(True)
        sel = jnp.where(jnp.repeat(keep, size, axis=1), sel, -jnp.inf)
    return jax.lax.top_k(sel, m.top_k)[1]


def _pack(dest, n_dest, capacity, payload):
    """Scatter ``payload`` rows into (n_dest, capacity, ...) buffers by
    running index within each destination. Returns (buffers, pos, valid)."""
    oh = jax.nn.one_hot(dest, n_dest, dtype=jnp.int32)        # (T, D)
    pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(dest.shape[0]), dest]
    valid = pos < capacity
    pos_c = jnp.where(valid, pos, capacity - 1)
    buf = jnp.zeros((n_dest, capacity) + payload.shape[1:], payload.dtype)
    upd = jnp.where(valid[:, None] if payload.ndim == 2 else valid,
                    payload, 0).astype(payload.dtype)
    buf = buf.at[dest, pos_c].add(upd, mode="drop")
    return buf, pos, valid


def _expert_ffn(w_gate, w_up, w_out, x, cfg: ArchConfig):
    """x: (E_l, C, d) -> (E_l, C, d) batched over local experts; the hidden
    dim may be a TP shard (partial contributions combined by the caller)."""
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    return jnp.einsum("ecf,efd->ecd", act(g) * u, w_out)


def _moe_body(x, router_w, w_gate, w_up, w_out, cfg: ArchConfig,
              ep_axis: str | None, tp_axis: str | None):
    """Local view: x (T_l, d) — this shard's tokens; expert weights are the
    LOCAL (E_l, d, f_l) shard. ep_axis=None means all experts local."""
    m = cfg.moe
    T, d = x.shape
    E_l = w_gate.shape[0]
    ep = m.n_experts // E_l
    k = m.top_k

    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)  # (T, E)
    ids = route(logits, None, m)                                 # (T, k)
    vals = jnp.take_along_axis(logits, ids, axis=-1)
    if m.router_softmax:
        w = jax.nn.softmax(vals, axis=-1)
    else:
        w = jax.nn.sigmoid(vals)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    w = w * m.routed_scaling_factor

    flat_ids = ids.reshape(-1)                                   # (T*k,)
    flat_src = jnp.repeat(jnp.arange(T), k)
    dest_shard = flat_ids // E_l
    cap_send = max(1, math.ceil(T * k / ep * m.capacity_factor))
    payload = jnp.take(x, flat_src, axis=0)                      # (T*k, d)
    send, pos_send, valid_send = _pack(dest_shard, ep, cap_send, payload)
    meta = flat_ids % E_l                                        # local expert id
    send_meta, _, _ = _pack(dest_shard, ep, cap_send,
                            jnp.where(valid_send, meta + 1, 0))  # 0 == empty

    if ep_axis is not None and ep > 1:
        if m.a2a_quant:
            # int8 dispatch with per-slot scales (DeepSeek-V3 fp8-dispatch
            # analog): ~2x less wire bytes both ways; the custom VJP keeps
            # the gradient's all_to_all (round() alone would zero it out)
            send = _qa2a(send, ep_axis)
        else:
            send = jax.lax.all_to_all(send, ep_axis, 0, 0, tiled=False)
            send = send.reshape((ep, cap_send, d))
        send_meta = jax.lax.all_to_all(send_meta, ep_axis, 0, 0, tiled=False)
        send_meta = send_meta.reshape((ep, cap_send))

    # destination side: group received slots by local expert; empty wire
    # slots go to a trash bucket so they never consume expert capacity
    recv = send.reshape(ep * cap_send, d)
    recv_meta = send_meta.reshape(ep * cap_send)
    has_tok = recv_meta > 0
    local_e = jnp.where(has_tok, recv_meta - 1, E_l)
    # per-LOCAL-expert capacity: each local expert receives its global load
    # (T_local * ep sources * k / E experts == T_local * k / E_local)
    cap_e = max(1, math.ceil(T * k / E_l
                             * m.capacity_factor * m.capacity_factor))
    ebuf, pos_e, valid_e = _pack(local_e, E_l + 1, cap_e,
                                 jnp.where(has_tok[:, None], recv, 0))
    valid_e = valid_e & has_tok
    y_e = _expert_ffn(w_gate, w_up, w_out, ebuf[:E_l].astype(x.dtype), cfg)
    # un-pack back into the (ep, cap_send) wire layout
    flat_pos = jnp.where(valid_e, local_e * cap_e + pos_e, E_l * cap_e)
    back = jnp.take(y_e.reshape(E_l * cap_e, d), flat_pos, axis=0,
                    mode="fill", fill_value=0)
    back = back.reshape(ep, cap_send, d)

    if ep_axis is not None and ep > 1:
        if m.a2a_quant:
            back = _qa2a(back.astype(x.dtype), ep_axis)
        else:
            back = jax.lax.all_to_all(back, ep_axis, 0, 0, tiled=False)
            back = back.reshape((ep, cap_send, d))

    # combine at the source: gather each (t, j) contribution
    flat_idx = dest_shard * cap_send + jnp.where(valid_send, pos_send, 0)
    contrib = jnp.take(back.reshape(ep * cap_send, d), flat_idx, axis=0)
    contrib = jnp.where(valid_send[:, None], contrib, 0)
    contrib = contrib * w.reshape(-1)[:, None].astype(contrib.dtype)
    y = jax.ops.segment_sum(contrib, flat_src, num_segments=T)
    if tp_axis is not None:
        # combine the TP-partial w_out contractions
        y = jax.lax.psum(y, tp_axis)
    return y.astype(x.dtype)


def apply_moe(p: dict, x: jnp.ndarray, cfg: ArchConfig, pctx=None) -> jnp.ndarray:
    """x: (B, S, d). With a ParallelCtx: EP over 'data', TP over 'model'."""
    m = cfg.moe
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    ep_axis = "data"
    use_ep = (pctx is not None and ep_axis in pctx.mesh.axis_names
              and pctx.mesh.shape[ep_axis] > 1
              and m.n_experts % pctx.mesh.shape[ep_axis] == 0
              and (B * S) % pctx.dp_size == 0)
    if use_ep:
        tp_axis = (pctx.tp_axis if m.d_expert % pctx.tp_size == 0
                   and pctx.tp_size > 1 else None)
        f_spec = tp_axis
        body = functools.partial(_moe_body, cfg=cfg, ep_axis=ep_axis,
                                 tp_axis=tp_axis)
        fn = jax.shard_map(
            body, mesh=pctx.mesh,
            in_specs=(P(pctx.dp_axes, None), P(None, None),
                      P(ep_axis, None, f_spec), P(ep_axis, None, f_spec),
                      P(ep_axis, f_spec, None)),
            out_specs=P(pctx.dp_axes, None))
        y = fn(xt, p["router"], p["w_gate"], p["w_up"], p["w_out"])
    else:
        y = _moe_body(xt, p["router"], p["w_gate"], p["w_up"], p["w_out"],
                      cfg, None, None)
    y = y.reshape(B, S, d)
    if m.n_shared_experts:
        sh = p["shared"]
        act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
        y = y + (act(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_out"]
    return y

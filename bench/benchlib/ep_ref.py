"""Plain reference of one expert-parallel decode step of a node-limited
MoE model (DeepSeek-V3) on the ExaNeSt rack.

Written from the semantics alone and importing nothing of the program
under test: the model's widths and routing values, the deployment and the
machine's constants come from the configuration file, the router logits
and the fault draws from seeds.  The machine, its routes and its resource
clocks are :mod:`benchlib.exanet_ref`'s.

The deployment (``config["deployment"]``): ``ranks`` ranks, rank ``r`` on
core ``r * cores_per_mpsoc / ranks_per_mpsoc``, each holding
``n_routed_experts / ranks`` consecutive routed experts and decoding
``tokens_per_rank`` sequences of ``context`` cached tokens.  The step runs
``first_k_dense_replace`` dense layers, then the MoE layers up to
``num_hidden_layers``.

Routing (``noaux_tc``): scores ``sigmoid(logits)``; the experts fall into
``n_group`` contiguous groups, a group scores the sum of its two best, a
token keeps the ``topk_group`` best groups and picks its
``num_experts_per_tok`` best experts inside them.

Per rank, a dense layer computes; an MoE layer computes attention, the
shared expert and the router, receives from every rank that routes it a
token (one message per (token, rank) pair: a token that picks two experts
here comes once, one that stays home moves nothing), sends likewise, waits
for all of them, computes its experts over the tokens they got, and sends
the results back along the same pairs.  A compute takes the larger of its
operations over the rank's rate and its bytes over the rank's memory
bandwidth (``cores_per_rank`` A53 cores); posting a send or a receive
costs ``a53_call_overhead_us``; every message is a rendez-vous transfer
issued when both sides have posted.

Message order: each rank posts its receives from ranks r-1, r-2, ...
then its sends to r+1, r+2, ... (modulo ``ranks``; the combine receives
from the dispatch's destinations and sends to its sources, in the same
turns).  Ranks advance one operation at a time, the rank with the
smallest clock first and the lower rank on a tie, and a message fires
when its second side is posted.  Resources are taken in that firing order
on the healthy machine; a fault set changes the times, never the order.
"""

from __future__ import annotations

import heapq

import numpy as np

from benchlib.exanet_ref import Machine, _Costs

#: op kinds of a rank's stream
COMPUTE, SEND, RECV, WAIT = range(4)


def route(logits, c: dict) -> np.ndarray:
    """Expert ids ``(tokens, num_experts_per_tok)``, best first."""
    s = 1.0 / (1.0 + np.exp(-np.asarray(logits, dtype=np.float64)))
    n_tok, n_exp = s.shape
    size = n_exp // c["n_group"]
    grouped = s.reshape(n_tok, c["n_group"], size)
    gscore = np.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)
    groups = np.argsort(-gscore, axis=-1, kind="stable")[:, :c["topk_group"]]
    keep = np.zeros((n_tok, c["n_group"]), dtype=bool)
    keep[np.arange(n_tok)[:, None], groups] = True
    masked = np.where(np.repeat(keep, size, axis=1), s, -np.inf)
    return np.argsort(-masked, axis=-1,
                      kind="stable")[:, :c["num_experts_per_tok"]]


def _attention_params(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


class Step:
    """The step's traffic and compute times for one draw of logits."""

    def __init__(self, config: dict, logits):
        c, dep = config, config["deployment"]
        self.c, self.dep = c, dep
        self.n = dep["ranks"]
        self.tok = dep["tokens_per_rank"]
        self.per_rank = c["n_routed_experts"] // self.n
        self.n_dense = c["first_k_dense_replace"]
        self.n_moe = c["num_hidden_layers"] - self.n_dense
        self.ids = [route(lg, c) for lg in logits]
        d = c["hidden_size"]
        wb = dep["weight_bytes"]
        ctx = float(dep["context"])
        n_tok = float(self.tok)
        # MLA: one latent and one rope key cached per token; the absorbed
        # decode scores against both and sums the latent, per head
        kv_tok = (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * wb
        attn_fl = 2.0 * c["num_attention_heads"] * (
            2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])
        attn = _attention_params(c)

        def layer_us(params, tokens, attention):
            """One layer's part on one rank: a weight sweep (none
            without tokens), two operations per weight and token, and
            with ``attention`` the cache reads and writes."""
            flops = tokens * (2.0 * params
                              + (attn_fl * ctx if attention else 0.0))
            hbm = params * wb if tokens > 0 else 0.0
            if attention:
                hbm += tokens * ctx * kv_tok + tokens * kv_tok
            return max(flops / (dep["core_flops_per_us"]
                                * dep["cores_per_rank"]),
                       hbm / (dep["core_bytes_per_us"]
                              * dep["cores_per_rank"]))

        self.dense_us = layer_us(attn + 3 * d * c["intermediate_size"],
                                 n_tok, True)
        self.dp_us = layer_us(
            attn + d * c["n_routed_experts"]
            + c["n_shared_experts"] * 3 * d * c["moe_intermediate_size"],
            n_tok, True)
        expert = 3 * d * c["moe_intermediate_size"]
        self.pairs, self.expert_us = [], []
        for ids in self.ids:
            ranks = ids // self.per_rank
            pairs = {}
            for t, row in enumerate(ranks):
                src = t // self.tok
                for dst in set(row.tolist()) - {src}:
                    pairs[(src, dst)] = pairs.get((src, dst), 0) + 1
            self.pairs.append(pairs)
            load = np.bincount(ids.ravel(), minlength=c["n_routed_experts"])
            self.expert_us.append([
                sum(layer_us(expert, float(load[e]), False)
                    for e in range(r * self.per_rank,
                                   (r + 1) * self.per_rank))
                for r in range(self.n)])

    def ops(self) -> list:
        """Each rank's operations; a message is ``(src, dst, tag)`` and
        its bytes."""
        n, dep = self.n, self.dep
        out = []
        for r in range(n):
            ops = [(COMPUTE, self.dense_us)] * self.n_dense
            for layer, pairs in enumerate(self.pairs):
                srcs = [(r - i) % n for i in range(1, n)
                        if ((r - i) % n, r) in pairs]
                dsts = [(r + i) % n for i in range(1, n)
                        if (r, (r + i) % n) in pairs]
                db = dep["dispatch_bytes_per_token"]
                cb = dep["combine_bytes_per_token"]
                ops.append((COMPUTE, self.dp_us))
                ops += [(RECV, (s, r, 2 * layer), pairs[(s, r)] * db)
                        for s in srcs]
                ops += [(SEND, (r, d, 2 * layer), pairs[(r, d)] * db)
                        for d in dsts]
                ops.append((WAIT,))
                ops.append((COMPUTE, self.expert_us[layer][r]))
                ops += [(RECV, (d, r, 2 * layer + 1), pairs[(r, d)] * cb)
                        for d in dsts]
                ops += [(SEND, (r, s, 2 * layer + 1), pairs[(s, r)] * cb)
                        for s in srcs]
                ops.append((WAIT,))
            out.append(ops)
        return out


def _core(m: dict, dep: dict, rank: int) -> int:
    return rank * m["cores_per_mpsoc"] // dep["ranks_per_mpsoc"]


def firing_order(machine: Machine, step: Step, ops: list) -> list:
    """The messages in the order they fire on the healthy machine."""
    m, dep = machine.m, step.dep
    costs = _Costs(machine, {})
    n = len(ops)
    clock, pc = [0.0] * n, [0] * n
    posted, done, order = {}, {}, []
    outstanding = [[] for _ in range(n)]
    waiting = {}                    # blocked rank -> open messages
    waiter = {}                     # open message -> ranks blocked on it
    ready = [(0.0, r) for r in range(n)]
    heapq.heapify(ready)
    while ready:
        _, r = heapq.heappop(ready)
        if r in waiting or pc[r] >= len(ops[r]):
            continue
        op = ops[r][pc[r]]
        pc[r] += 1
        if op[0] == COMPUTE:
            clock[r] += op[1]
        elif op[0] == WAIT:
            reqs, outstanding[r] = outstanding[r], []
            open_ = [q for q in reqs if q not in done]
            if open_:
                waiting[r] = (reqs, len(open_))
                for q in open_:
                    waiter.setdefault(q, []).append(r)
            else:
                clock[r] = max([clock[r]] + [done[q] for q in reqs])
        else:
            msg, nbytes = op[1], op[2]
            t_post = clock[r]
            clock[r] += m["a53_call_overhead_us"]
            outstanding[r].append(msg)
            other = posted.pop(msg, None)
            if other is None:
                posted[msg] = t_post
            else:
                src, dst, _ = msg
                done[msg] = costs.rendezvous(
                    _core(m, dep, src), _core(m, dep, dst), nbytes,
                    max(t_post, other), m["sw_oneway_base_us"])
                order.append((msg, nbytes))
                for w in waiter.pop(msg, ()):
                    reqs, left = waiting[w]
                    if left == 1:
                        del waiting[w]
                        clock[w] = max([clock[w]] + [done[q] for q in reqs])
                        heapq.heappush(ready, (clock[w], w))
                    else:
                        waiting[w] = (reqs, left - 1)
        if r not in waiting and pc[r] < len(ops[r]):
            heapq.heappush(ready, (clock[r], r))
    return order


def step_latency(machine: Machine, step: Step, ops: list, order: list,
                 faults: dict) -> tuple:
    """``(latency_us, per-rank clocks)`` of the step under ``faults``,
    taking resources in ``order`` (:func:`firing_order`)."""
    m, dep = machine.m, step.dep
    costs = _Costs(machine, faults)
    n = len(ops)
    clock, pc = [0.0] * n, [0] * n
    posts, done = {}, {}
    outstanding = [[] for _ in range(n)]

    def advance(r, until=None):
        """Run rank ``r`` through its next operations, up to and
        including the post of message ``until`` (to its end for None)."""
        while pc[r] < len(ops[r]):
            op = ops[r][pc[r]]
            pc[r] += 1
            if op[0] == COMPUTE:
                clock[r] += op[1]
            elif op[0] == WAIT:
                reqs, outstanding[r] = outstanding[r], []
                clock[r] = max([clock[r]] + [done[q] for q in reqs])
            else:
                posts[(op[1], op[0])] = clock[r]
                clock[r] += m["a53_call_overhead_us"]
                outstanding[r].append(op[1])
                if op[1] == until:
                    return

    for msg, nbytes in order:
        src, dst, _ = msg
        for r in (src, dst):
            if (msg, SEND if r == src else RECV) not in posts:
                advance(r, msg)
        done[msg] = costs.rendezvous(
            _core(m, dep, src), _core(m, dep, dst), nbytes,
            max(posts[(msg, SEND)], posts[(msg, RECV)]),
            m["sw_oneway_base_us"])
    for r in range(n):
        advance(r)
    return max(clock), clock

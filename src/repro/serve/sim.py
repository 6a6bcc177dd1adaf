"""Simulated LM serving as a first-class Program-IR workload (ROADMAP
item 1): continuous-batching decode/prefill steps emitted as per-rank
``Compute`` + embedded KV/activation ``Collective``\\ s, costed by the
closed-form roofline estimator
(:func:`repro.roofline.analysis.lm_serve_step_cost`) and executed on the
ExaNeSt event engine — congestion, skewed collective entries and
per-rank arrival jitter are simulated, not modeled.

The fast path is the whole point (DESIGN.md §2.7): a continuous-batching
server only ever occupies finitely many *step states* — (decoding slots,
prefilling slots, KV-occupancy bucket) — so an entire load sweep needs
just one :meth:`~repro.core.exanet.mpi.ExanetMPI.run_program_scenarios`
call: every (state x Monte-Carlo-draw) binds as one column of the
compiled artifact (per-column compute skew, per-column collective
payloads via the ``site_scale`` seam, per-rank arrival skew via the
``t0`` axis), and the open-loop traffic replay
(:mod:`repro.serve.traffic`) then walks millions of simulated steps as
table lookups.  The per-step lane — rebind + ``run_program`` per
simulated step — is the baseline the speedup row in ``BENCH_serve.json``
measures against.

Step model
----------
One step advances every decoding slot by one token and pushes one
``prefill_chunk``-sized chunk through every prefilling slot (chunked
prefill: a P-token prompt occupies its slot for ``ceil(P/chunk)`` steps,
its final chunk emitting the first output token).  Per rank (tensor
parallelism over all ``nranks``) the step is::

    Compute(roofline max of flops/rate and bytes/bw, jittered)
    Collective(allgather,  act_bytes / nranks)   # per-token activations
    Collective(alltoall | allgather, kv_bytes / nranks)  # KV-shard moves

The KV-shard exchange is a pairwise ``alltoall`` up to
``alltoall_max_ranks`` and an ``allgather`` beyond it: the XOR-pairwise
schedule is O(nranks) exchange rounds, which a real system would never
run over thousands of ranks for a few migrated shards — and which would
also dominate the compiled replay itself.  Both ops resolve to a single
schedule regardless of payload, so per-column ``site_scale`` bindings
can never flip the probe tape (the hazard ``algo="auto"`` sites have).

Expert-parallel decode
----------------------
:class:`EPDecodeSim` emits the other layout: one decode step of a
node-limited MoE model (DeepSeek-V3) with the routed experts spread over
the ranks and attention, the shared expert and the router data-parallel,
one rank per MPSoC.  The dispatch and combine are ``Isend``/``Irecv``
all-to-alls whose message sizes follow the routing (an alltoallv, which
no uniform collective schedule expresses); see
:meth:`EPDecodeSim.emit_step` for the step and its message order.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.program import (Collective, Compute, Irecv, Isend, Program,
                                Wait)
from repro.runtime import spans


@dataclasses.dataclass(frozen=True)
class ServeSimSpec:
    """One simulated serving deployment: model config x machine shard."""
    arch: str = "exanest-lm-100m"
    nranks: int = 512
    slots: int = 8                 #: continuous-batching slots per replica
    window: int = 1024             #: KV capacity per slot (tokens)
    prefill_chunk: int = 256       #: prompt tokens per prefill step
    dtype_bytes: int = 2
    #: per-rank A53-class compute roofline: NEON peak (~8 flop/cycle at
    #: 1.5 GHz) and the per-core DDR copy bandwidth of params.HwParams
    core_rate_flops_per_us: float = 12000.0
    mem_bw_bytes_per_us: float = 2000.0
    #: fixed per-step dispatch overhead (kernel launches, batching glue)
    step_overhead_us: float = 25.0
    #: KV-occupancy buckets the step table quantizes decode context into
    kv_buckets: int = 4
    #: per-rank request-dispatch jitter, uniform [0, skew) us (t0 axis)
    arrival_skew_us: float = 2.0
    #: multiplicative per-rank compute noise, uniform 1 +/- jitter
    compute_jitter: float = 0.02
    #: pairwise alltoall is O(nranks) rounds; beyond this the KV-shard
    #: exchange emits as a recursive-doubling allgather instead
    alltoall_max_ranks: int = 128

    def kv_centers(self) -> np.ndarray:
        """Bucket-center KV occupancies (tokens) for the step table."""
        k = max(1, int(self.kv_buckets))
        return (np.arange(k) + 0.5) * (self.window / k)

    def kv_bucket(self, kv_mean: float) -> int:
        k = max(1, int(self.kv_buckets))
        return min(k - 1, max(0, int(kv_mean / self.window * k)))


@dataclasses.dataclass
class StepTable:
    """Batched step-latency table: one row per step state, one column
    per Monte-Carlo draw — the product of ONE ``run_program_scenarios``
    call.  ``cols`` maps a (state, draw) back to its scenario column so
    the per-step lane can rebind the *identical* payload."""
    states: list          #: [(n_decode, n_prefill, kv_bucket), ...]
    mc: int
    us: np.ndarray        #: (n_states, mc) simulated step latency
    index: dict           #: state -> row
    compute_scale: np.ndarray   #: (nranks, N) column compute skew
    site_scale: np.ndarray      #: (n_sites, N) column payload scale
    t0: np.ndarray              #: (nranks, N) column entry clocks

    def col(self, state, j: int) -> int:
        return self.index[state] * self.mc + int(j)

    def lookup(self, nd: int, npf: int, kvb: int, step: int) -> float:
        """Step latency for a replay step: deterministic draw rotation."""
        return float(self.us[self.index[(nd, npf, kvb)], step % self.mc])


class ServeSim:
    """Emit + cost serving-step Programs for one :class:`ServeSimSpec`.

    The simulation instance (base prototype or scaled-torus twin) is
    resolved per rank count through the same
    :meth:`~repro.core.machine.ExanetMachine._mpi_for` tier cache the
    planner and app sweeps use.
    """

    def __init__(self, spec: ServeSimSpec, mpi=None):
        from repro.configs import get
        self.spec = spec
        self.cfg = get(spec.arch)
        if mpi is None:
            from repro.core.exanet.mpi import ExanetMPI
            from repro.core.exanet.params import DEFAULT
            from repro.core.machine import ExanetMachine
            mpi = ExanetMachine(mpi=ExanetMPI(DEFAULT))._mpi_for(spec.nranks)
        self.mpi = mpi
        if spec.nranks & (spec.nranks - 1):
            raise ValueError(
                f"nranks must be a power of two for the allgather/"
                f"alltoall schedules; got {spec.nranks}")
        self._base_state = (max(1, spec.slots), 1,
                            spec.kv_buckets // 2)
        self._base_prog = None

    # ------------------------------------------------------------- costing
    def step_cost(self, nd: float, npf: float, kv_mean: float) -> dict:
        """Whole-model cost of one (nd decode, npf prefill-chunk) step."""
        from repro.roofline.analysis import lm_serve_step_cost
        sp = self.spec
        return lm_serve_step_cost(
            self.cfg, n_decode=nd, decode_kv=kv_mean,
            n_prefill=npf * sp.prefill_chunk,
            prefill_kv=0.0, dtype_bytes=sp.dtype_bytes)

    def rank_compute_us(self, nd: float, npf: float,
                        kv_mean: float) -> float:
        """Per-rank roofline step compute: the tensor-parallel shard of
        the whole-model flops/bytes, whichever roof binds, plus the
        fixed dispatch overhead."""
        sp = self.spec
        c = self.step_cost(nd, npf, kv_mean)
        return sp.step_overhead_us + max(
            c["flops"] / sp.nranks / sp.core_rate_flops_per_us,
            c["hbm_bytes"] / sp.nranks / sp.mem_bw_bytes_per_us)

    def site_bytes(self, nd: float, npf: float, kv_mean: float) -> tuple:
        """(act allgather, kv exchange) per-rank payloads in bytes."""
        c = self.step_cost(nd, npf, kv_mean)
        n = self.spec.nranks
        return (max(1, int(round(c["act_bytes"] / n))),
                max(1, int(round(c["kv_bytes"] / n))) if npf > 0 else 1)

    # ------------------------------------------------------------ emission
    def kv_exchange_op(self) -> tuple:
        """(op, algo) of the KV-shard exchange collective."""
        if self.spec.nranks <= self.spec.alltoall_max_ranks:
            return "alltoall", "pairwise"
        return "allgather", "recursive_doubling"

    def emit_step(self, nd: int, npf: int, kv_mean: float) -> Program:
        """One serving step as a Program: every rank computes its shard
        then enters the activation allgather and the KV-shard exchange.
        Structure is state-independent — only payloads move — so every
        step of every load point binds as a column of ONE artifact."""
        sp = self.spec
        us = self.rank_compute_us(nd, npf, kv_mean)
        act_b, kv_b = self.site_bytes(nd, npf, kv_mean)
        kv_op, kv_algo = self.kv_exchange_op()
        ops = (Compute(us=us),
               Collective(op="allgather", nbytes=act_b,
                          algo="recursive_doubling"),
               Collective(op=kv_op, nbytes=kv_b, algo=kv_algo))
        return Program(tuple(ops for _ in range(sp.nranks)))

    def base_program(self) -> Program:
        """The base binding every scenario column perturbs (all payloads
        strictly positive, so per-column multiplicative scales are
        well-defined)."""
        if self._base_prog is None:
            nd, npf, kvb = self._base_state
            kv = float(self.spec.kv_centers()[kvb])
            self._base_prog = self.emit_step(nd, npf, kv)
        return self._base_prog

    # --------------------------------------------------------- step states
    def step_states(self) -> list:
        """Every (n_decode, n_prefill, kv_bucket) a replay can occupy:
        occupancy up to ``slots``, KV bucketed only where decode reads
        it (pure-prefill states pin bucket 0)."""
        sp = self.spec
        out = []
        for nd in range(sp.slots + 1):
            for npf in range(sp.slots + 1 - nd):
                if nd == 0 and npf == 0:
                    continue
                for kvb in (range(sp.kv_buckets) if nd else (0,)):
                    out.append((nd, npf, kvb))
        return out

    # ------------------------------------------------------------ the table
    def build_table(self, *, mc: int = 3, rng=None, engine=None,
                    check: int = 0, rtol: float = 1e-9) -> StepTable:
        """Cost every step state x Monte-Carlo draw in ONE batched
        scenario replay.  ``check`` forwards to
        :meth:`~repro.core.exanet.mpi.ExanetMPI.run_program_scenarios`
        (sampled columns re-run on the interpreter, <=1e-9 agreement or
        raise)."""
        sp = self.spec
        rng = np.random.default_rng(rng)
        states = self.step_states()
        centers = sp.kv_centers()
        base = self.base_program()
        base_us = self.rank_compute_us(
            self._base_state[0], self._base_state[1],
            float(centers[self._base_state[2]]))
        base_sites = np.array(self.site_bytes(
            self._base_state[0], self._base_state[1],
            float(centers[self._base_state[2]])), dtype=np.float64)
        n_states = len(states)
        N = n_states * mc
        cs = np.empty((sp.nranks, N))
        ss = np.empty((2, N))
        for i, (nd, npf, kvb) in enumerate(states):
            kv = float(centers[kvb])
            cols = slice(i * mc, (i + 1) * mc)
            cs[:, cols] = self.rank_compute_us(nd, npf, kv) / base_us
            a, k = self.site_bytes(nd, npf, kv)
            ss[0, cols] = a / base_sites[0]
            ss[1, cols] = k / base_sites[1]
        if sp.compute_jitter > 0:
            cs *= rng.uniform(1.0 - sp.compute_jitter,
                              1.0 + sp.compute_jitter, cs.shape)
        t0 = rng.uniform(0.0, max(sp.arrival_skew_us, 1e-30),
                         (sp.nranks, N))
        res = self.mpi.run_program_scenarios(
            base, compute_scale=cs, site_scale=ss, t0=t0,
            engine=engine, check=check, rtol=rtol)
        us = np.array([r.latency_us for r in res]).reshape(n_states, mc)
        return StepTable(states=states, mc=mc, us=us,
                         index={s: i for i, s in enumerate(states)},
                         compute_scale=cs, site_scale=ss, t0=t0)

    # ------------------------------------------------------ per-step lane
    def step_time_single(self, table: StepTable, state, j: int, *,
                         backend: str = "auto", engine=None) -> float:
        """The naive lane: rebind the column's exact payload as a fresh
        Program and run it alone — what a per-step simulator pays for
        every simulated step.  Bit-identical inputs to the batched
        column, so lane agreement is pure executor agreement."""
        from repro.core.exanet.program_compiled import (extract_data,
                                                        rebind_program)
        b = table.col(state, j)
        base = self.base_program()
        data = extract_data(base)
        comp = np.array(data[0]) * table.compute_scale[:, b]
        site = np.rint(np.array(data[2], dtype=np.float64)
                       * table.site_scale[:, b]).astype(np.int64)
        prog = rebind_program(base, compute_us=comp, site_nbytes=site)
        return self.mpi.run_program(prog, backend=backend, engine=engine,
                                    t0=table.t0[:, b]).latency_us


# ---------------------------------------------------------------------------
# expert-parallel decode
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class EPDecodeSpec:
    """One expert-parallel decode deployment: the routed experts split
    evenly over ``nranks`` ranks (one per MPSoC), everything else
    data-parallel with ``tokens_per_rank`` decoding sequences per rank at
    ``context`` cached tokens each.  The step runs ``n_dense_layers``
    dense layers, then ``n_moe_layers`` MoE layers."""
    arch: str = "deepseek-v3-671b"
    nranks: int = 128
    tokens_per_rank: int = 16
    context: int = 4096
    n_dense_layers: int = 1
    n_moe_layers: int = 4
    #: weight and KV-cache bytes per element in the compute costs (FP8)
    dtype_bytes: int = 1
    #: dispatch payload per token: FP8 activations plus one float32
    #: scale per ``dispatch_scale_block`` elements
    dispatch_dtype_bytes: int = 1
    dispatch_scale_block: int = 128
    dispatch_scale_bytes: int = 4
    #: combine payload per token: BF16 expert outputs
    combine_dtype_bytes: int = 2
    #: the per-core A53 roofline of :class:`ServeSimSpec`, times the
    #: cores a rank owns
    core_rate_flops_per_us: float = ServeSimSpec.core_rate_flops_per_us
    mem_bw_bytes_per_us: float = ServeSimSpec.mem_bw_bytes_per_us
    cores_per_rank: int = 4


class EPDecodeSim:
    """Emit one expert-parallel decode step of an :class:`EPDecodeSpec` as
    a :class:`~repro.core.program.Program`, from router logits.

    Expert ``e`` lives on rank ``e // (n_experts // nranks)``, so the
    config's ``n_group`` routing groups are contiguous blocks of ranks
    (on the ExaNeSt rack with 128 ranks: one group per blade) and
    ``topk_group`` bounds the blocks a token's dispatch reaches.  Compute
    times come from :func:`repro.roofline.analysis.lm_serve_step_cost`
    over one-layer slices of the config, at the rank's roofline."""

    def __init__(self, spec: EPDecodeSpec, cfg=None):
        from repro.configs import get
        self.spec = spec
        self.cfg = cfg if cfg is not None else get(spec.arch)
        m = self.cfg.moe
        if m is None or m.n_experts % spec.nranks:
            raise ValueError(f"{self.cfg.name}: the routed experts do not "
                             f"split evenly over {spec.nranks} ranks")
        self.experts_per_rank = m.n_experts // spec.nranks
        d = self.cfg.d_model
        self.dispatch_bytes = d * spec.dispatch_dtype_bytes + \
            -(-d // spec.dispatch_scale_block) * spec.dispatch_scale_bytes
        self.combine_bytes = d * spec.combine_dtype_bytes

    # ------------------------------------------------------------- costing
    def _slice(self, **kw):
        return dataclasses.replace(self.cfg, n_layers=1, n_dense_layers=0,
                                   vocab_size=0, mtp_depth=0, **kw)

    def _us(self, cfg, n_tokens: float, context: float) -> float:
        """One slice's roofline time for ``n_tokens`` decode tokens on
        one rank."""
        from repro.roofline.analysis import lm_serve_step_cost
        sp = self.spec
        c = lm_serve_step_cost(cfg, n_decode=n_tokens, decode_kv=context,
                               dtype_bytes=sp.dtype_bytes)
        return max(
            c["flops"] / (sp.core_rate_flops_per_us * sp.cores_per_rank),
            c["hbm_bytes"] / (sp.mem_bw_bytes_per_us * sp.cores_per_rank))

    def dense_us(self) -> float:
        """A dense layer (attention and dense FFN) on one rank's tokens."""
        sp = self.spec
        return self._us(self._slice(family="dense", moe=None),
                        sp.tokens_per_rank, sp.context)

    def data_parallel_us(self) -> float:
        """An MoE layer's data-parallel part on one rank's tokens:
        attention, shared expert and router (the routed experts left
        out)."""
        sp = self.spec
        moe = dataclasses.replace(self.cfg.moe, d_expert=0)
        return self._us(self._slice(moe=moe), sp.tokens_per_rank,
                        sp.context)

    def expert_us(self, n_tokens: int) -> float:
        """One routed expert over ``n_tokens`` tokens (no work, no weight
        read, for none)."""
        if n_tokens <= 0:
            return 0.0
        cfg = self._slice(family="dense", moe=None, mla=None, n_heads=0,
                          n_kv_heads=0, d_ff=self.cfg.moe.d_expert)
        return self._us(cfg, n_tokens, 0.0)

    # ------------------------------------------------------------- routing
    def route(self, logits) -> np.ndarray:
        """Expert ids ``(layers, tokens, top_k)`` for router logits
        ``(layers, tokens, n_experts)`` (token ``t`` of rank ``r`` is row
        ``r * tokens_per_rank + t``), by the model's own router
        (:func:`repro.models.moe.route`), in float64 on the host."""
        import jax
        import jax.numpy as jnp
        from repro.models.moe import route
        logits = np.asarray(logits, dtype=np.float64)
        sp = self.spec
        want = (sp.n_moe_layers, sp.nranks * sp.tokens_per_rank,
                self.cfg.moe.n_experts)
        if logits.shape != want:
            raise ValueError(f"logits must have shape {want}, got "
                             f"{logits.shape}")
        with spans.span("serve.ep_route"), jax.enable_x64(True), \
                jax.default_device(jax.devices("cpu")[0]):
            return np.stack([np.asarray(route(jnp.asarray(lg), None,
                                              self.cfg.moe))
                             for lg in logits])

    def layer_traffic(self, ids) -> tuple:
        """``(tokens, load)`` of one MoE layer from its expert ids
        ``(tokens, top_k)``: ``tokens[s, d]`` counts rank ``s``'s tokens
        that pick an expert on rank ``d != s`` (a token that picks two
        experts on one rank goes there once; one on its own rank moves
        nothing), ``load[e]`` the tokens expert ``e`` computes."""
        sp = self.spec
        ids = np.asarray(ids)
        n_tok = ids.shape[0]
        hit = np.zeros((n_tok, sp.nranks), dtype=bool)
        hit[np.arange(n_tok)[:, None], ids // self.experts_per_rank] = True
        src = np.arange(n_tok) // sp.tokens_per_rank
        tokens = np.zeros((sp.nranks, sp.nranks), dtype=np.int64)
        np.add.at(tokens, src, hit.astype(np.int64))
        np.fill_diagonal(tokens, 0)
        load = np.bincount(ids.ravel(), minlength=self.cfg.moe.n_experts)
        return tokens, load

    def rank_expert_us(self, load) -> np.ndarray:
        """Each rank's routed-expert compute for the per-expert ``load``."""
        per = np.array([self.expert_us(int(n)) for n in load])
        return per.reshape(self.spec.nranks, self.experts_per_rank).sum(1)

    # ------------------------------------------------------------ emission
    def emit_step(self, logits) -> Program:
        """One decode step as a Program, from router logits ``(layers,
        tokens, n_experts)`` (see :meth:`route`).  Per rank ``r``::

            Compute(dense layer) x n_dense_layers
            for each MoE layer l:
                Compute(attention + shared expert + router)
                Irecv(s, n[s, r] * dispatch_bytes, tag 2l)  s = r-1, r-2, ...
                Isend(d, n[r, d] * dispatch_bytes, tag 2l)  d = r+1, r+2, ...
                Wait()
                Compute(r's experts over the tokens they received)
                Irecv(d, n[r, d] * combine_bytes, tag 2l+1)  d = r+1, ...
                Isend(s, n[s, r] * combine_bytes, tag 2l+1)  s = r-1, ...
                Wait()

        with ``n = layer_traffic(...)[0]``; pairs that exchange no token
        post nothing, ranks run modulo ``nranks``.  Messages are matched
        and fire in the order of the interpreter's scheduler
        (:class:`~repro.core.program.ProgramExecutor`: one op at a time,
        the rank with the smallest clock first, the lower rank on a tie;
        a transfer fires when its second side is posted), on the healthy
        machine; scenario columns replay that order."""
        ids = self.route(logits)
        with spans.span("serve.ep_emit"):
            sp = self.spec
            n = sp.nranks
            db, cb = self.dispatch_bytes, self.combine_bytes
            head = (Compute(self.dense_us()),) * sp.n_dense_layers
            dp = Compute(self.data_parallel_us())
            ranks = [list(head) for _ in range(n)]
            for layer, lid in enumerate(ids):
                tok, load = self.layer_traffic(lid)
                eus = self.rank_expert_us(load)
                td, tc = 2 * layer, 2 * layer + 1
                for r, ops in enumerate(ranks):
                    srcs = [s for s in ((r - i) % n for i in range(1, n))
                            if tok[s, r]]
                    dsts = [d for d in ((r + i) % n for i in range(1, n))
                            if tok[r, d]]
                    ops.append(dp)
                    ops += [Irecv(s, int(tok[s, r]) * db, td) for s in srcs]
                    ops += [Isend(d, int(tok[r, d]) * db, td) for d in dsts]
                    ops.append(Wait())
                    ops.append(Compute(float(eus[r])))
                    ops += [Irecv(d, int(tok[r, d]) * cb, tc) for d in dsts]
                    ops += [Isend(s, int(tok[s, r]) * cb, tc) for s in srcs]
                    ops.append(Wait())
            return Program(tuple(tuple(ops) for ops in ranks))

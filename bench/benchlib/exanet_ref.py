"""Plain reference of one bulk-synchronous CG iteration on the ExaNeSt rack.

A direct max-plus evaluation, written from the semantics alone and
importing nothing of the program under test: the machine's constants, the
application's constants and the fault draws all come from the
configuration file and the seed.

The machine (``config["machine"]``): A53 cores, four to an MPSoC, four
MPSoCs to a QFDB (a full crossbar of 16 Gb/s links), QFDBs on a 3-D torus
of 10 Gb/s links (X inside a blade, Y and Z across blades) entered and left
through each QFDB's MPSoC 0.  Routes are dimension-ordered X, Y, Z, each
ring the short way round (a tie goes +1).  Every MPSoC has a packetizer,
an R5 firmware core and a DMA wire; every link direction is one resource;
each serves one use at a time, in the order the uses are issued.

The iteration (``config["iteration"]``), on a balanced 3-D grid of ranks,
one rank per core:

1. every rank posts its face receives, then its face sends (each post
   costs ``a53_call_overhead_us`` on the poster's clock).  The ranks post
   in lockstep, so a face transfer is issued when its sender posts it:
   post by post, rank by rank.  A face is a rendez-vous transfer: the
   RTS/CTS handshake, one R5 invocation, then the stream through the
   source DMA, every link of the route and the destination DMA;
2. every rank waits for its faces, then computes;
3. ``n_dots`` allreduces of ``dot_bytes`` by recursive doubling: a copy
   in, log2(N) rounds of eager send-receive and a local reduction, a copy
   out and the barrier exit.

A fault set is a dict of undirected link keys ``(kind, lo, hi)``:
``slow`` (bandwidth divided by the factor), ``lossy`` (block-loss
probability p: bandwidth divided by 1/(1-p) more, the expected
retransmissions) and ``extra_us`` (one-way latency added per link).
"""

from __future__ import annotations

import numpy as np

INTRA, MEZZ = "intra_qfdb", "mezz"


def link_key(kind: str, a: int, b: int) -> tuple:
    return (kind, a, b) if a <= b else (kind, b, a)


class Machine:
    """Structure and routes of the rack described by ``m``."""

    def __init__(self, m: dict):
        self.m = m
        self.cpm = m["cores_per_mpsoc"]
        self.mpq = m["mpsocs_per_qfdb"]
        self.ring = tuple(m["torus"])                 # (X, Y, Z) QFDBs
        self.n_qfdbs = self.ring[0] * self.ring[1] * self.ring[2]
        self.n_cores = self.n_qfdbs * self.mpq * self.cpm
        self._routes: dict = {}

    def coords(self, q: int) -> tuple:
        X, Y, _ = self.ring
        return (q % X, (q // X) % Y, q // (X * Y))

    def qfdb(self, c) -> int:
        X, Y, _ = self.ring
        return c[0] + X * (c[1] + Y * c[2])

    def links(self) -> list:
        """Every physical link, undirected, sorted."""
        keys = set()
        for q in range(self.n_qfdbs):
            base = q * self.mpq
            for i in range(self.mpq):
                for j in range(i + 1, self.mpq):
                    keys.add(link_key(INTRA, base + i, base + j))
            c = self.coords(q)
            for dim in range(3):
                nxt = list(c)
                nxt[dim] = (c[dim] + 1) % self.ring[dim]
                other = self.qfdb(nxt) * self.mpq
                if other != base:
                    keys.add(link_key(MEZZ, base, other))
        return sorted(keys)

    def route(self, src: int, dst: int) -> tuple:
        """``(directed links, routers)`` from core ``src`` to ``dst``."""
        hit = self._routes.get((src, dst))
        if hit is None:
            hit = self._routes[(src, dst)] = self._route(src, dst)
        return hit

    def _route(self, src: int, dst: int) -> tuple:
        sm, dm = src // self.cpm, dst // self.cpm
        if sm == dm:
            return (), 0
        sq, dq = sm // self.mpq, dm // self.mpq
        if sq == dq:
            return ((INTRA, sm, dm),), 0
        links = []
        at = sm
        if at != sq * self.mpq:
            links.append((INTRA, at, sq * self.mpq))
            at = sq * self.mpq
        routers = 1
        cur, goal = list(self.coords(sq)), self.coords(dq)
        for dim in range(3):
            size = self.ring[dim]
            fwd = (goal[dim] - cur[dim]) % size
            step = 1 if fwd <= (cur[dim] - goal[dim]) % size else -1
            while cur[dim] != goal[dim]:
                cur[dim] = (cur[dim] + step) % size
                nxt = self.qfdb(cur) * self.mpq
                links.append((MEZZ, at, nxt))
                at = nxt
                routers += 1
        if at != dm:
            links.append((INTRA, at, dm))
        return tuple(links), routers


def sample_faults(rng, machine: Machine, f: dict, n: int) -> list:
    """``n`` fault sets: on each, ``n_slow_links`` hot links (a factor
    uniform in ``slow_factor``, plus ``extra_latency_us``) and
    ``n_lossy_links`` lossy ones (a loss uniform in ``loss_prob``), all
    distinct."""
    keys = machine.links()
    n_slow, n_lossy = f["n_slow_links"], f["n_lossy_links"]
    picks = np.argsort(rng.random((n, len(keys))), axis=1)[:, :n_slow
                                                           + n_lossy]
    factor = rng.uniform(*f["slow_factor"], size=(n, n_slow))
    loss = rng.uniform(*f["loss_prob"], size=(n, n_lossy))
    out = []
    for j in range(n):
        hot = [keys[i] for i in picks[j, :n_slow]]
        out.append({
            "slow": {k: float(v) for k, v in zip(hot, factor[j])},
            "extra_us": {k: float(f["extra_latency_us"]) for k in hot},
            "lossy": {keys[i]: float(p)
                      for i, p in zip(picks[j, n_slow:], loss[j])}})
    return out


def grid3(n: int) -> tuple:
    """The most cubic ``(px, py, pz)`` with ``px * py * pz == n``; among
    equals the first found with px, then py, smallest."""
    best, score = (n, 1, 1), float("inf")
    for px in range(1, n + 1):
        if n % px:
            continue
        for py in range(1, n // px + 1):
            if (n // px) % py:
                continue
            pz = n // px // py
            s = max(px, py, pz) / min(px, py, pz)
            if s < score:
                score, best = s, (px, py, pz)
    return best


def faces(r: int, grid: tuple) -> list:
    """``(neighbour, face)`` of rank ``r``'s periodic faces, face =
    2 * dim + (0 toward +, 1 toward -); a dimension of extent 1 has none."""
    px, py, pz = grid
    c = [r % px, (r // px) % py, r // (px * py)]
    out = []
    for dim in range(3):
        if grid[dim] == 1:
            continue
        for face, step in ((0, 1), (1, -1)):
            nb = list(c)
            nb[dim] = (c[dim] + step) % grid[dim]
            out.append((nb[0] + px * (nb[1] + py * nb[2]), 2 * dim + face))
    return out


class _Costs:
    """Per-route constants and resource clocks of one iteration under one
    fault set."""

    def __init__(self, machine: Machine, faults: dict):
        self.mach, self.m = machine, machine.m
        self.slow = dict(faults.get("slow", {}))
        for k, p in faults.get("lossy", {}).items():
            self.slow[k] = self.slow.get(k, 1.0) / (1.0 - p)
        self.extra = faults.get("extra_us", {})
        self.free: dict = {}
        self._paths: dict = {}

    def use(self, res, t: float, dur: float) -> float:
        """Start of a use of ``res`` asked for at ``t``; holds it ``dur``."""
        start = max(t, self.free.get(res, 0.0))
        self.free[res] = start + dur
        return start

    def path(self, src: int, dst: int) -> tuple:
        """``(hop_us, eager_us_per_byte, stream_us_per_byte, links)``."""
        hit = self._paths.get((src, dst))
        if hit is not None:
            return hit
        m = self.m
        links, routers = self.mach.route(src, dst)
        hop = routers * m["router_latency_us"] \
            + len(links) * m["link_latency_us"] \
            + sum(k == INTRA for k, _, _ in links) * \
            m["local_switch_latency_us"]
        per_byte, wire = 0.0, []
        for kind, a, b in links:
            key = link_key(kind, a, b)
            hop += self.extra.get(key, 0.0)
            s = self.slow.get(key, 1.0)
            rate = m["rate_gbps"][kind] / s
            per_byte += 8.0 / (rate * 1000.0)
            wire.append(m["wire_gbps"][kind] / s)
        bw = min(wire) if wire else m["axi_gbps"] * (
            m["wire_gbps"][INTRA] / m["rate_gbps"][INTRA])
        block = m["rdma_block_bytes"] * 8.0
        rdma = block / (block / (bw * 1000.0) + m["rdma_block_gap_us"]) \
            / 1000.0
        hit = self._paths[(src, dst)] = (hop, per_byte, 8.0 / (rdma * 1000.0),
                                         links)
        return hit

    def rendezvous(self, src: int, dst: int, nbytes: int, t: float,
                   base_us: float) -> float:
        """Arrival of a rendez-vous transfer issued at ``t``."""
        m = self.m
        hop, _, per_byte, links = self.path(src, dst)
        sm, dm = src // self.mach.cpm, dst // self.mach.cpm
        t = t + 2.0 * (base_us + hop)                       # RTS + CTS
        t = self.use(("r5", sm), t, m["r5_occupancy_us"]) + \
            m["rdma_startup_us"]
        dur = nbytes * per_byte
        t = self.use(("dma", sm), t, dur)
        end = t + dur
        for link in links:
            t = self.use(link, t, dur)
            end = t + dur
        if dm != sm:
            end = self.use(("dma", dm), t, dur) + dur
        return end + hop

    def eager(self, src: int, dst: int, nbytes: int, t: float,
              base_us: float) -> tuple:
        """``(arrival, sender free)`` of an eager message issued at ``t``."""
        m = self.m
        hop, per_byte, _, _ = self.path(src, dst)
        depart = self.use(("pktz", src // self.mach.cpm), t,
                          m["pktz_occupancy_us"])
        return (depart + base_us + hop + nbytes * per_byte,
                depart + m["pktz_occupancy_us"] + m["a53_call_overhead_us"])


def _allreduce(costs: _Costs, enters: list, nbytes: int) -> list:
    """Recursive doubling (send-receive latency model) from per-rank entry
    clocks; returns per-rank exit clocks."""
    m = costs.m
    n = len(enters)
    copy = nbytes / m["a53_copy_bw_bytes_per_us"] + m["a53_call_overhead_us"]
    reduce = 3.0 * nbytes / m["a53_copy_bw_bytes_per_us"] + \
        m["a53_call_overhead_us"]
    clocks = [t + copy for t in enters]
    d = 1
    while d < n:
        arrive, free = [0.0] * n, [0.0] * n
        for r in range(n):
            a, f = costs.eager(r, r ^ d, nbytes, clocks[r],
                               m["sw_pingpong_base_us"])
            arrive[r ^ d] = max(arrive[r ^ d], a)
            free[r] = max(free[r], f)
        clocks = [max(free[r], arrive[r]) + m["sendrecv_sw_eager_us"]
                  + reduce for r in range(n)]
        d *= 2
    return [c + copy + m["barrier_exit_us"] for c in clocks]


def iteration(machine: Machine, it: dict, faults: dict) -> tuple:
    """``(latency_us, per-rank clocks)`` of one iteration of ``it`` on
    ``machine`` under ``faults``."""
    m = machine.m
    n = it["ranks"]
    if n & (n - 1) or n > machine.n_cores:
        raise ValueError(f"{n} ranks: a power of two up to "
                         f"{machine.n_cores} is needed")
    side = it["points_per_rank"] ** (1.0 / 3.0)
    face_bytes = max(1, int(side * side * it["halo_bytes_per_point"]))
    if face_bytes <= m["eager_max_bytes"]:
        raise ValueError("eager faces are outside this reference")
    comp = it["points_per_rank"] * it["flops_per_point"] / \
        it["core_flops_per_us"] * it["memory_contention"]
    grid = grid3(n)
    nbrs = [faces(r, grid) for r in range(n)]
    costs = _Costs(machine, faults)

    # post times: receives first, then sends, one overhead each
    posts = [0.0]
    for _ in range(2 * len(nbrs[0])):
        posts.append(posts[-1] + m["a53_call_overhead_us"])
    n_faces = len(nbrs[0])
    done = [posts[-1]] * n
    for k in range(n_faces):
        for r in range(n):
            nb, face = nbrs[r][k]
            # the receiver posted this face's receive at its own face^1
            t_recv = posts[[f for _, f in nbrs[nb]].index(face ^ 1)]
            t = costs.rendezvous(r, nb, face_bytes,
                                 max(posts[n_faces + k], t_recv),
                                 m["sw_oneway_base_us"])
            done[r] = max(done[r], t)
            done[nb] = max(done[nb], t)
    clocks = [t + comp for t in done]
    for _ in range(it["n_dots"]):
        clocks = _allreduce(costs, clocks, it["dot_bytes"])
    return max(clocks), clocks

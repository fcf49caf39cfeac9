"""Plain reference of the warp-size timing model, independent of ``src/``.

What a record of the study engine has to equal, bit for bit: one
(benchmark, machine, seed) cell simulated by the straightforward
algorithm of the paper's model, written once here and never imported
from the program under test.

1. The benchmark's kernel program (Table 2 of arXiv:1205.4967, as the
   configuration files name them) is walked over the whole thread pool
   with a reconvergence stack: branch outcomes and addresses are drawn
   from ``numpy.random.default_rng(seed)`` in walk order.
2. Each executed statement becomes one macro-op per active warp
   (SIMT: full-warp issue slots; LW+ MIMD: slots for active threads
   only, coalescing within never-reconverging fragments).
3. A discrete-event loop schedules the warps: one issue port per SM, a
   set-associative LRU L1 with pending fills, bandwidth-server memory
   controllers, and for SW+ an SM-wide table of outstanding reads.

Times are IEEE-754 doubles. ``precision="float32"`` rounds every time
value to single precision instead: the control, the nearest precision
below the one the configurations state, which has to come out as not
correct.
"""

from __future__ import annotations

import dataclasses
import heapq
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

KIND_COMPUTE, KIND_LOAD, KIND_STORE = 0, 1, 2
WORD = 4                    # bytes per thread access
REGION_BITS = 28            # address bits of one statement region

RESULT_FIELDS = ("name", "machine", "cycles", "thread_insns", "mem_insns",
                 "offchip_requests", "merged_requests", "l1_hits",
                 "idle_cycles", "busy_cycles", "simd_eff")


# ---------------------------------------------------------------------------
# Kernel programs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Compute:
    n: int


@dataclasses.dataclass(frozen=True)
class Mem:
    pattern: str = "coalesced"
    is_load: bool = True
    stride: int = 4
    working_set: int = 1 << 20
    irregularity: float = 0.0
    region: Optional[str] = None
    offset: int = 0


@dataclasses.dataclass(frozen=True)
class Branch:
    p_taken: float
    corr: float
    then: Sequence = ()
    orelse: Sequence = ()


@dataclasses.dataclass(frozen=True)
class Loop:
    trips: int
    body: Sequence = ()


def _st(**kw):
    return Mem("strided", **kw)


def _rnd(**kw):
    return Mem("random", **kw)


PROGRAMS: Dict[str, list] = {
    "BFS": [
        Mem(),
        Branch(0.45, 0.90, then=[
            Compute(8),
            _rnd(region="bfs_edges", working_set=1 << 19),
            Loop(2, [
                _rnd(region="bfs_nodes", working_set=1 << 18),
                Compute(3),
                Branch(0.5, 0.85,
                       then=[_rnd(is_load=False, working_set=1 << 18),
                             Compute(4)],
                       orelse=[Compute(1)]),
            ]),
        ], orelse=[Compute(1)]),
        Compute(2),
    ],
    "BKP": [Loop(6, [Mem(), Mem(working_set=1024), Compute(6),
                     _st(stride=8), Compute(4), Mem(is_load=False)])],
    "DYN": [Loop(8, [Mem("broadcast"), Compute(24),
                     Mem(region="dyn_tab", working_set=1 << 14),
                     Compute(16)])],
    "FWAL": [Loop(7, [Mem(region="fwal_buf", working_set=1 << 15),
                      Compute(10),
                      Mem(region="fwal_buf", working_set=1 << 15,
                          is_load=False)])],
    "GAS": [Loop(5, [Mem(working_set=512), _st(stride=16), Mem(),
                     Compute(5), Mem(is_load=False)])],
    "HSPT": [Loop(4, [
        Mem(region="hspt_grid", working_set=1 << 20, irregularity=0.15),
        Mem(region="hspt_grid", working_set=1 << 20, irregularity=0.15,
            offset=-64),
        Compute(14),
        Branch(0.12, 0.96, then=[Compute(3)]),
        Mem(is_load=False)])],
    "MP": [Loop(6, [
        _rnd(region="mp_tree", working_set=1 << 15),
        Compute(16),
        Branch(0.5, 0.80,
               then=[Compute(12),
                     _rnd(region="mp_tree", working_set=1 << 15)],
               orelse=[Compute(5),
                       Branch(0.5, 0.80, then=[Compute(10)],
                              orelse=[Compute(3)])])])],
    "MTM": [Loop(6, [Mem(), _st(stride=64), Compute(8)]),
            _st(stride=128, is_load=False),
            _st(stride=128, is_load=False)],
    "MU": [Loop(5, [
        _rnd(region="mu_tree", working_set=1 << 15),
        Compute(16),
        Branch(0.45, 0.80,
               then=[Compute(14),
                     _rnd(region="mu_tree", working_set=1 << 15)],
               orelse=[Compute(5)])])],
    "NNC": [Loop(5, [Mem(irregularity=0.1), Compute(6),
                     Branch(0.3, 0.86, then=[Compute(4)])])],
    "NQU": [Loop(8, [
        Compute(6),
        Branch(0.5, 0.75,
               then=[Compute(10),
                     Branch(0.5, 0.75, then=[Compute(8)],
                            orelse=[Compute(2)])],
               orelse=[Compute(2)]),
        Mem("broadcast")])],
    "NW": [Loop(5, [_st(stride=8), Mem(working_set=1024), Compute(8),
                    Branch(0.2, 0.92, then=[Compute(3)]),
                    Mem(is_load=False)])],
    "SCN": [Loop(4, [Branch(0.55, 0.88,
                            then=[_st(region="scn_buf", stride=8),
                                  Compute(5),
                                  _st(region="scn_buf", stride=8,
                                      is_load=False)],
                            orelse=[Compute(1)])])],
    "SR1": [Loop(5, [
        Mem(region="sr1_img", working_set=1 << 21),
        Mem(region="sr1_img", working_set=1 << 21, offset=-64),
        Mem(region="sr1_img", working_set=1 << 21, offset=64),
        Mem(working_set=512), Compute(9), Mem(is_load=False)])],
    "SR2": [Loop(4, [
        Mem(region="sr2_img", working_set=1 << 17),
        Mem(region="sr2_img", working_set=1 << 17, offset=64),
        Mem(working_set=512), Compute(9), Mem(is_load=False)])],
}


# ---------------------------------------------------------------------------
# Workload walk: per-warp macro-op lists
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Op:
    issue: int
    tins: int
    kind: int
    maccs: int = 0
    blocks: Tuple[int, ...] = ()
    nbytes: Tuple[int, ...] = ()


def expansion_key(m: dict) -> tuple:
    """The machine fields the walk reads; machines sharing it share ops."""
    return (m["warp_size"], m["simd_width"], bool(m["mimd"]),
            m["transaction_bytes"])


def branch_outcomes(rng, n: int, p: float, corr: float) -> np.ndarray:
    """Bernoulli(p) per thread, constant over runs of neighbour threads
    whose length is geometric with mean 1/(1-corr)."""
    corr = min(max(corr, 0.0), 0.995)
    new_run = rng.random(n) < (1.0 - corr)
    new_run[0] = True
    run_id = np.cumsum(new_run) - 1
    draws = rng.random(int(run_id[-1]) + 1) < p
    return draws[run_id]


def addresses(stmt: Mem, uid: int, n: int, rng) -> np.ndarray:
    """Byte address of every thread for one executed memory statement."""
    if stmt.region is not None:
        region = zlib.crc32(stmt.region.encode()) % (1 << 20)
    else:
        region = (1 << 20) + uid
    base = np.int64(region) << REGION_BITS
    tid = np.arange(n, dtype=np.int64)
    ws = max(int(stmt.working_set), WORD * n)
    if stmt.pattern == "coalesced":
        off = tid * WORD
    elif stmt.pattern == "strided":
        off = tid * np.int64(stmt.stride)
    elif stmt.pattern == "random":
        off = rng.integers(0, ws, n, dtype=np.int64)
    elif stmt.pattern == "broadcast":
        off = np.zeros(n, dtype=np.int64)
    else:
        raise ValueError(f"unknown pattern {stmt.pattern!r}")
    off = (off + np.int64(stmt.offset)) % ws
    if stmt.irregularity > 0.0:
        irr = rng.random(n) < stmt.irregularity
        off = np.where(irr, rng.integers(0, ws, n, dtype=np.int64), off)
    return base + off


def warp_ops(bench: str, n_threads: int, seed: int,
             m: dict) -> List[List[Op]]:
    """Per-warp macro-op lists of one workload on machine fields `m`."""
    n = n_threads
    ws, simd, mimd, tb = expansion_key(m)
    if n % ws:
        raise ValueError(f"n_threads {n} not a multiple of warp size {ws}")
    n_warps = n // ws
    slots = max(1, ws // simd)          # issue cycles of one full warp
    warp_of = np.arange(n) // ws
    rng = np.random.default_rng(seed)
    frag = np.zeros(n, dtype=np.int64)
    ops: List[List[Op]] = [[] for _ in range(n_warps)]
    uid = 0

    def active(mask):
        counts = np.bincount(warp_of[mask], minlength=n_warps)
        return [(int(w), int(counts[w])) for w in np.nonzero(counts)[0]]

    def issue_of(a: int) -> int:
        return -(-a // simd) if mimd else slots

    def compute(mask, count: int) -> None:
        for w, a in active(mask):
            ops[w].append(Op(count * issue_of(a), count * a, KIND_COMPUTE))

    def mem(mask, stmt: Mem) -> None:
        nonlocal uid
        uid += 1
        addr = addresses(stmt, uid, n, rng)
        tid = np.nonzero(mask)[0]
        w_t = warp_of[tid]
        f_t = frag[tid] if mimd else np.zeros(len(tid), dtype=np.int64)
        b_t = addr[tid] // tb
        order = np.lexsort((b_t, f_t, w_t))
        w_t, f_t, b_t = w_t[order], f_t[order], b_t[order]
        # One transaction per distinct (warp, fragment, block).
        new = np.ones(len(b_t), dtype=bool)
        new[1:] = (w_t[1:] != w_t[:-1]) | (f_t[1:] != f_t[:-1]) | \
            (b_t[1:] != b_t[:-1])
        starts = np.nonzero(new)[0]
        counts = np.diff(np.append(starts, len(b_t)))
        txn_w = w_t[starts].tolist()
        txn_b = b_t[starts].tolist()
        txn_n = np.minimum(counts * WORD, tb).tolist()
        per_warp: Dict[int, Tuple[list, list]] = {}
        for w, b, nb in zip(txn_w, txn_b, txn_n):
            blk, byt = per_warp.setdefault(w, ([], []))
            blk.append(b)
            byt.append(nb)
        kind = KIND_LOAD if stmt.is_load else KIND_STORE
        for w, a in active(mask):
            blk, byt = per_warp[w]
            ops[w].append(Op(issue_of(a), a, kind, a, tuple(blk),
                             tuple(byt)))

    def walk(stmts, mask) -> None:
        if not mask.any():
            return
        for s in stmts:
            if isinstance(s, Compute):
                compute(mask, s.n)
            elif isinstance(s, Mem):
                mem(mask, s)
            elif isinstance(s, Loop):
                for _ in range(s.trips):
                    walk(s.body, mask)
                    if mimd:
                        frag[mask] = 0      # LW+ re-forms warps per trip
            elif isinstance(s, Branch):
                compute(mask, 1)            # the branch instruction
                taken = branch_outcomes(rng, n, s.p_taken, s.corr)
                if mimd:
                    # Fragments never reconverge; at most 4 per warp.
                    per_warp = np.sort(frag.reshape(n_warps, ws), axis=1)
                    n_frag = 1 + (per_warp[:, 1:] != per_warp[:, :-1]).sum(1)
                    split = mask & (n_frag < 4)[warp_of]
                    frag[split] = frag[split] * 2 + taken[split]
                walk(s.then, mask & taken)
                walk(s.orelse, mask & ~taken)
            else:
                raise TypeError(f"unknown statement {s!r}")

    walk(PROGRAMS[bench], np.ones(n, dtype=bool))
    return ops


# ---------------------------------------------------------------------------
# Timing: discrete-event loop
# ---------------------------------------------------------------------------


def _f32(x: float) -> float:
    return float(np.float32(x))


def dram_svc(m: dict) -> float:
    """Core cycles one 64 B transaction holds a memory controller: the
    paper's 76.8 GB/s for 16 SMs, shared in proportion to the SMs run."""
    bw = m["dram_bw_gbps"] * (m["num_sms"] / 16.0)
    per_ctrl = bw * 1e9 / m["num_mem_ctrls"]
    secs = m["transaction_bytes"] / per_ctrl
    return secs * m["core_clock_ghz"] * 1e9


def simulate(name: str, ops: List[List[Op]], m: dict,
             precision: str = "float64") -> dict:
    """Schedule the warps of `ops` on machine `m`; one result record."""
    if precision == "float64":
        r = float
    elif precision == "float32":
        r = _f32
    else:
        raise ValueError(f"unknown precision {precision!r}")
    n_sms = m["num_sms"]
    n_ctrl = m["num_mem_ctrls"]
    n_sets = m["l1_size_bytes"] // (m["transaction_bytes"] * m["l1_ways"])
    ways = m["l1_ways"]
    ideal = bool(m["ideal_coalescing"])
    hit_lat = m["l1_hit_latency"]
    depth = m["pipeline_depth"]
    lat = r(float(m["dram_latency_cycles"]))
    svc = r(dram_svc(m))

    n_warps = len(ops)
    sm_of = [min(w * n_sms // max(n_warps, 1), n_sms - 1)
             for w in range(n_warps)]
    issue_free = [0.0] * n_sms
    ctrl_free = [0.0] * n_ctrl
    l1 = [dict() for _ in range(n_sms)]     # set -> {block: [tick, fill]}
    tick = [0] * n_sms
    outstanding = [dict() for _ in range(n_sms)]
    offchip = merged = hits = 0

    def dram(block: int, now: float, cost: float) -> float:
        c = block % n_ctrl
        start = max(ctrl_free[c], now)
        ctrl_free[c] = r(start + cost)
        return r(r(start + lat) + cost)

    heap = [(0.0, w) for w in range(n_warps) if ops[w]]
    heapq.heapify(heap)
    nxt = [0] * n_warps
    while heap:
        ready, w = heapq.heappop(heap)
        sm = sm_of[w]
        op = ops[w][nxt[w]]
        nxt[w] += 1
        t_start = max(ready, issue_free[sm])
        t_acc = r(t_start + op.issue)
        issue_free[sm] = t_acc
        if op.kind == KIND_COMPUTE:
            warp_ready = r(t_acc + depth)
        elif op.kind == KIND_STORE:
            # Fire and forget; a partial store still takes a 32 B burst.
            for block, nb in zip(op.blocks, op.nbytes):
                dram(block, t_acc, r(svc * (max(nb, 32) / 64.0)))
                offchip += 1
            warp_ready = r(t_acc + hit_lat)
        else:
            done = r(t_acc + hit_lat)
            sets = l1[sm]
            for block in op.blocks:
                tick[sm] += 1
                line = sets.setdefault(block % n_sets, {})
                ent = line.get(block)
                if ent is not None:
                    ent[0] = tick[sm]
                    if ent[1] <= t_acc:
                        hits += 1
                        continue
                if ideal:
                    out = outstanding[sm].get(block)
                    if out is not None and out > t_acc:
                        merged += 1
                        done = max(done, out)
                        continue
                completion = dram(block, t_acc, svc)
                offchip += 1
                tick[sm] += 1
                if ent is not None:
                    ent[0] = tick[sm]
                    ent[1] = min(ent[1], completion)
                else:
                    if len(line) >= ways:
                        del line[min(line, key=lambda b: line[b][0])]
                    line[block] = [tick[sm], completion]
                if ideal:
                    outstanding[sm][block] = completion
                done = max(done, completion)
            warp_ready = done
        if nxt[w] < len(ops[w]):
            heapq.heappush(heap, (warp_ready, w))

    thread_insns = sum(op.tins for warp in ops for op in warp)
    mem_insns = sum(op.maccs for warp in ops for op in warp)
    issued = sum(op.issue for warp in ops for op in warp)
    lane_slots = issued * m["simd_width"]
    cycles = max(max(issue_free), 1.0)
    busy = r(float(issued))
    return {
        "name": name,
        "machine": m["name"],
        "cycles": cycles,
        "thread_insns": thread_insns,
        "mem_insns": mem_insns,
        "offchip_requests": offchip,
        "merged_requests": merged,
        "l1_hits": hits,
        "idle_cycles": r(r(r(n_sms * cycles) - busy) / n_sms),
        "busy_cycles": r(busy / n_sms),
        "simd_eff": r(r(float(thread_insns)) / r(float(max(lane_slots, 1)))),
    }


class Reference:
    """Cells of one configuration, with the walk shared per expansion key."""

    def __init__(self, n_threads: Dict[str, int], precision: str = "float64"):
        self.n_threads = dict(n_threads)
        self.precision = precision
        self._ops: Dict[tuple, List[List[Op]]] = {}

    def ops(self, bench: str, seed: int, m: dict) -> List[List[Op]]:
        key = (bench, seed, expansion_key(m))
        got = self._ops.get(key)
        if got is None:
            if len(self._ops) >= 64:
                self._ops.pop(next(iter(self._ops)))
            got = self._ops[key] = warp_ops(bench, self.n_threads[bench],
                                            seed, m)
        return got

    def cell(self, bench: str, seed: int, m: dict) -> dict:
        return simulate(bench, self.ops(bench, seed, m), m, self.precision)

    def n_ops(self, bench: str, seed: int, m: dict) -> int:
        """Macro-ops the engine schedules for this cell (its work)."""
        return sum(len(w) for w in self.ops(bench, seed, m))

"""The one traffic generator: studies and cell reads from a mix's data.

Everything is drawn from ``--seed``: the same seed gives the same
studies in the same order and the same reads per client; another seed
gives other workload seeds and other reads, of the same sizes.
"""

from __future__ import annotations

import bisect
import dataclasses
import hashlib
import random
from typing import Iterator, List, Sequence, Tuple

from chipbench import grid

SEED_SPACE = 1 << 31


def derive(seed: int, *parts) -> int:
    """A workload seed in [0, 2**31) from the run seed and a label."""
    blob = repr((int(seed),) + tuple(parts)).encode()
    return int.from_bytes(hashlib.sha256(blob).digest()[:8], "big") % \
        SEED_SPACE


@dataclasses.dataclass(frozen=True)
class StudySpec:
    """One request: benchmarks x machines x workload seeds."""

    index: int
    benches: Tuple[str, ...]
    seeds: Tuple[int, ...]
    machines: Tuple[str, ...]

    def cells(self) -> List[Tuple[str, str, int]]:
        """(machine, bench, seed) in the study engine's cell order:
        machines-major, benches, seeds innermost."""
        return [(m, b, s) for m in self.machines for b in self.benches
                for s in self.seeds]


class StudyPlan:
    """The studies of a run, in issue order: one benchmark each, the
    benchmarks in turn, at workload seeds that no other study of the run
    (warm-up included) uses.

    A mix may list ``workload_seeds`` per benchmark: seeds whose work has
    the same sizes. The run seed orders each benchmark's list; the
    warm-up takes its first seeds and the window the rest, so every run
    does work of the same sizes. Past the end of a list, and for a mix
    without one, seeds are derived afresh from the run seed.
    """

    def __init__(self, traffic: dict, benches: Sequence[str],
                 machines: Sequence[str], seed: int):
        self.benches = list(benches)
        self.machines = tuple(machines)
        self.seed = seed
        pools = traffic.get("workload_seeds") or {}
        self._pool = {}
        for b in self.benches:
            pool = list(pools.get(b, ()))
            random.Random(derive(seed, "pool", b)).shuffle(pool)
            self._pool[b] = pool
        self._used = {s for p in self._pool.values() for s in p}
        self._taken = dict.fromkeys(self.benches, 0)
        # One study per benchmark warms every shape the window uses.
        self.warmup = [self._spec(i, i) for i in range(len(self.benches))]
        self._window: List[StudySpec] = []

    @classmethod
    def for_config(cls, traffic: dict, cfg: dict, seed: int) -> "StudyPlan":
        return cls(traffic, grid.benches(cfg), grid.machines(cfg), seed)

    def _next_seed(self, bench: str) -> int:
        k = self._taken[bench]
        self._taken[bench] += 1
        pool = self._pool[bench]
        if k < len(pool):
            return pool[k]
        j = 0
        while True:
            s = derive(self.seed, "fresh", bench, k, j)
            if s not in self._used:
                self._used.add(s)
                return s
            j += 1

    def _spec(self, index: int, bench_pos: int) -> StudySpec:
        bench = self.benches[bench_pos]
        return StudySpec(index, (bench,), (self._next_seed(bench),),
                         self.machines)

    def study(self, i: int) -> StudySpec:
        """The window's study `i` (0-based, in issue order)."""
        while len(self._window) <= i:
            j = len(self._window)
            self._window.append(self._spec(j, j % len(self.benches)))
        return self._window[i]


def fill_cells(traffic: dict, cfg: dict) -> List[Tuple[str, str, int]]:
    """The finished cells a read mix reads: every machine x benchmark at
    workload seeds 0 .. ``fill_seeds`` - 1, the same in every run; the
    run seed decides which of them are hot."""
    return [(m, b, s) for s in range(int(traffic["fill_seeds"]))
            for m in grid.machines(cfg) for b in grid.benches(cfg)]


class Zipf:
    """Ranks 0..n-1 with P(rank r) proportional to 1 / (r + 1) ** theta."""

    def __init__(self, n: int, theta: float):
        acc = 0.0
        self.cdf = []
        for r in range(n):
            acc += 1.0 / (r + 1) ** theta
            self.cdf.append(acc)
        self.total = acc

    def draw(self, rng: random.Random) -> int:
        return min(bisect.bisect_left(self.cdf, rng.random() * self.total),
                   len(self.cdf) - 1)


def read_stream(traffic: dict, n_cells: int, seed: int, client: int
                ) -> Iterator[int]:
    """The endless stream of cell indices of one client, Zipf over the
    cells. Popularity rank r maps to a cell by a permutation drawn from
    the seed, so which cells are hot changes with the seed and how hot
    does not."""
    perm = list(range(n_cells))
    random.Random(derive(seed, "rank")).shuffle(perm)
    zipf = Zipf(n_cells, float(traffic["zipf_constant"]))
    rng = random.Random(derive(seed, "client", client))
    while True:
        yield perm[zipf.draw(rng)]


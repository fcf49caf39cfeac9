"""Parallel warp-size sweep engine with content-addressed result caching.

The paper's argument rests on dense sweeps of warp size × machine variant ×
benchmark grids (Figs. 1–7). This module turns those grids into first-class
objects:

* :class:`SweepSpec` — a declarative grid (benches × machines × seeds,
  optional warp-size range 4–128) that enumerates its cells in a fixed,
  deterministic order.
* :class:`ResultCache` — a content-addressed on-disk cache. Keys are SHA-256
  digests over ``(model version, bench, canonical MachineConfig dict,
  n_threads, seed)``, so *any* change to any machine parameter — or to the
  simulation model itself via :data:`MODEL_VERSION` — produces a different
  key. Corrupt or stale cache files are treated as misses and removed.
* :func:`run_sweep` — executes the uncached cells, process-parallel via
  ``concurrent.futures.ProcessPoolExecutor``, and returns results in the
  spec's deterministic order regardless of completion order.

Cold-path scheduling is a *two-level sharing hierarchy*:

* **Shared thread traces** — expansion phase 1
  (:func:`~repro.core.warpsim.divergence.build_thread_trace`) depends on
  *no* machine field at all, so uncached cells are first bucketed into
  families by ``(bench, n_threads, seed)``; each family is one unit of
  worker work that builds (or fetches from :data:`TRACE_CACHE`, a bounded
  LRU with optional on-disk persistence next to the result cells) the
  :class:`~repro.core.warpsim.trace.ThreadTrace` once.
* **Shared expansions** — phase 2 aggregation
  (:func:`~repro.core.warpsim.divergence.aggregate_stream`) depends only
  on the four machine fields in :func:`expansion_key` (warp size, SIMD
  width, MIMD flag, transaction bytes), so cells inside one family are
  sub-bucketed by expansion key: the worker aggregates the family's trace
  once per key and simulates every machine variant sharing the resulting
  :class:`WarpStream` (the paper suite shares ws8's stream with SW+, so a
  6-machine × 15-bench grid needs 15 trace builds + 75 aggregations
  instead of 90 full expansions). Aggregated streams additionally flow
  through a small per-process LRU (:data:`EXPANSION_CACHE`), so repeated
  *serial* sweeps in one process — figure generation on small hosts,
  long-lived sweep servers — skip re-aggregation entirely without
  unbounded memory growth. (Parallel sweeps tear their worker pool down
  per call; workers inherit the parent's caches on fork-start platforms
  but their own fills are not carried back.)

Usage (see ``examples/warpsize_study.py``)::

    from repro.core.warpsim import sweep, machines

    spec = sweep.SweepSpec(machines=machines.paper_suite())
    grid = sweep.run_sweep(spec, cache=sweep.ResultCache("/tmp/warpsim"))
    grid["SW+"]["BFS"].ipc          # results[machine][bench] -> SimResult

    # Dense warp-size scaling study, 4..128 threads/warp:
    spec = sweep.SweepSpec.warp_size_range()
    grid = sweep.run_sweep(spec)

Simulation results are bit-deterministic across processes (workload
expansion draws everything from the workload seed and stable hashes), so a
cache entry computed by any worker — or any earlier run — is exact.
:func:`run_sweep_with_stats` returns each run's private cell/cache/grouping
counter snapshot (surfaced by ``benchmarks/sweep_bench.py``); the old
``LAST_SWEEP_STATS`` global survives only as a deprecated alias behind a
DeprecationWarning.

This module is the low-level engine; ``repro.core.warpsim.api`` is the
facade over it (typed ``Study``/``StudyResult``, pluggable backends,
session-owned cache stack).
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import hashlib
import itertools
import json
import os
import sys
import tempfile
import threading
import warnings
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.warpsim import _native, _pallas
from repro.core.warpsim import machines as machines_mod
from repro.core.warpsim import obs as obs_mod
from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim.divergence import (
    WarpStream, aggregate_stream, build_thread_trace, expand_stream,
    expand_stream_single,
)
from repro.core.warpsim.timing import (
    SimResult, loop_result, simulate, stream_totals,
)
from repro.core.warpsim.trace import (
    BENCHMARKS, ThreadTrace, Workload, get_workload,
)

# Bump whenever the simulation model changes observable numbers: it is part
# of every cache key, so stale entries from older models can never be
# returned as current results.
MODEL_VERSION = "warpsim-2"

# SimResult fields persisted in cache entries (derived properties such as
# ipc / coalescing_rate are recomputed, never stored).
_RESULT_FIELDS = tuple(f.name for f in dataclasses.fields(SimResult))


# ---------------------------------------------------------------------------
# Cache keys
# ---------------------------------------------------------------------------


def machine_key(cfg: MachineConfig) -> str:
    """Stable content hash of a machine configuration.

    Every field participates, so changing any parameter (warp size, DRAM
    latency, L1 geometry, idealization flags, even the display name) yields
    a different key.
    """
    blob = json.dumps(dataclasses.asdict(cfg), sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def expansion_key(cfg: MachineConfig) -> tuple:
    """The machine fields that determine ``expand_stream`` output.

    Cells whose machines collide on this key (and share bench, thread
    count and seed) share one expanded :class:`WarpStream`; see
    :meth:`MachineConfig.expansion_key`. The collision⇔identical-stream
    property is locked by ``tests/test_golden.py``.
    """
    return cfg.expansion_key()


@functools.lru_cache(maxsize=None)
def _default_n_threads(bench: str) -> int:
    return get_workload(bench).n_threads


@functools.lru_cache(maxsize=256)
def _machine_dict(cfg: MachineConfig) -> dict:
    """Memoized ``dataclasses.asdict`` (MachineConfig is frozen/hashable;
    one grid keys the same few configs hundreds of times)."""
    return dataclasses.asdict(cfg)


def cell_key(bench: str, cfg: MachineConfig, n_threads: Optional[int],
             seed: int) -> str:
    """Content-addressed key for one (bench, machine, n_threads, seed) cell.

    The blob encoding is part of the on-disk contract: existing caches
    (including PR 1's sharded layout) stay valid, so changes here require
    a MODEL_VERSION bump.
    """
    if n_threads is None:
        # Canonicalize: a cell run with the bench's default thread count is
        # the same cell as one requesting that count explicitly.
        n_threads = _default_n_threads(bench.upper())
    blob = json.dumps({
        "model": MODEL_VERSION,
        "bench": bench.upper(),
        "machine": _machine_dict(cfg),
        "n_threads": n_threads,
        "seed": seed,
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


class ResultCache:
    """Content-addressed on-disk store of :class:`SimResult` cells.

    One JSON file per key, flat under `root` (cell files are only ever
    opened by exact name, so sharded subdirectories bought nothing but
    per-shard ``mkdir``/``stat`` traffic on cold sweeps). Reads that fail
    for any reason (truncated write, garbage contents, missing or extra
    fields, schema drift) count as misses and the offending file is
    quarantined under a ``.corrupt`` suffix (counted in
    :attr:`corrupt`), so a corrupt cache degrades to a cold one instead
    of poisoning sweeps — and the bad bytes survive for post-mortem.

    Existence is answered from a one-time directory listing (plus this
    instance's own writes): a cold 90-cell sweep costs one ``scandir``
    instead of 90 failed ``open`` calls. The listing is *positive-only*:
    an index miss falls back to one direct existence probe, and a cell
    written by another process/worker after this instance's first scan is
    adopted into the index on first touch — a long-lived process (the
    sweep service, a work-queue worker) therefore sees every peer's writes
    instead of permanently re-simulating them. :meth:`refresh` re-scans
    the directory wholesale (the service's ``/stats`` endpoint uses it to
    report live entry counts).

    Instances are thread-safe: the index and counters are guarded by a
    lock, and concurrent ``put`` calls for one key race benignly (results
    are deterministic, so last-writer-wins is byte-identical).
    """

    def __init__(self, root: str):
        self.root = root
        self.hits = 0
        self.misses = 0
        self.adopted = 0          # index misses rescued by a direct probe
        self.corrupt = 0          # unreadable entries quarantined on read
        self._listing: Optional[set] = None
        self._legacy: Dict[str, str] = {}
        self._root_ok = False
        self._lock = threading.RLock()

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + ".json")

    def _index(self) -> set:
        if self._listing is None:
            try:
                self._listing = set(os.listdir(self.root))
                self._root_ok = True
            except OSError:
                self._listing = set()
            # Older caches sharded cells under two-hex-char subdirectories;
            # those entries stay readable (keys are unchanged) — new writes
            # always land flat. Flat cell names are 64 hex chars + .json,
            # so the isdir probe only ever fires on legacy shard dirs.
            for entry in [e for e in self._listing if len(e) == 2]:
                shard = os.path.join(self.root, entry)
                if not os.path.isdir(shard):
                    continue
                self._listing.discard(entry)
                try:
                    for name in os.listdir(shard):
                        self._legacy[name] = os.path.join(shard, name)
                        self._listing.add(name)
                except OSError:
                    pass
        return self._listing

    def refresh(self) -> int:
        """Re-scan the cache directory, picking up cells written by other
        processes since the last scan. Returns the number of indexed cells."""
        with self._lock:
            self._listing = None
            self._legacy.clear()
            return sum(1 for e in self._index() if e.endswith(".json"))

    def count(self) -> int:
        """Number of cells currently indexed (no directory re-scan)."""
        with self._lock:
            return sum(1 for e in self._index() if e.endswith(".json"))

    def _locate(self, name: str) -> Optional[str]:
        """Path of `name` if present, else None; adopts external writes.

        The one-shot listing is a snapshot: a cell persisted by another
        process after this instance's first scan is not in it. Treating
        that as a miss would turn a permanent hit into a permanent
        re-simulation in long-lived processes, so an index miss is
        confirmed with a direct existence probe and confirmed entries are
        adopted into the index.
        """
        with self._lock:
            if name in self._index():
                return self._legacy.get(name) or os.path.join(self.root, name)
            path = os.path.join(self.root, name)
            if os.path.exists(path):
                self._listing.add(name)
                self.adopted += 1
                return path
            return None

    def contains(self, key: str) -> bool:
        """Existence check without a read (and without hit/miss counting)."""
        return self._locate(key + ".json") is not None

    def get(self, key: str) -> Optional[SimResult]:
        name = key + ".json"
        path = self._locate(name)
        if path is None:
            with self._lock:
                self.misses += 1
            return None
        try:
            with open(path) as f:
                blob = json.load(f)
            fields = blob["result"]
            if set(fields) != set(_RESULT_FIELDS):
                raise ValueError("schema mismatch")
            res = SimResult(**fields)
        except FileNotFoundError:
            with self._lock:
                self._index().discard(name)
                self.misses += 1
            return None
        except Exception:
            # Corrupt entry (torn write, disk-full truncation, schema
            # drift): treat as a miss and *quarantine* rather than delete
            # — rename to `<name>.corrupt` so the evidence survives for
            # post-mortem while the key re-simulates cleanly. The
            # `.corrupt` suffix keeps it out of the index and the
            # count()/refresh() tallies (both count `.json` names only).
            try:
                os.replace(path, path + ".corrupt")
            except OSError:
                try:
                    os.remove(path)
                except OSError:
                    pass
            with self._lock:
                self._index().discard(name)
                self._legacy.pop(name, None)
                self.misses += 1
                self.corrupt += 1
            return None
        with self._lock:
            self.hits += 1
        return res

    def put(self, key: str, result: SimResult) -> None:
        if not self._root_ok:
            os.makedirs(self.root, exist_ok=True)
            self._root_ok = True
        # Direct low-level write, no tmp+rename dance: a torn write (crash
        # mid-put, or two processes racing on one cell) leaves a file the
        # corruption-recovery path in get() detects, deletes and
        # re-simulates — and results are deterministic, so losing a racer's
        # copy costs a re-simulation, never wrong data. The rename barely
        # bought safety but doubled the syscall bill of cold sweeps.
        data = json.dumps({"key": key, "model": MODEL_VERSION,
                           "result": dataclasses.asdict(result)}).encode()
        fd = os.open(self._path(key),
                     os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
        try:
            os.write(fd, data)
        finally:
            os.close(fd)
        name = key + ".json"
        with self._lock:
            self._legacy.pop(name, None)  # flat copy supersedes a legacy one
            self._index().add(name)


# ---------------------------------------------------------------------------
# Per-process expansion LRU
# ---------------------------------------------------------------------------


class ExpansionCache:
    """Bounded LRU of expanded :class:`WarpStream` objects.

    Keyed by ``(bench, n_threads, seed, expansion_key)`` — everything that
    determines ``expand_stream`` output. Bounded (default
    :data:`EXPANSION_CACHE_SIZE` streams, a few hundred KB each) so
    long-lived sweep servers cannot grow without limit; eviction is
    least-recently-used. Each process (sweep parent and every pool worker)
    holds its own instance.

    Thread-safe: the LRU dict and counters are guarded by a lock (the
    sweep service hits the module-global instance from many request
    threads; unguarded ``move_to_end``/``popitem`` interleavings corrupt
    recency order or raise mid-iteration). The lock is *not* held while a
    missing stream is built, so two threads missing the same key may both
    build it — a benign duplicate (streams are deterministic, last insert
    wins); cell-level dedup lives in the service layer.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        # key -> (workload, stream); the stored workload pins the program
        # object so the identity check below can never alias a recycled id.
        self._streams: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict())
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()

    def get(self, workload: Workload, cfg: MachineConfig,
            trace: Optional[ThreadTrace] = None,
            trace_fn=None,
            single_phase: bool = False) -> WarpStream:
        """Cached stream for ``(workload, cfg.expansion_key())``.

        On a miss the stream is built by aggregating `trace` (or the
        result of calling `trace_fn`, resolved lazily so a cache hit never
        touches the trace layer — the two-phase fast path: one
        :class:`~repro.core.warpsim.trace.ThreadTrace` serves every
        expansion key of the workload), by the retired single-phase walk
        when ``single_phase=True`` (the honest PR 2 baseline of
        ``benchmarks/sweep_bench.py``), else by the two-phase
        ``expand_stream`` building its own trace.
        """
        key = (workload.name, workload.n_threads, workload.seed,
               cfg.expansion_key())
        with self._lock:
            ent = self._streams.get(key)
            # The program-identity check guards callers that build Workload
            # objects by hand: two different programs sharing a name must
            # not alias one cached stream (get_workload-canonical workloads
            # always pass — the workload itself is memoized).
            if ent is not None and ent[0].program is workload.program:
                self._streams.move_to_end(key)
                self.hits += 1
                return ent[1]
            self.misses += 1
        if trace is None and trace_fn is not None:
            trace = trace_fn()
        if trace is not None:
            stream = aggregate_stream(trace, cfg)
        elif single_phase:
            stream = expand_stream_single(workload, cfg)
        else:
            stream = expand_stream(workload, cfg)
        with self._lock:
            self._streams[key] = (workload, stream)
            while len(self._streams) > self.maxsize:
                self._streams.popitem(last=False)
        return stream

    def __len__(self) -> int:
        with self._lock:
            return len(self._streams)

    def clear(self) -> None:
        with self._lock:
            self._streams.clear()
            self.hits = 0
            self.misses = 0


EXPANSION_CACHE_SIZE = 64
EXPANSION_CACHE = ExpansionCache(EXPANSION_CACHE_SIZE)


# ---------------------------------------------------------------------------
# Per-process thread-trace LRU (+ optional on-disk persistence)
# ---------------------------------------------------------------------------


# Bump when the ThreadTrace encoding changes: part of every on-disk trace
# key, so stale trace files from older encodings can never be loaded.
TRACE_VERSION = "trace-1"

_TRACE_FIELDS = ("ev_kind", "ev_mask", "ev_arg", "ev_addr", "masks",
                 "addr_off", "addr_vals")


def trace_key(bench: str, n_threads: int, seed: int) -> str:
    """Content-addressed key of one workload's thread trace on disk."""
    blob = json.dumps({
        "model": MODEL_VERSION,
        "trace": TRACE_VERSION,
        "bench": bench.upper(),
        "n_threads": n_threads,
        "seed": seed,
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


class TraceCache:
    """Bounded LRU of :class:`~repro.core.warpsim.trace.ThreadTrace`.

    Sibling of :data:`EXPANSION_CACHE` one level up the sharing hierarchy:
    keyed by ``(bench, n_threads, seed)`` only — *no* machine field
    participates, every expansion key aggregates from the same trace.
    Bounded (default :data:`TRACE_CACHE_SIZE` traces, a few hundred KB
    each) with LRU eviction, like the expansion cache.

    With a `root` directory (``run_sweep`` points it at ``traces/`` inside
    the :class:`ResultCache` root), in-memory misses fall back to an
    ``.npz`` snapshot on disk and fresh builds are persisted — traces are
    deterministic in ``(MODEL_VERSION, TRACE_VERSION, bench, n_threads,
    seed)`` (stable region hashing), so a snapshot written by any process
    is exact. Unreadable or stale snapshots are deleted and rebuilt, the
    same corruption contract as ``ResultCache``.

    Thread-safe with the same locking discipline as
    :class:`ExpansionCache`: dict and counters under a lock, builds and
    disk I/O outside it (duplicate concurrent builds are benign).
    """

    def __init__(self, maxsize: int = 32):
        self.maxsize = maxsize
        # key -> (workload, trace); the stored workload pins the program
        # object so the identity check can never alias a recycled id.
        self._traces: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict())
        self.hits = 0
        self.misses = 0
        self.disk_hits = 0
        self.builds = 0
        self._lock = threading.Lock()

    def get(self, workload: Workload,
            root: Optional[str] = None) -> ThreadTrace:
        key = (workload.name, workload.n_threads, workload.seed)
        with self._lock:
            ent = self._traces.get(key)
            if ent is not None and ent[0].program is workload.program:
                self._traces.move_to_end(key)
                self.hits += 1
                hit = ent[1]
            else:
                hit = None
                self.misses += 1
        if hit is not None:
            if root and not os.path.exists(self._path(workload, root)):
                # The LRU entry may predate persistence (built by an
                # earlier sweep without a root): snapshot it now so the
                # persist_traces=True promise holds for later processes.
                self._store(workload, root, hit)
            return hit
        trace = self._load(workload, root) if root else None
        if trace is None:
            trace = build_thread_trace(workload)
            if root:
                self._store(workload, root, trace)
            with self._lock:
                self.builds += 1
        else:
            with self._lock:
                self.disk_hits += 1
        with self._lock:
            self._traces[key] = (workload, trace)
            while len(self._traces) > self.maxsize:
                self._traces.popitem(last=False)
        return trace

    def _path(self, workload: Workload, root: str) -> str:
        return os.path.join(root, trace_key(
            workload.name, workload.n_threads, workload.seed) + ".npz")

    def _load(self, workload: Workload,
              root: str) -> Optional[ThreadTrace]:
        path = self._path(workload, root)
        try:
            with np.load(path) as data:
                if set(data.files) != set(_TRACE_FIELDS):
                    raise ValueError("schema mismatch")
                return ThreadTrace(n_threads=workload.n_threads,
                                   **{f: data[f] for f in _TRACE_FIELDS})
        except FileNotFoundError:
            return None
        except Exception:
            # Corrupt/stale snapshot: drop it and rebuild.
            try:
                os.remove(path)
            except OSError:
                pass
            return None

    def _store(self, workload: Workload, root: str,
               trace: ThreadTrace) -> None:
        # The tmp file must be unique per *writer*, not per process: two
        # service threads (same pid) persisting one trace family through a
        # deterministic `{path}.{pid}.tmp` name would open the same file,
        # truncate each other mid-write, and os.replace would publish the
        # torn interleaving. mkstemp in the cache dir gives every writer a
        # private file (same filesystem, so the rename stays atomic) and
        # the last complete snapshot wins — byte-identical anyway, traces
        # are deterministic.
        path = self._path(workload, root)
        tmp = None
        try:
            os.makedirs(root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=root, prefix=os.path.basename(path) + ".", suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **{f_: getattr(trace, f_)
                               for f_ in _TRACE_FIELDS})
            os.replace(tmp, path)   # atomic: concurrent writers race benignly
        except OSError:
            if tmp is not None:
                try:
                    os.remove(tmp)
                except OSError:
                    pass

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()
            self.hits = self.misses = self.disk_hits = self.builds = 0


TRACE_CACHE_SIZE = 32
TRACE_CACHE = TraceCache(TRACE_CACHE_SIZE)

# Deprecated alias: counters of the most recent run_sweep call in this
# process. Prefer :func:`run_sweep_with_stats`, which returns each run's
# private snapshot — concurrent sweeps (service request threads) each get
# their own dict, while this global only ever holds whichever run
# published last. Kept as the same mutable object across runs because
# callers import it by value; updates are atomic under _STATS_LOCK.
# Reads go through the module ``__getattr__`` below, which emits a
# DeprecationWarning — no in-repo caller reads it anymore.
_LAST_SWEEP_STATS: Dict[str, int] = {}  # guarded-by: _STATS_LOCK
_STATS_LOCK = threading.Lock()


def __getattr__(name: str):
    if name == "LAST_SWEEP_STATS":
        warnings.warn(
            "sweep.LAST_SWEEP_STATS is deprecated: it is overwritten by "
            "every concurrent sweep in the process. Use "
            "run_sweep_with_stats() (or api.Session.run(...).stats) for a "
            "per-run snapshot.", DeprecationWarning, stacklevel=2)
        return _LAST_SWEEP_STATS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# ---------------------------------------------------------------------------
# Sweep specification
# ---------------------------------------------------------------------------


# One grid cell: (machine name, machine config, bench, n_threads, seed).
Cell = Tuple[str, MachineConfig, str, Optional[int], int]


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """A declarative bench × machine × seed grid.

    `machines` maps display name -> :class:`MachineConfig`; when omitted,
    `warp_sizes` builds plain SIMT baselines (``ws4`` … ``ws128``), and when
    both are omitted the paper's seven-machine suite is used. Cells are
    enumerated machines-major, benches-minor, seeds-innermost — a fixed
    total order that parallel execution must (and does) preserve.
    """

    benches: Tuple[str, ...] = tuple(BENCHMARKS)
    machines: Optional[Mapping[str, MachineConfig]] = None
    warp_sizes: Tuple[int, ...] = ()
    simd_width: int = 8
    n_threads: Optional[int] = None
    seeds: Tuple[int, ...] = (0,)

    @classmethod
    def warp_size_range(cls, lo: int = 4, hi: int = 128,
                        simd_width: int = 8, **kw) -> "SweepSpec":
        """Dense power-of-two warp-size sweep, `lo`..`hi` threads/warp."""
        sizes = []
        w = lo
        while w <= hi:
            sizes.append(w)
            w *= 2
        return cls(warp_sizes=tuple(sizes), simd_width=simd_width, **kw)

    def machine_set(self) -> Dict[str, MachineConfig]:
        if self.machines is not None:
            return dict(self.machines)
        if self.warp_sizes:
            return {f"ws{w}": machines_mod.baseline(w, self.simd_width)
                    for w in self.warp_sizes}
        return machines_mod.paper_suite(self.simd_width)

    def cells(self, machine_set: Optional[Mapping[str, MachineConfig]] = None
              ) -> List[Cell]:
        """Cell list in the spec's fixed order.

        Pass a precomputed ``machine_set()`` to avoid rebuilding it (the
        result is identical; ``run_sweep`` computes the set exactly once).
        """
        mset = self.machine_set() if machine_set is None else machine_set
        out: List[Cell] = []
        for mname, cfg in mset.items():
            for b in self.benches:
                for seed in self.seeds:
                    out.append((mname, cfg, b, self.n_threads, seed))
        return out


def spec_to_dict(spec: SweepSpec) -> dict:
    """JSON-safe encoding of a spec (service POST bodies, queue shards)."""
    d = {
        "benches": list(spec.benches),
        "warp_sizes": list(spec.warp_sizes),
        "simd_width": spec.simd_width,
        "n_threads": spec.n_threads,
        "seeds": list(spec.seeds),
    }
    if spec.machines is not None:
        d["machines"] = {name: dataclasses.asdict(cfg)
                         for name, cfg in spec.machines.items()}
    return d


def spec_from_dict(d: Mapping) -> SweepSpec:
    """Inverse of :func:`spec_to_dict`.

    Absent (or null) fields take the spec defaults; a *present but empty*
    ``benches``/``seeds`` list is honored as an empty grid rather than
    silently widened to the full default suite — an emptied-out client
    filter must not trigger a 90-cell sweep.
    """
    machines = d.get("machines")
    if machines is not None:
        machines = {name: MachineConfig(**fields)
                    for name, fields in machines.items()}
    benches = d.get("benches")
    seeds = d.get("seeds")
    return SweepSpec(
        benches=tuple(BENCHMARKS) if benches is None else tuple(benches),
        machines=machines,
        warp_sizes=tuple(d.get("warp_sizes") or ()),
        simd_width=d.get("simd_width", 8),
        n_threads=d.get("n_threads"),
        seeds=(0,) if seeds is None else tuple(seeds),
    )


def family_major_cells(cells: List[Cell]) -> List[Cell]:
    """Reorder cells family-major: trace family ``(bench, n_threads,
    seed)``, then expansion key within the family, preserving first-seen
    order of both. Consecutive cells then share traces and aggregated
    streams through the per-process LRUs — the same locality ``run_sweep``
    engineers for its worker payloads, reused by the sweep service's
    cell-at-a-time path and the work queue's chunk sharding."""
    families: "collections.OrderedDict[tuple, collections.OrderedDict]" = (
        collections.OrderedDict())
    for cell in cells:
        _mname, cfg, bench, n_threads, seed = cell
        fam = families.setdefault((bench, n_threads, seed),
                                  collections.OrderedDict())
        fam.setdefault(cfg.expansion_key(), []).append(cell)
    return [cell for fam in families.values()
            for group in fam.values() for cell in group]


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------


# One unit of worker work: (bench, n_threads, seed, [configs sharing one
# expansion key], engine, reuse_expansion, share_trace, trace_dir).
# Payloads are ordered family-major (all expansion-key groups of one
# workload adjacent), so parallel chunking colocates a family's groups in
# one worker and its per-process trace LRU serves them all.
_GroupPayload = Tuple[str, Optional[int], int, List[MachineConfig], str,
                      bool, bool, Optional[str]]


def _run_group(args: _GroupPayload,
               trace_cache: Optional[TraceCache] = None,
               expansion_cache: Optional[ExpansionCache] = None
               ) -> List[SimResult]:
    """Worker: aggregate one expansion key's stream, simulate every member.

    Top-level for pickling. With `share_trace` the workload's ThreadTrace
    comes from the trace LRU (or its on-disk snapshot under `trace_dir`),
    resolved lazily on an expansion-LRU miss — so every expansion-key
    group of one workload handled by this process shares a single trace
    build, and a worker that sees the same (bench, n_threads, seed,
    expansion_key) bucket again — across chunks, or across run_sweep
    calls in serial mode — skips re-aggregation entirely.
    `share_trace=False` keeps per-group single-phase expansion (the PR 2
    cold path, re-measured by ``benchmarks/sweep_bench.py``), and
    `reuse_expansion=False` bypasses every cache and expands from scratch
    (the PR 1 baseline); riding in the payload means the flags reach pool
    workers under any multiprocessing start method.

    `trace_cache`/`expansion_cache` default to the module-global LRUs —
    a serial sweep run through an :class:`api.Session` passes the
    session-owned instances instead; pool workers always use their own
    process's globals (cache objects hold locks and don't pickle).
    """
    tcache = TRACE_CACHE if trace_cache is None else trace_cache
    ecache = EXPANSION_CACHE if expansion_cache is None else expansion_cache
    wl, stream = _group_stream(args, tcache, ecache)
    engine = args[4]
    ops = stream.to_warp_ops() if engine == "event" else stream
    out = []
    for cfg in args[3]:
        with obs_mod.stage("engine", engine=engine, bench=wl.name):
            out.append(simulate(wl.name, ops, cfg, engine=engine))
    return out


def _group_stream(args: _GroupPayload, tcache: TraceCache,
                  ecache: ExpansionCache) -> Tuple[Workload, WarpStream]:
    """Resolve one payload's workload + aggregated stream through the LRUs
    (shared by the per-group worker path and the pallas family launcher)."""
    bench, n_threads, seed, cfgs, _engine, reuse, share, tdir = args
    wl = get_workload(bench, n_threads=n_threads, seed=seed)
    # The aggregate stage covers the expansion-LRU resolution; a cold
    # trace build nests a trace_build span/stage inside it, so the
    # histogram pair separates re-aggregation cost from trace cost.
    with obs_mod.stage("aggregate", bench=bench):
        if reuse:
            if share:
                stream = ecache.get(
                    wl, cfgs[0],
                    trace_fn=lambda: _traced_build(tcache, wl, tdir))
            else:
                stream = ecache.get(wl, cfgs[0], single_phase=True)
        else:
            stream = (expand_stream(wl, cfgs[0]) if share
                      else expand_stream_single(wl, cfgs[0]))
    return wl, stream


def _traced_build(tcache: TraceCache, wl: Workload,
                  root: Optional[str]) -> "ThreadTrace":
    """Trace-LRU resolve under the ``trace_build`` stage (only reached on
    an expansion-LRU miss, so the histogram counts real builds/loads)."""
    with obs_mod.stage("trace_build", bench=wl.name):
        return tcache.get(wl, root=root)


def _run_family_pallas(fam_payloads: List[_GroupPayload],
                       tcache: TraceCache, ecache: ExpansionCache
                       ) -> Tuple[Optional[List[List[SimResult]]], bool]:
    """Simulate one trace family's payloads in a single device launch.

    All expansion-key groups of the family (each carrying its machine
    variants) become units of one ``_pallas.run_family`` call — a family
    costs one launch instead of one engine run per cell. Returns
    ``(per-group result lists, launched)``; ``(None, False)`` only when
    ``WARPSIM_PALLAS`` is off, in which case the caller runs the
    per-group path (whose per-cell pallas dispatch honours the same kill
    switch). A failed compile or launch raises.
    """
    groups = []
    pairs = []
    for payload in fam_payloads:
        wl, stream = _group_stream(payload, tcache, ecache)
        cfgs = payload[3]
        groups.append((wl, stream, cfgs))
        pairs.extend((stream, cfg) for cfg in cfgs)
    raw = _pallas.run_family(pairs)
    if raw is None:
        return None, False
    out: List[List[SimResult]] = []
    i = 0
    for wl, stream, cfgs in groups:
        totals = stream_totals(stream)
        out.append([loop_result(wl.name, cfg, raw[i + j], totals)
                    for j, cfg in enumerate(cfgs)])
        i += len(cfgs)
    return out, True


def compute_family_pallas(bench: str, n_threads: Optional[int], seed: int,
                          cfgs: Sequence[MachineConfig],
                          trace_dir: Optional[str],
                          trace_cache: TraceCache,
                          expansion_cache: ExpansionCache
                          ) -> Tuple[List[SimResult], bool]:
    """Simulate machine variants of one trace family in one device launch.

    The daemon's batched sibling of :func:`compute_cell`: each run of
    consecutive `cfgs` that share an expansion key becomes one payload of
    :func:`_run_family_pallas` (callers pass cells in
    :func:`family_major_cells` order, so each key is one run), with
    streams from the given LRUs. Returns one result per config, in `cfgs`
    order, and whether a launch ran; with ``WARPSIM_PALLAS`` off each
    payload runs through :func:`_run_group` instead.
    """
    payloads: List[_GroupPayload] = [
        (bench, n_threads, seed, list(members), "pallas", True, True,
         trace_dir)
        for _key, members in itertools.groupby(cfgs, key=expansion_key)]
    fam_res, launched = _run_family_pallas(payloads, trace_cache,
                                           expansion_cache)
    if not launched:
        fam_res = [_run_group(payload, trace_cache=trace_cache,
                              expansion_cache=expansion_cache)
                   for payload in payloads]
    return [res for group_res in fam_res for res in group_res], launched


def compute_cell(bench: str, cfg: MachineConfig,
                 n_threads: Optional[int] = None, seed: int = 0,
                 engine: str = "auto",
                 trace_dir: Optional[str] = None,
                 trace_cache: Optional[TraceCache] = None,
                 expansion_cache: Optional[ExpansionCache] = None
                 ) -> SimResult:
    """Simulate one grid cell through the trace/expansion LRUs.

    The cell-at-a-time sibling of :func:`_run_group`, used by the sweep
    service, work-queue workers and ``api.Session.cell``: the stream
    comes from the expansion LRU (lazily backed by the trace LRU, with
    on-disk trace snapshots under `trace_dir` when given), so callers that
    walk cells in :func:`family_major_cells` order get the same trace- and
    expansion-sharing as a grouped sweep. The LRUs default to the
    module-global instances; pass session-owned ones to keep the state
    off the process globals.
    """
    tcache = TRACE_CACHE if trace_cache is None else trace_cache
    ecache = EXPANSION_CACHE if expansion_cache is None else expansion_cache
    wl = get_workload(bench, n_threads=n_threads, seed=seed)
    with obs_mod.stage("aggregate", bench=bench):
        stream = ecache.get(
            wl, cfg, trace_fn=lambda: _traced_build(tcache, wl, trace_dir))
    ops = stream.to_warp_ops() if engine == "event" else stream
    with obs_mod.stage("engine", engine=engine, bench=bench):
        return simulate(wl.name, ops, cfg, engine=engine)


def _jax_backend_up() -> bool:
    """True once this process has initialised a jax backend (checked
    without importing jax into a process that never used it)."""
    if "jax" not in sys.modules:
        return False
    from repro import compat
    return compat.backend_initialized()


def run_sweep(
    spec: SweepSpec,
    cache: Optional[ResultCache] = None,
    parallel: Optional[bool] = None,
    max_workers: Optional[int] = None,
    engine: str = "auto",
    group_expansion: bool = True,
    reuse_expansion: bool = True,
    share_traces: bool = True,
    persist_traces: bool = False,
    trace_cache: Optional[TraceCache] = None,
    expansion_cache: Optional[ExpansionCache] = None,
) -> Dict[int, Dict[str, Dict[str, SimResult]]] | Dict[str, Dict[str, SimResult]]:
    """:func:`run_sweep_with_stats` without the stats snapshot.

    Kept as the primary low-level entry point for callers that only want
    numbers (``repro.core.warpsim.api.Session`` is the facade above it).
    """
    results, _stats = run_sweep_with_stats(
        spec, cache=cache, parallel=parallel, max_workers=max_workers,
        engine=engine, group_expansion=group_expansion,
        reuse_expansion=reuse_expansion, share_traces=share_traces,
        persist_traces=persist_traces, trace_cache=trace_cache,
        expansion_cache=expansion_cache)
    return results


def run_sweep_with_stats(
    spec: SweepSpec,
    cache: Optional[ResultCache] = None,
    parallel: Optional[bool] = None,
    max_workers: Optional[int] = None,
    engine: str = "auto",
    group_expansion: bool = True,
    reuse_expansion: bool = True,
    share_traces: bool = True,
    persist_traces: bool = False,
    trace_cache: Optional[TraceCache] = None,
    expansion_cache: Optional[ExpansionCache] = None,
) -> Tuple[Dict, Dict[str, int]]:
    """Run a sweep grid; returns ``(results, stats)``.

    ``results[machine][bench] -> SimResult`` as for :func:`run_sweep`;
    `stats` is this run's private counter snapshot (cells, cache hits and
    misses counted per cell actually probed by *this* run, grouping and
    LRU counters). Unlike the deprecated ``LAST_SWEEP_STATS`` global —
    which concurrent sweeps overwrite — the snapshot is race-free per
    run; the LRU deltas it carries still read shared caches and are
    approximate when other threads sweep through the same LRUs
    concurrently.

    `trace_cache`/`expansion_cache` select the LRU instances (default:
    the module globals). An :class:`api.Session` passes its own — serial
    sweeps then keep all LRU state session-local; pool workers always use
    their own process's globals either way (the instances hold locks and
    do not pickle).

    With multiple seeds the result is keyed ``results[seed][machine][bench]``.
    Cached cells are served from `cache`; uncached cells are bucketed by
    shared expansion key within trace families (``(bench, n_threads,
    seed)``) — one expansion-key group is one unit of worker work
    (aggregate the family's ThreadTrace once per key, simulate every
    machine variant), ordered family-major so a family's groups land in
    one worker's chunk and share a single trace build through the
    per-process :data:`TRACE_CACHE`; run process-parallel
    (`parallel=None` auto-enables parallelism when the grid is big enough
    and at least four CPUs are available). ``share_traces=False`` drops
    back to single-phase expansion per group (the PR 2 cold path,
    re-measured live by ``benchmarks/sweep_bench.py``);
    ``group_expansion=False`` schedules one cell per work unit (the PR 1
    behavior) and ``reuse_expansion=False`` additionally bypasses the
    per-process trace/expansion LRUs in every worker (the from-scratch
    baseline mode). With ``persist_traces=True`` (and a `cache`), traces
    are additionally persisted under ``<cache root>/traces/`` and
    reloaded by later processes — worth it for long-lived grids that keep
    adding machine variants; off by default (cold sweeps should not pay
    the snapshot writes). Result ordering is deterministic — the spec's
    cell order — independent of worker completion order.
    """
    tcache = TRACE_CACHE if trace_cache is None else trace_cache
    ecache = EXPANSION_CACHE if expansion_cache is None else expansion_cache
    mset = spec.machine_set()
    cells = spec.cells(machine_set=mset)
    results: Dict[int, Dict[str, Dict[str, SimResult]]] = {
        seed: {} for seed in spec.seeds}
    # Per-run cache counters are tallied locally (one hit xor miss per cell
    # probed below) instead of diffing the shared instance counters, so
    # concurrent sweeps against one cache don't bleed into each other.
    run_cache_hits = 0
    exp_hits0, exp_miss0 = ecache.hits, ecache.misses
    trc_hits0, trc_miss0 = tcache.hits, tcache.misses
    trc_disk0 = tcache.disk_hits

    todo: List[Tuple[Cell, Optional[str]]] = []
    for mname, cfg, bench, n_threads, seed in cells:
        key = (cell_key(bench, cfg, n_threads, seed)
               if cache is not None else None)
        cached = cache.get(key) if cache is not None else None
        if cached is not None:
            run_cache_hits += 1
            results[seed].setdefault(mname, {})[bench] = cached
        else:
            todo.append(((mname, cfg, bench, n_threads, seed), key))

    n_groups = 0
    n_families = 0
    n_family_launches = 0
    if not group_expansion:
        share_traces = False     # per-cell scheduling: no sharing at all
    if todo:
        # Two-level bucketing of uncached cells: trace family (bench,
        # n_threads, seed), then expansion key within the family. One
        # expansion-key group is one unit of worker work; keeping the
        # family level makes payload order family-major, so a family's
        # groups are adjacent and parallel chunking sends them to one
        # worker (whose trace LRU then builds the family's trace once).
        families: "collections.OrderedDict[tuple, collections.OrderedDict]" = (
            collections.OrderedDict())
        for idx, (cell, key) in enumerate(todo):
            mname, cfg, bench, n_threads, seed = cell
            if not group_expansion:
                fkey, gkey = (idx,), idx
            else:
                fkey = (bench, n_threads, seed)
                gkey = cfg.expansion_key()
            fam = families.setdefault(fkey, collections.OrderedDict())
            fam.setdefault(gkey, []).append((cell, key))
        n_families = len(families)
        n_groups = sum(len(fam) for fam in families.values())
        trace_dir = (os.path.join(cache.root, "traces")
                     if cache is not None and share_traces and
                     reuse_expansion and persist_traces else None)
        payloads: List[_GroupPayload] = []
        grp_members: List[List[Tuple[Cell, Optional[str]]]] = []
        for fam in families.values():
            for members in fam.values():
                first = members[0][0]
                payloads.append((
                    first[2], first[3], first[4],
                    [cell[1] for cell, _ in members],
                    engine, reuse_expansion, share_traces, trace_dir))
                grp_members.append(members)

        ncpu = os.cpu_count() or 1
        if engine in ("auto", "native"):
            # Compile/load the native core once in the parent so forked
            # workers inherit it instead of racing to build it (and so the
            # parallel heuristic below knows the per-cell cost).
            cells_are_cheap = _native.available()
        else:
            cells_are_cheap = False
        if engine == "pallas":
            # Device batching replaces process parallelism: the whole
            # family runs as one launch in the parent (jit caches are
            # per-process; a pool would re-trace in every worker).
            parallel = False
        if parallel is None:
            # Process pools only pay off when there is real work per cell
            # relative to pool spawn + IPC: with the compiled engine a
            # grid cell costs ~0.5 ms, so below 4 CPUs the pool overhead
            # exceeds the extra cores' contribution (measured: 0.26 s
            # serial vs 0.33 s parallel for the 90-cell paper grid on a
            # 2-CPU host). On the pure-Python engines (no compiler, or
            # event/fast_nested explicitly) cells are ~10x heavier and a
            # second core already wins.
            parallel = len(payloads) >= 4 and (
                ncpu >= 4 or (ncpu > 1 and not cells_are_cheap))
        if parallel and _jax_backend_up():
            # One process per device: a fork of a process whose jax
            # backend is up can deadlock, and a child cannot share the
            # parent's chip. Such a process sweeps serially.
            parallel = False

        def _scatter(members, group_res) -> None:
            for (cell, key), res in zip(members, group_res):
                mname, cfg, bench, n_threads, seed = cell
                results[seed].setdefault(mname, {})[bench] = res
                if cache is not None:
                    cache.put(key, res)

        if parallel:
            workers = max_workers or min(ncpu, len(payloads))
            chunk = max(1, len(payloads) // (4 * workers))
            with concurrent.futures.ProcessPoolExecutor(workers) as ex:
                for members, group_res in zip(
                        grp_members,
                        ex.map(_run_group, payloads, chunksize=chunk)):
                    _scatter(members, group_res)
        elif engine == "pallas" and group_expansion:
            # Family-major device batching: one launch per trace family
            # covers all its expansion keys x machine variants. Payloads
            # are already family-major, so each family is a contiguous
            # payload run of len(fam) groups.
            i = 0
            for fam in families.values():
                k = len(fam)
                fam_res, launched = _run_family_pallas(
                    payloads[i:i + k], tcache, ecache)
                if launched:
                    n_family_launches += 1
                    for members, group_res in zip(grp_members[i:i + k],
                                                  fam_res):
                        _scatter(members, group_res)
                else:
                    for members, payload in zip(grp_members[i:i + k],
                                                payloads[i:i + k]):
                        _scatter(members, _run_group(
                            payload, trace_cache=tcache,
                            expansion_cache=ecache))
                i += k
        else:
            for members, payload in zip(grp_members, payloads):
                _scatter(members, _run_group(payload, trace_cache=tcache,
                                             expansion_cache=ecache))

    stats = dict(
        cells=len(cells),
        cache_hits=run_cache_hits,
        cache_misses=len(todo) if cache is not None else 0,
        simulated=len(todo),
        # Stats-shape parity with the service's mesh path: in-process
        # sweeps have no peers, so this is identically zero here.
        peer_hits=0,
        expansion_groups=n_groups,
        expansions_saved=len(todo) - n_groups,
        trace_families=n_families,
        traces_shared=(n_groups - n_families if share_traces else 0),
        # Device launches performed by the pallas family path (one per
        # trace family when the engine is live; 0 for every other engine).
        family_launches=n_family_launches,
        # LRU counter deltas of the sweep parent (serial sweeps; pool
        # workers keep their own caches, like the expansion LRU).
        expansion_cache_hits=ecache.hits - exp_hits0,
        expansion_cache_misses=ecache.misses - exp_miss0,
        trace_cache_hits=tcache.hits - trc_hits0,
        trace_cache_misses=tcache.misses - trc_miss0,
        trace_disk_hits=tcache.disk_hits - trc_disk0,
    )
    with _STATS_LOCK:
        _LAST_SWEEP_STATS.clear()
        _LAST_SWEEP_STATS.update(stats)

    # Re-impose the spec's machine/bench ordering (cache hits and parallel
    # completion both fill dicts out of order).
    ordered: Dict[int, Dict[str, Dict[str, SimResult]]] = {}
    for seed in spec.seeds:
        ordered[seed] = {
            mname: {b: results[seed][mname][b] for b in spec.benches}
            for mname in mset
        }
    if len(spec.seeds) == 1:
        return ordered[spec.seeds[0]], stats
    return ordered, stats

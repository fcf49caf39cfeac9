"""Sweep engine unit tests: cache keys, hit/miss, corruption recovery,
spec enumeration, serial/parallel equivalence, and the concurrency-safety
contracts of the cache stack (stale-index adoption, atomic trace
persistence, locked LRUs, per-run stats snapshots)."""

import dataclasses
import io
import json
import os
import threading

import numpy as np
import pytest

from repro.core.warpsim import machines
from repro.core.warpsim import sweep as sweep_mod
from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim.sweep import (
    ResultCache, SweepSpec, cell_key, machine_key, run_sweep,
    run_sweep_with_stats,
)

SMALL = dict(benches=("BFS", "BKP", "DYN"), n_threads=256)


def _spec(**kw):
    base = dict(machines={"ws8": machines.baseline(8),
                          "SW+": machines.sw_plus()}, **SMALL)
    base.update(kw)
    return SweepSpec(**base)


# ------------------------------------------------------------------- cache

def test_cache_miss_then_hit(tmp_path):
    cache = ResultCache(str(tmp_path))
    spec = _spec()
    first = run_sweep(spec, cache=cache, parallel=False)
    assert cache.hits == 0 and cache.misses == len(spec.cells())

    warm = ResultCache(str(tmp_path))
    second = run_sweep(spec, cache=warm, parallel=False)
    assert warm.hits == len(spec.cells()) and warm.misses == 0
    for m in first:
        for b in first[m]:
            assert (dataclasses.asdict(second[m][b])
                    == dataclasses.asdict(first[m][b]))


def test_warm_cache_never_simulates(tmp_path, monkeypatch):
    cache = ResultCache(str(tmp_path))
    spec = _spec()
    run_sweep(spec, cache=cache, parallel=False)

    from repro.core.warpsim import sweep as sweep_mod

    def boom(args):
        raise AssertionError("warm sweep must not simulate")

    monkeypatch.setattr(sweep_mod, "_run_group", boom)
    res = run_sweep(spec, cache=ResultCache(str(tmp_path)), parallel=False)
    assert res["SW+"]["BFS"].cycles > 0


def test_cache_key_depends_on_every_machine_field(tmp_path):
    """Changing ANY MachineConfig field must change the cell key.

    The alternates map must cover every dataclass field — adding a field to
    MachineConfig without extending it fails here, which is the reminder to
    keep the cache key exhaustive.
    """
    base = MachineConfig()
    alternates = {
        "name": "other",
        "warp_size": 64,
        "simd_width": 4,
        "ideal_coalescing": True,
        "mimd": True,
        "num_sms": 4,
        "threads_per_sm": 2048,
        "pipeline_depth": 12,
        "core_clock_ghz": 2.0,
        "num_mem_ctrls": 8,
        "dram_bw_gbps": 100.0,
        "dram_latency_cycles": 100,
        "transaction_bytes": 128,
        "l1_size_bytes": 96 * 1024,
        "l1_ways": 4,
        "l1_hit_latency": 2,
    }
    fields = {f.name for f in dataclasses.fields(MachineConfig)}
    assert fields == set(alternates), "extend alternates for new fields"
    k0 = cell_key("BFS", base, 256, 0)
    for fname, alt in alternates.items():
        assert getattr(base, fname) != alt, fname
        cfg = dataclasses.replace(base, **{fname: alt})
        assert cell_key("BFS", cfg, 256, 0) != k0, fname
        assert machine_key(cfg) != machine_key(base), fname


def test_cache_key_depends_on_bench_threads_seed():
    cfg = MachineConfig()
    k = cell_key("BFS", cfg, 256, 0)
    assert cell_key("BKP", cfg, 256, 0) != k
    assert cell_key("BFS", cfg, 512, 0) != k
    assert cell_key("BFS", cfg, 256, 1) != k
    # None canonicalizes to the bench's default thread count.
    from repro.core.warpsim.trace import get_workload
    default = get_workload("BFS").n_threads
    assert cell_key("BFS", cfg, None, 0) == cell_key("BFS", cfg, default, 0)


def test_cache_corrupt_file_recovers(tmp_path):
    cache = ResultCache(str(tmp_path))
    spec = _spec(benches=("DYN",))
    ref = run_sweep(spec, cache=cache, parallel=False)

    # Corrupt every stored entry three different ways.
    paths = [os.path.join(root, f)
             for root, _, files in os.walk(str(tmp_path))
             for f in files if f.endswith(".json")]
    assert paths
    breakers = [
        lambda p: open(p, "w").write("{ not json"),
        lambda p: open(p, "w").write(json.dumps({"result": {"cycles": 1}})),
        lambda p: open(p, "w").write(""),
    ]
    for i, p in enumerate(paths):
        breakers[i % len(breakers)](p)

    recovered = ResultCache(str(tmp_path))
    res = run_sweep(spec, cache=recovered, parallel=False)
    assert recovered.hits == 0          # all corrupt entries -> misses
    for m in ref:
        for b in ref[m]:
            assert (dataclasses.asdict(res[m][b])
                    == dataclasses.asdict(ref[m][b]))
    # ... and the rewritten entries serve the next run.
    again = ResultCache(str(tmp_path))
    run_sweep(spec, cache=again, parallel=False)
    assert again.misses == 0


def test_cache_corrupt_entry_quarantined_not_deleted(tmp_path):
    """Read-path hardening regression: a truncated/corrupt cell JSON is a
    miss that *quarantines* the file (``.corrupt`` suffix, counted in
    ``cache.corrupt``) instead of raising or silently deleting the
    evidence, and the key re-simulates/re-writes cleanly. Before the fix
    the file was removed outright (no counter, no post-mortem trail)."""
    cache = ResultCache(str(tmp_path))
    cfg = machines.baseline(8)
    res = sweep_mod.compute_cell("DYN", cfg, n_threads=64, seed=0)
    key = cell_key("DYN", cfg, 64, 0)
    cache.put(key, res)
    path = os.path.join(str(tmp_path), key + ".json")

    with open(path, "w") as f:
        f.write('{"key": "x", "result')        # torn write / disk-full

    assert cache.get(key) is None               # miss, never an exception
    assert cache.corrupt == 1 and cache.misses == 1
    assert os.path.exists(path + ".corrupt")    # quarantined for post-mortem
    assert not os.path.exists(path)
    # The quarantine file never pollutes entry counts or the index ...
    assert cache.count() == 0 and cache.refresh() == 0
    assert not cache.contains(key)
    # ... and the key re-simulates and serves again.
    cache.put(key, res)
    got = cache.get(key)
    assert dataclasses.asdict(got) == dataclasses.asdict(res)
    assert cache.refresh() == 1
    # Surfaced in the session-level cache stats too.
    from repro.core.warpsim import api
    session = api.Session(result_cache=cache)
    assert session.cache_stats()["result_cache"]["corrupt"] == 1


def test_cache_reads_legacy_sharded_layout(tmp_path):
    """Caches written by the PR 1 layout (key[:2]/ shard dirs) stay warm."""
    cache = ResultCache(str(tmp_path))
    spec = _spec(benches=("DYN",))
    ref = run_sweep(spec, cache=cache, parallel=False)

    for name in os.listdir(tmp_path):       # re-shard like the old layout
        if name.endswith(".json"):
            shard = tmp_path / name[:2]
            shard.mkdir(exist_ok=True)
            os.replace(tmp_path / name, shard / name)

    legacy = ResultCache(str(tmp_path))
    res = run_sweep(spec, cache=legacy, parallel=False)
    assert legacy.hits == len(spec.cells()) and legacy.misses == 0
    for m in ref:
        for b in ref[m]:
            assert (dataclasses.asdict(res[m][b])
                    == dataclasses.asdict(ref[m][b]))


# -------------------------------------------------------------------- spec

def test_spec_deterministic_cell_order():
    spec = _spec()
    cells = spec.cells()
    assert cells == spec.cells()
    assert [(m, b) for m, _, b, _, _ in cells] == [
        ("ws8", "BFS"), ("ws8", "BKP"), ("ws8", "DYN"),
        ("SW+", "BFS"), ("SW+", "BKP"), ("SW+", "DYN"),
    ]


def test_warp_size_range_spec():
    spec = SweepSpec.warp_size_range(4, 128, benches=("DYN",))
    names = list(spec.machine_set())
    assert names == ["ws4", "ws8", "ws16", "ws32", "ws64", "ws128"]
    sizes = [cfg.warp_size for cfg in spec.machine_set().values()]
    assert sizes == [4, 8, 16, 32, 64, 128]


def test_multi_seed_sweep_shape():
    # BFS is seed-sensitive (branch outcomes + random neighbor loads).
    spec = _spec(benches=("BFS",), seeds=(0, 1))
    res = run_sweep(spec, parallel=False)
    assert set(res) == {0, 1}
    assert res[0]["ws8"]["BFS"].cycles != res[1]["ws8"]["BFS"].cycles


# ---------------------------------------------------------- parallel exec

def test_parallel_matches_serial():
    spec = _spec()
    serial = run_sweep(spec, parallel=False)
    par = run_sweep(spec, parallel=True, max_workers=2)
    assert list(par) == list(serial)            # deterministic ordering
    for m in serial:
        assert list(par[m]) == list(serial[m])
        for b in serial[m]:
            assert (dataclasses.asdict(par[m][b])
                    == dataclasses.asdict(serial[m][b]))


# ------------------------------------------------- shared-expansion groups

def test_grouped_matches_ungrouped():
    """Expansion sharing must be invisible in the numbers."""
    spec = _spec()
    grouped = run_sweep(spec, parallel=False)
    ungrouped = run_sweep(spec, parallel=False, group_expansion=False)
    for m in ungrouped:
        for b in ungrouped[m]:
            assert (dataclasses.asdict(grouped[m][b])
                    == dataclasses.asdict(ungrouped[m][b]))


def test_sweep_stats_expansion_groups():
    # ws8 and SW+ share an expansion key; ws16 does not.
    spec = _spec(machines={"ws8": machines.baseline(8),
                           "SW+": machines.sw_plus(),
                           "ws16": machines.baseline(16)})
    _res, stats = run_sweep_with_stats(spec, parallel=False)
    assert stats["cells"] == stats["simulated"] == 9
    assert stats["expansion_groups"] == 6       # 3 benches x {ws8/SW+, ws16}
    assert stats["expansions_saved"] == 3
    assert stats["cache_hits"] == stats["cache_misses"] == 0

    _res, stats = run_sweep_with_stats(spec, parallel=False,
                                       group_expansion=False)
    assert stats["expansion_groups"] == 9 and stats["expansions_saved"] == 0


def test_sweep_stats_cache_counters(tmp_path):
    cache = ResultCache(str(tmp_path))
    spec = _spec(benches=("DYN",))
    _res, stats = run_sweep_with_stats(spec, cache=cache, parallel=False)
    assert stats["cache_misses"] == 2
    assert stats["cache_hits"] == 0
    _res, stats = run_sweep_with_stats(
        spec, cache=ResultCache(str(tmp_path)), parallel=False)
    assert stats["cache_hits"] == 2
    assert stats["simulated"] == 0
    assert stats["expansion_groups"] == 0


def test_expansion_cache_lru_bound():
    from repro.core.warpsim.sweep import ExpansionCache
    from repro.core.warpsim.trace import get_workload

    lru = ExpansionCache(maxsize=2)
    cfgs = [machines.baseline(8), machines.baseline(16),
            machines.baseline(32)]
    wl = get_workload("DYN", n_threads=256)
    for cfg in cfgs:
        lru.get(wl, cfg)
    assert len(lru) == 2 and lru.misses == 3    # ws8 evicted (LRU)
    s16 = lru.get(wl, cfgs[1])
    assert lru.hits == 1
    assert s16 is lru.get(wl, cfgs[1])          # cached object, not a copy
    lru.get(wl, cfgs[0])                        # re-expand after eviction
    assert lru.misses == 4 and len(lru) == 2
    lru.clear()
    assert len(lru) == 0 and lru.hits == lru.misses == 0


def test_expansion_cache_lru_recency_order():
    """A hit refreshes recency: the least-recently-USED entry is evicted,
    not the least-recently-inserted one."""
    from repro.core.warpsim.sweep import ExpansionCache
    from repro.core.warpsim.trace import get_workload

    lru = ExpansionCache(maxsize=2)
    wl = get_workload("DYN", n_threads=256)
    ws8, ws16, ws32 = (machines.baseline(w) for w in (8, 16, 32))
    lru.get(wl, ws8)
    lru.get(wl, ws16)
    lru.get(wl, ws8)                            # refresh ws8
    lru.get(wl, ws32)                           # evicts ws16, not ws8
    hits0 = lru.hits
    lru.get(wl, ws8)
    assert lru.hits == hits0 + 1                # ws8 still cached
    lru.get(wl, ws16)
    assert lru.misses == 4                      # ws16 was the evictee


def test_expansion_cache_shared_across_variants():
    """ws8 and SW+ collide on the expansion key -> one stored stream."""
    from repro.core.warpsim.sweep import ExpansionCache
    from repro.core.warpsim.trace import get_workload

    lru = ExpansionCache()
    wl = get_workload("BFS", n_threads=256)
    a = lru.get(wl, machines.baseline(8))
    b = lru.get(wl, machines.sw_plus())
    assert a is b and lru.hits == 1 and lru.misses == 1


def test_expansion_cache_aggregates_supplied_trace():
    """A trace passed (directly or lazily) must feed the miss path; the
    lazy supplier must not run on a hit."""
    from repro.core.warpsim.divergence import build_thread_trace
    from repro.core.warpsim.sweep import ExpansionCache
    from repro.core.warpsim.trace import get_workload

    lru = ExpansionCache()
    wl = get_workload("BFS", n_threads=256)
    trace = build_thread_trace(wl)
    calls = []

    def supplier():
        calls.append(1)
        return trace

    a = lru.get(wl, machines.baseline(8), trace_fn=supplier)
    assert calls == [1] and lru.misses == 1
    b = lru.get(wl, machines.baseline(8), trace_fn=supplier)
    assert calls == [1] and lru.hits == 1       # hit: supplier untouched
    assert a is b


# -------------------------------------------------------- trace cache (LRU)

def test_trace_cache_lru_and_counters():
    from repro.core.warpsim.sweep import TraceCache
    from repro.core.warpsim.trace import get_workload

    lru = TraceCache(maxsize=2)
    wls = [get_workload(b, n_threads=256) for b in ("BFS", "BKP", "DYN")]
    for wl in wls:
        lru.get(wl)
    assert len(lru) == 2 and lru.misses == 3 and lru.builds == 3
    assert lru.hits == 0
    t = lru.get(wls[1])                         # BKP still cached
    assert lru.hits == 1 and t is lru.get(wls[1])
    lru.get(wls[0])                             # BFS evicted -> rebuild
    assert lru.misses == 4 and lru.builds == 4 and len(lru) == 2
    lru.clear()
    assert len(lru) == 0
    assert lru.hits == lru.misses == lru.builds == lru.disk_hits == 0


def test_trace_cache_keyed_by_threads_and_seed():
    from repro.core.warpsim.sweep import TraceCache
    from repro.core.warpsim.trace import get_workload

    lru = TraceCache()
    a = lru.get(get_workload("BFS", n_threads=256))
    b = lru.get(get_workload("BFS", n_threads=512))
    c = lru.get(get_workload("BFS", n_threads=256, seed=1))
    assert lru.misses == 3 and len({id(a), id(b), id(c)}) == 3
    assert a is lru.get(get_workload("BFS", n_threads=256))


def test_trace_cache_disk_roundtrip(tmp_path):
    import numpy as np

    from repro.core.warpsim.divergence import aggregate_stream
    from repro.core.warpsim.sweep import TraceCache
    from repro.core.warpsim.trace import get_workload

    root = str(tmp_path / "traces")
    wl = get_workload("BFS", n_threads=256)
    writer = TraceCache()
    built = writer.get(wl, root=root)
    assert writer.builds == 1
    files = os.listdir(root)
    assert len(files) == 1 and files[0].endswith(".npz")

    # A fresh cache (fresh process stand-in) loads the snapshot instead of
    # rebuilding, and the loaded trace aggregates to the identical stream.
    reader = TraceCache()
    loaded = reader.get(wl, root=root)
    assert reader.disk_hits == 1 and reader.builds == 0
    cfg = machines.baseline(8)
    ref = aggregate_stream(built, cfg)
    got = aggregate_stream(loaded, cfg)
    assert ref.n_warps == got.n_warps
    for f in ("warp", "issue", "tins", "lanes", "kind", "maccs",
              "blk_off", "blk_len", "blocks", "nbytes", "op_start"):
        assert np.array_equal(getattr(ref, f), getattr(got, f)), f


def test_trace_cache_corrupt_snapshot_rebuilds(tmp_path):
    from repro.core.warpsim.sweep import TraceCache
    from repro.core.warpsim.trace import get_workload

    root = str(tmp_path / "traces")
    wl = get_workload("DYN", n_threads=256)
    TraceCache().get(wl, root=root)
    (path,) = [os.path.join(root, f) for f in os.listdir(root)]
    with open(path, "w") as f:
        f.write("not an npz")

    recovered = TraceCache()
    recovered.get(wl, root=root)
    assert recovered.builds == 1 and recovered.disk_hits == 0
    assert not os.path.exists(path) or os.path.getsize(path) > 20
    # ... and the rewritten snapshot serves the next fresh cache.
    again = TraceCache()
    again.get(wl, root=root)
    assert again.disk_hits == 1 and again.builds == 0


# ------------------------------------------------------ trace-family sweeps

def test_share_traces_off_matches_default():
    """Trace sharing must be invisible in the numbers."""
    spec = _spec()
    shared = run_sweep(spec, parallel=False)
    unshared = run_sweep(spec, parallel=False, share_traces=False)
    for m in unshared:
        for b in unshared[m]:
            assert (dataclasses.asdict(shared[m][b])
                    == dataclasses.asdict(unshared[m][b]))


def test_sweep_stats_trace_families():
    # Two benches x two expansion keys (ws8/SW+ share, ws16 alone):
    # 2 families, 4 expansion groups, 2 of them riding a shared trace.
    spec = _spec(benches=("BFS", "DYN"),
                 machines={"ws8": machines.baseline(8),
                           "SW+": machines.sw_plus(),
                           "ws16": machines.baseline(16)})
    sweep_mod.TRACE_CACHE.clear()
    sweep_mod.EXPANSION_CACHE.clear()
    _res, stats = run_sweep_with_stats(spec, parallel=False)
    assert stats["trace_families"] == 2
    assert stats["expansion_groups"] == 4
    assert stats["traces_shared"] == 2
    assert stats["trace_cache_misses"] == 2     # one build per family
    assert stats["trace_cache_hits"] == 2       # second key rides the first
    # One expansion-LRU probe per group (SW+ shares ws8's group outright).
    assert stats["expansion_cache_misses"] == 4
    assert stats["expansion_cache_hits"] == 0

    # Serial re-sweep in the same process: streams come from the expansion
    # LRU, the trace layer is never touched (lazy trace_fn).
    _res, stats = run_sweep_with_stats(spec, parallel=False)
    assert stats["expansion_cache_hits"] == 4
    assert stats["trace_cache_hits"] == stats["trace_cache_misses"] == 0

    _res, stats = run_sweep_with_stats(spec, parallel=False,
                                       share_traces=False)
    assert stats["traces_shared"] == 0


def test_sweep_persist_traces_writes_beside_result_cache(tmp_path):
    spec = _spec(benches=("DYN",))
    sweep_mod.TRACE_CACHE.clear()
    sweep_mod.EXPANSION_CACHE.clear()   # a warm stream would skip the trace
    run_sweep(spec, cache=ResultCache(str(tmp_path)), parallel=False,
              persist_traces=True)
    tdir = tmp_path / "traces"
    assert tdir.is_dir() and len(list(tdir.glob("*.npz"))) == 1

    # A fresh process stand-in (cleared LRU) cold-starts from the snapshot
    # ... and the snapshot dir never confuses the result-cache listing.
    sweep_mod.TRACE_CACHE.clear()
    cache = ResultCache(str(tmp_path))
    ref = run_sweep(spec, cache=cache, parallel=False, persist_traces=True)
    assert cache.hits == len(spec.cells())
    sweep_mod.TRACE_CACHE.clear()
    _res2, stats = run_sweep_with_stats(
        _spec(benches=("DYN",), n_threads=128),
        cache=ResultCache(str(tmp_path)), parallel=False,
        persist_traces=True)
    assert stats["trace_disk_hits"] == 0        # new key
    sweep_mod.TRACE_CACHE.clear()
    run_sweep(_spec(benches=("DYN",), n_threads=128, seeds=(0,)),
              parallel=False)
    # default sweeps (no cache) never touch the snapshot dir
    assert sorted(f.name for f in tmp_path.iterdir() if f.is_dir()) == [
        "traces"]
    del ref


# ------------------------------------------- cross-process index adoption

def test_result_cache_sees_external_writes(tmp_path):
    """Regression: the one-shot scandir index must not turn cells written
    by *other* processes after startup into permanent misses.

    A long-lived reader (service, queue worker) and a writer are stood in
    for by two instances over one directory: the reader snapshots its
    index first, the writer persists a cell afterwards, and the reader
    must serve it (fallback existence probe + adoption), not re-simulate.
    """
    spec = _spec(benches=("DYN",))
    (mname, cfg, bench, n_threads, seed) = spec.cells()[0]
    key = cell_key(bench, cfg, n_threads, seed)

    reader = ResultCache(str(tmp_path))
    assert reader.get(key) is None          # forces the index snapshot
    writer = ResultCache(str(tmp_path))     # the "other worker"
    ref = run_sweep(spec, cache=writer, parallel=False)

    got = reader.get(key)
    assert got is not None, "externally written cell must be adopted"
    assert reader.adopted >= 1
    assert (dataclasses.asdict(got)
            == dataclasses.asdict(ref[mname][bench]))
    # Adopted entries are indexed: the next probe is a plain index hit.
    adopted0 = reader.adopted
    assert reader.get(key) is not None and reader.adopted == adopted0


def test_result_cache_contains_and_refresh(tmp_path):
    spec = _spec(benches=("DYN",))
    cells = spec.cells()
    keys = [cell_key(b, c, nt, s) for _, c, b, nt, s in cells]

    reader = ResultCache(str(tmp_path))
    assert not reader.contains(keys[0]) and reader.misses == 0
    assert reader.count() == 0
    run_sweep(spec, cache=ResultCache(str(tmp_path)), parallel=False)
    # refresh() re-scans wholesale (the service /stats path) ...
    assert reader.refresh() == len(cells)
    # ... and contains() answers without touching hit/miss counters.
    assert all(reader.contains(k) for k in keys)
    assert reader.hits == reader.misses == 0


# --------------------------------------------- atomic trace persistence

def test_trace_store_concurrent_writers_publish_complete_snapshots(
        tmp_path, monkeypatch):
    """Regression: two same-process writers persisting one trace family
    must never publish a torn ``.npz``.

    The pre-fix code derived the tmp name from the pid alone, so two
    *threads* (the sweep service) shared one tmp file: the orchestration
    below holds writer A between its completed write and its atomic
    rename while writer B re-opens and half-fills "A's" tmp file — with a
    shared name, A then publishes B's torn prefix. With per-writer tmp
    files (mkstemp) every published snapshot is complete at all times.
    """
    from repro.core.warpsim.sweep import TraceCache, _TRACE_FIELDS
    from repro.core.warpsim.trace import get_workload
    from repro.core.warpsim.divergence import build_thread_trace

    root = str(tmp_path / "traces")
    wl = get_workload("DYN", n_threads=128)
    trace = build_thread_trace(wl)
    cache = TraceCache()
    path = cache._path(wl, root)

    a_ready = threading.Event()       # A wrote + closed, about to rename
    b_half = threading.Event()        # B flushed a partial write
    published = threading.Event()     # A's rename happened
    reader_done = threading.Event()   # main thread inspected the file

    orig_savez, orig_replace = np.savez, os.replace

    def savez(f, **arrays):
        if threading.current_thread().name == "writer-b":
            buf = io.BytesIO()
            orig_savez(buf, **arrays)
            data = buf.getvalue()
            f.write(data[:100])
            f.flush()
            b_half.set()
            assert reader_done.wait(10)
            f.write(data[100:])
        else:
            orig_savez(f, **arrays)

    def replace(src, dst):
        if threading.current_thread().name == "writer-a":
            a_ready.set()
            assert b_half.wait(10)
            orig_replace(src, dst)
            published.set()
        else:
            orig_replace(src, dst)

    monkeypatch.setattr(np, "savez", savez)
    monkeypatch.setattr(os, "replace", replace)

    ta = threading.Thread(target=cache._store, args=(wl, root, trace),
                          name="writer-a")
    ta.start()
    assert a_ready.wait(10)
    tb = threading.Thread(target=cache._store, args=(wl, root, trace),
                          name="writer-b")
    tb.start()
    assert published.wait(10)
    try:
        with np.load(path) as data:
            assert set(data.files) == set(_TRACE_FIELDS)
    finally:
        reader_done.set()
        ta.join(10)
        tb.join(10)


# -------------------------------------------------- per-run stats snapshot

def test_run_sweep_with_stats_snapshot(tmp_path):
    spec = _spec(benches=("DYN",))
    res, stats = run_sweep_with_stats(
        spec, cache=ResultCache(str(tmp_path)), parallel=False)
    assert res["SW+"]["DYN"].cycles > 0
    assert stats["cells"] == 2 and stats["simulated"] == 2
    assert stats["cache_hits"] == 0 and stats["cache_misses"] == 2
    # The snapshot is private: a later sweep hands out a fresh dict while
    # earlier callers' dicts are untouched.
    first = stats
    _res2, stats2 = run_sweep_with_stats(
        spec, cache=ResultCache(str(tmp_path)), parallel=False)
    assert stats2["cache_hits"] == 2 and stats2["simulated"] == 0
    assert first["simulated"] == 2


def test_last_sweep_stats_alias_is_deprecated(tmp_path):
    """The retired global stays readable for one release of warning: the
    access itself raises DeprecationWarning and the dict carries the most
    recently published run's numbers."""
    spec = _spec(benches=("DYN",))
    _res, stats = run_sweep_with_stats(
        spec, cache=ResultCache(str(tmp_path)), parallel=False)
    with pytest.warns(DeprecationWarning, match="run_sweep_with_stats"):
        alias = sweep_mod.LAST_SWEEP_STATS
    assert dict(alias) == stats
    # Attribute passthrough stays strict for everything else.
    with pytest.raises(AttributeError):
        sweep_mod.NO_SUCH_ATTRIBUTE


# ------------------------------------------------------- locked LRU smoke

@pytest.mark.parametrize("cache_cls", ["expansion", "trace"])
def test_lru_caches_thread_safe_under_contention(cache_cls):
    """Hammer one LRU from many threads; pre-fix the unlocked OrderedDict
    interleavings corrupt recency state (KeyError from move_to_end racing
    popitem) and overshoot maxsize."""
    from repro.core.warpsim.sweep import ExpansionCache, TraceCache
    from repro.core.warpsim.trace import get_workload

    wls = [get_workload(b, n_threads=128)
           for b in ("BFS", "BKP", "DYN", "MTM", "NQU")]
    if cache_cls == "expansion":
        lru = ExpansionCache(maxsize=2)
        cfg = machines.baseline(8)
        probe = lambda wl: lru.get(wl, cfg)             # noqa: E731
    else:
        lru = TraceCache(maxsize=2)
        probe = lambda wl: lru.get(wl)                  # noqa: E731
    for wl in wls:                                      # pre-warm builds
        probe(wl)
    errors = []

    def worker(i):
        try:
            for j in range(100):
                probe(wls[(i + j) % len(wls)])
        except Exception as e:        # noqa: BLE001 — the assertion target
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert errors == []
    assert len(lru) <= 2


# ------------------------------------------------- pallas family batching

pallas_required = pytest.mark.skipif(
    not __import__("repro.core.warpsim._pallas",
                   fromlist=["_pallas"]).available(),
    reason="jax not importable (or WARPSIM_PALLAS=0)")


@pallas_required
def test_pallas_sweep_one_launch_per_family():
    """engine="pallas" batches a whole trace family — every expansion
    group x machine variant of one (bench, n_threads, seed) — into a
    single device launch, and the numbers stay bit-identical to fast."""
    from repro.core.warpsim import _pallas

    spec = _spec(benches=("BFS", "DYN"),
                 machines={"ws8": machines.baseline(8),
                           "SW+": machines.sw_plus(),
                           "ws16": machines.baseline(16)})
    before = _pallas.launch_count()
    res, stats = run_sweep_with_stats(spec, parallel=False,
                                      engine="pallas")
    # One launch per family: 2 benches x 1 n_threads x 1 seed.
    assert stats["family_launches"] == 2
    assert _pallas.launch_count() - before == 2

    ref, ref_stats = run_sweep_with_stats(spec, parallel=False,
                                          engine="fast")
    assert ref_stats["family_launches"] == 0    # counter is pallas-only
    for m in ref:
        for b in ref[m]:
            assert (dataclasses.asdict(res[m][b])
                    == dataclasses.asdict(ref[m][b]))


@pallas_required
def test_pallas_kill_switch_falls_back_per_group(monkeypatch):
    """WARPSIM_PALLAS=0 is re-read per launch: a sweep asked for pallas
    degrades to the per-group fallback (zero family launches) and still
    returns correct results — no restart, no error."""
    from repro.core.warpsim import _pallas

    monkeypatch.setenv("WARPSIM_PALLAS", "0")
    monkeypatch.setattr(_pallas, "_warned", False, raising=False)
    spec = _spec(benches=("DYN",))
    before = _pallas.launch_count()
    with pytest.warns(RuntimeWarning, match="pallas"):
        res, stats = run_sweep_with_stats(spec, parallel=False,
                                          engine="pallas")
    assert stats["family_launches"] == 0
    assert _pallas.launch_count() == before
    ref = run_sweep(spec, parallel=False, engine="fast")
    for m in ref:
        for b in ref[m]:
            assert (dataclasses.asdict(res[m][b])
                    == dataclasses.asdict(ref[m][b]))


@pallas_required
def test_auto_engine_never_selects_pallas():
    """engine="auto" resolves to native/fast even with jax importable:
    the device engine is strictly opt-in (on CPU hosts the XLA loop
    loses to the compiled/flat engines)."""
    from repro.core.warpsim import _pallas
    from repro.core.warpsim.divergence import expand_stream
    from repro.core.warpsim.timing import simulate
    from repro.core.warpsim.trace import get_workload

    assert _pallas.available() is True      # precondition: it *could* run
    cfg = machines.baseline(8)
    wl = get_workload("BFS", n_threads=128)
    stream = expand_stream(wl, cfg)
    before = _pallas.launch_count()
    auto = simulate(wl.name, stream, cfg, engine="auto")
    assert _pallas.launch_count() == before
    assert (dataclasses.asdict(auto)
            == dataclasses.asdict(simulate(wl.name, stream, cfg,
                                           engine="fast")))
    # The sweep layer inherits the same resolution.
    _res, stats = run_sweep_with_stats(_spec(benches=("BFS",)),
                                       parallel=False, engine="auto")
    assert stats["family_launches"] == 0
    assert _pallas.launch_count() == before


@pallas_required
def test_pallas_x64_scope_is_thread_local():
    """Two threads run device launches while a third jits an f32
    function: the launches stay exact and the third thread never sees
    64-bit types (the x64 scope is per thread, not a process flag)."""
    import jax
    import jax.numpy as jnp

    from repro.core.warpsim.divergence import expand_stream
    from repro.core.warpsim.timing import simulate
    from repro.core.warpsim.trace import get_workload

    cfg = machines.sw_plus()
    wl = get_workload("DYN", n_threads=128)
    stream = expand_stream(wl, cfg)
    want = dataclasses.asdict(simulate(wl.name, stream, cfg, engine="fast"))
    simulate(wl.name, stream, cfg, engine="pallas")     # compile once
    f32 = jax.jit(lambda x: x * 2.0 + 1.0)
    start = threading.Barrier(3)
    errors, dtypes = [], set()

    def launcher():
        try:
            start.wait()
            for _ in range(4):
                got = simulate(wl.name, stream, cfg, engine="pallas")
                assert dataclasses.asdict(got) == want
        except Exception as e:      # surfaced below
            errors.append(e)

    def plain():
        try:
            start.wait()
            for i in range(40):
                dtypes.add(f32(np.full(4, i, np.float32)).dtype)
                dtypes.add(jnp.asarray(1.0).dtype)
                assert jax.config.jax_enable_x64 is False
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=launcher) for _ in range(2)]
    threads.append(threading.Thread(target=plain))
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert errors == []
    assert dtypes == {np.dtype("float32")}


@pallas_required
def test_pallas_failed_launch_raises_instead_of_degrading(monkeypatch,
                                                          tmp_path):
    """A compile or launch failure surfaces with its message: no silent
    host-engine fallback at the cell, sweep or service layer, and the
    daemon's /healthz reports the failure instead of another engine."""
    from repro.core.warpsim import _pallas
    from repro.core.warpsim.divergence import expand_stream
    from repro.core.warpsim.service import SweepService
    from repro.core.warpsim.timing import simulate
    from repro.core.warpsim.trace import get_workload

    def broken(*_dims):
        def launch(_stacked):
            raise RuntimeError("Mosaic refused the kernel (injected)")
        return launch

    monkeypatch.setattr(_pallas, "_get_launch", broken)
    monkeypatch.setattr(_pallas, "_probe_result", None)
    monkeypatch.setattr(_pallas, "_import_error", None)
    spec = _spec(benches=("DYN",))
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        run_sweep_with_stats(spec, parallel=False, engine="pallas")
    cfg = machines.baseline(8)
    wl = get_workload("DYN", n_threads=128)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        simulate(wl.name, expand_stream(wl, cfg), cfg, engine="pallas")
    svc = SweepService(str(tmp_path), engine="pallas", persist_traces=False)
    h = svc.healthz()
    assert h["ok"] is False and h["engine"] == "pallas"
    assert h["pallas"]["probed"] is False
    assert "Mosaic refused" in h["pallas"]["error"]


def test_no_fork_pool_once_jax_backend_is_up(monkeypatch):
    """A process whose jax backend is initialised (it may hold the chip)
    sweeps serially even when asked for a pool; without a backend the
    pool is still used."""
    import jax

    jax.devices()                       # bring the backend up
    assert sweep_mod._jax_backend_up() is True

    class NoPool:
        def __init__(self, *a, **kw):
            raise AssertionError("process pool used after jax init")

    monkeypatch.setattr(sweep_mod.concurrent.futures,
                        "ProcessPoolExecutor", NoPool)
    spec = _spec()
    par = run_sweep(spec, parallel=True, max_workers=2)
    serial = run_sweep(spec, parallel=False)
    for m in serial:
        for b in serial[m]:
            assert (dataclasses.asdict(par[m][b])
                    == dataclasses.asdict(serial[m][b]))
    monkeypatch.setattr(sweep_mod, "_jax_backend_up", lambda: False)
    with pytest.raises(AssertionError, match="process pool used"):
        run_sweep(spec, parallel=True, max_workers=2)


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch):
    """The compile-cache helper honours JAX_COMPILATION_CACHE_DIR and
    sets nothing; unset, it picks one fixed path inside the checkout."""
    import jax

    from repro import compat

    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compat.init_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = compat.init_compile_cache()
        assert first == compat.init_compile_cache() == \
            compat.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == first
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            sweep_mod.__file__)))
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(root)))
        assert os.path.dirname(first) == checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", old)

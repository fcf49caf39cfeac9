"""Golden regression locks for the warp-size simulator.

Two layers of protection:

* The batched fast engine must be *bit-compatible* with the reference
  event-loop engine: every ``SimResult`` field identical, over every paper
  machine and a divergence/coalescing/store-heavy bench mix.
* The paper-claim headline numbers (``suite_summary``) and a set of raw
  per-cell counters are locked to golden constants on a small fixed-seed
  workload, so any unintended model change — in expansion, coalescing,
  timing, or the sweep plumbing — fails loudly here rather than shifting
  figures silently.

Golden constants were produced by ``runner.run_suite(paper_suite(),
n_threads=512, seed=0)`` at the model version that introduced the sweep
subsystem (coalesce.generate_addresses uses stable region hashing, so the
numbers are reproducible across processes and machines).
"""

import dataclasses

import numpy as np
import pytest

from repro.core.warpsim import _native, _pallas, machines, runner
from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim.divergence import (
    KIND_COMPUTE, KIND_LOAD, KIND_STORE, WarpStream, aggregate_stream,
    build_thread_trace, expand_stream, expand_stream_single,
)
from repro.core.warpsim.sweep import expansion_key
from repro.core.warpsim.timing import loop_result, simulate, stream_totals
from repro.core.warpsim.trace import (
    Branch, Compute, Loop, Mem, Workload, get_workload,
)

# Benches exercising every op path: divergence (BFS), dense strided loads
# (BKP), uncoalesced stores (MTM), shared-region reuse + broadcast (DYN),
# stencil regions (SR2).
GOLDEN_BENCHES = ("BFS", "BKP", "MTM", "DYN", "SR2")
N_THREADS = 512

# Every non-reference engine must replay the event loop bit-for-bit; the
# native engine only participates where the compiled core is available,
# the pallas engine where jax imports (bit-identical, no tolerance: the
# device loop runs the same IEEE-754 double ops in the same order).
FAST_ENGINES = ["fast", "fast_nested"] + (
    ["native"] if _native.available() else []) + (
    ["pallas"] if _pallas.available() else [])


@pytest.fixture(scope="module")
def small_suite():
    return runner.run_suite(machines.paper_suite(),
                            benches=GOLDEN_BENCHES,
                            n_threads=N_THREADS, parallel=False)


# ------------------------------------------------ engine bit-compatibility

@pytest.mark.parametrize("engine", FAST_ENGINES)
@pytest.mark.parametrize("mname", list(machines.paper_suite()))
@pytest.mark.parametrize("bench", GOLDEN_BENCHES)
def test_fast_engine_matches_event_loop(mname, bench, engine):
    cfg = machines.paper_suite()[mname]
    wl = get_workload(bench, n_threads=N_THREADS)
    stream = expand_stream(wl, cfg)
    fast = simulate(wl.name, stream, cfg, engine=engine)
    event = simulate(wl.name, stream, cfg, engine="event")
    assert dataclasses.asdict(fast) == dataclasses.asdict(event)


@pytest.mark.parametrize("engine", FAST_ENGINES)
def test_fast_engine_accepts_legacy_warp_ops(engine):
    """The fast paths give identical results fed WarpOp lists or streams."""
    cfg = machines.sw_plus()
    wl = get_workload("BFS", n_threads=N_THREADS)
    stream = expand_stream(wl, cfg)
    from_stream = simulate(wl.name, stream, cfg, engine=engine)
    from_ops = simulate(wl.name, stream.to_warp_ops(), cfg, engine=engine)
    assert dataclasses.asdict(from_stream) == dataclasses.asdict(from_ops)


def _hand_stream(warps) -> WarpStream:
    """A WarpStream from per-warp ``[(kind, blocks), ...]``; every op
    issues in 1 cycle with 8 lanes, every transaction touches 64 B."""
    ops = [(w, kind, blocks) for w, wops in enumerate(warps)
           for kind, blocks in wops]
    lens = np.array([len(b) for _, _, b in ops], dtype=np.int64)
    ones = np.ones(len(ops), dtype=np.int64)
    blocks = np.concatenate([np.asarray(b, dtype=np.int64)
                             for _, _, b in ops])
    return WarpStream(
        n_warps=len(warps),
        warp=np.array([w for w, _, _ in ops], dtype=np.int64),
        issue=ones, tins=8 * ones, lanes=8 * ones,
        kind=np.array([k for _, k, _ in ops], dtype=np.int8),
        maccs=8 * lens, blk_off=np.cumsum(lens) - lens, blk_len=lens,
        blocks=blocks, nbytes=np.full(len(blocks), 64, dtype=np.int64),
        op_start=np.cumsum([0] + [len(w) for w in warps]).astype(np.int64))


@pytest.mark.skipif(not _pallas.available(), reason="jax does not import")
def test_family_launch_reads_the_last_l1_row_like_the_event_loop():
    """Two units in one launch whose loads land in the last set of the
    last SM, i.e. the last row of the flat L1 tables. The 3-way unit pads
    to 4 ways, so its row read has a masked way; it evicts its LRU line
    where the 4-way unit still has room. Every field equals the event
    loop's."""
    ld, st = KIND_LOAD, KIND_STORE
    stream = _hand_stream([
        [(KIND_COMPUTE, []), (ld, [0, 4])],                # warp 0, SM 0
        [(ld, [3, 7, 11]), (ld, [3]), (ld, [15]),          # warp 1, SM 1,
         (ld, [7]), (st, [3])],                            # set 3
    ])
    cfgs = [MachineConfig(name="ways3", num_sms=2, l1_ways=3,
                          l1_size_bytes=64 * 3 * 4),
            MachineConfig(name="ways4.sw+", num_sms=2, l1_ways=4,
                          l1_size_bytes=64 * 4 * 4, ideal_coalescing=True)]
    units = [(_pallas._stream_cols(stream), _pallas._cfg_scalars(c))
             for c in cfgs]
    n_sms, _, n_sets, ways, _ = _pallas.pack_units(units)[0]
    assert (n_sms, n_sets, ways) == (2, 4, 4)
    event = [simulate("edge", stream, c, engine="event") for c in cfgs]
    # The 3-way unit misses on block 7 after evicting it; the 4-way hits.
    assert [e.l1_hits for e in event] == [1, 2]
    loops = _pallas._launch_units(units, count_launch=False)
    for cfg, loop, ref in zip(cfgs, loops, event):
        got = loop_result("edge", cfg, loop, stream_totals(stream))
        assert dataclasses.asdict(got) == dataclasses.asdict(ref), cfg.name


# ------------------------------------------------------------ expansion key

_STREAM_FIELDS = ("warp", "issue", "tins", "lanes", "kind", "maccs",
                  "blk_off", "blk_len", "blocks", "nbytes", "op_start")


def _streams_equal(a: WarpStream, b: WarpStream) -> bool:
    if a.n_warps != b.n_warps:
        return False
    return all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in _STREAM_FIELDS)


def _assert_streams_equal(got: WarpStream, ref: WarpStream, tag) -> None:
    assert got.n_warps == ref.n_warps, tag
    for f in _STREAM_FIELDS:
        assert np.array_equal(getattr(got, f), getattr(ref, f)), (tag, f)


# ----------------------------------------------- two-phase expansion paths

# Aggregation implementations that must replay the single-phase walk
# bit-for-bit; the native core only participates where it compiled.
AGG_IMPLS = ["python"] + (["native"] if _native.available() else [])


@pytest.mark.parametrize("impl", AGG_IMPLS)
@pytest.mark.parametrize("mname", list(machines.paper_suite()))
@pytest.mark.parametrize("bench", GOLDEN_BENCHES)
def test_two_phase_expansion_matches_single_phase(bench, mname, impl):
    """trace build + per-key aggregation == the retired single-phase walk,
    every WarpStream column bit-identical, for every paper machine."""
    cfg = machines.paper_suite()[mname]
    wl = get_workload(bench, n_threads=N_THREADS)
    trace = build_thread_trace(wl)
    ref = expand_stream_single(wl, cfg)
    got = aggregate_stream(trace, cfg, impl=impl)
    _assert_streams_equal(got, ref, (bench, mname, impl))


def test_expand_stream_reuses_supplied_trace():
    """expand_stream(trace=...) must equal expand_stream building its own,
    and one trace must serve every expansion key of the workload."""
    wl = get_workload("BFS", n_threads=N_THREADS)
    trace = build_thread_trace(wl)
    for cfg in machines.paper_suite().values():
        _assert_streams_equal(expand_stream(wl, cfg, trace=trace),
                              expand_stream(wl, cfg), cfg.name)


def test_expansion_key_collides_iff_streams_identical():
    """expansion_key(a) == expansion_key(b) <=> identical expand_stream.

    Walks every MachineConfig field with an alternate value: fields inside
    the expansion key must change both the key and the expanded stream;
    fields outside it must change neither stream nor key. BFS exercises
    every mechanism a key field feeds (branch divergence for the MIMD
    flag, loads+stores for transaction bytes, issue occupancy for
    warp/SIMD width). Adding a MachineConfig field without classifying it
    here fails the exhaustiveness check.
    """
    base = MachineConfig()
    wl = get_workload("BFS", n_threads=256)
    base_stream = expand_stream(wl, base)

    # field -> (alternate value, participates in the expansion key?)
    alternates = {
        "name": ("other", False),
        "warp_size": (64, True),
        "simd_width": (4, True),
        "ideal_coalescing": (True, False),
        "mimd": (True, True),
        "num_sms": (4, False),
        "threads_per_sm": (2048, False),
        "pipeline_depth": (12, False),
        "core_clock_ghz": (2.0, False),
        "num_mem_ctrls": (8, False),
        "dram_bw_gbps": (100.0, False),
        "dram_latency_cycles": (100, False),
        "transaction_bytes": (128, True),
        "l1_size_bytes": (96 * 1024, False),
        "l1_ways": (4, False),
        "l1_hit_latency": (2, False),
    }
    fields = {f.name for f in dataclasses.fields(MachineConfig)}
    assert fields == set(alternates), "classify new fields for expansion_key"

    k0 = expansion_key(base)
    for fname, (alt, in_key) in alternates.items():
        cfg = dataclasses.replace(base, **{fname: alt})
        stream = expand_stream(wl, cfg)
        if in_key:
            assert expansion_key(cfg) != k0, fname
            assert not _streams_equal(stream, base_stream), fname
        else:
            assert expansion_key(cfg) == k0, fname
            assert _streams_equal(stream, base_stream), fname


# ------------------------------------- property-based engine equivalence
# Guarded import: hypothesis is optional — the golden locks above must run
# (and fail loudly) even on hosts without it, so no module-level skip.

try:
    import hypothesis as hyp
    import hypothesis.strategies as hyp_st
except ImportError:
    hyp = None


if hyp is None:
    @pytest.mark.skip(reason="optional dep: property test needs hypothesis")
    def test_engines_bit_identical_on_random_workloads():
        pass


def _program_strategy():
    computes = hyp_st.builds(Compute, n=hyp_st.integers(1, 8))
    mems = hyp_st.builds(
        Mem,
        pattern=hyp_st.sampled_from(
            ["coalesced", "strided", "random", "broadcast"]),
        is_load=hyp_st.booleans(),
        stride=hyp_st.sampled_from([4, 8, 64, 128]),
        working_set=hyp_st.sampled_from([1 << 12, 1 << 16]),
        irregularity=hyp_st.sampled_from([0.0, 0.25]),
        region=hyp_st.sampled_from([None, "hyp_a", "hyp_b"]),
        offset=hyp_st.sampled_from([0, -64, 64]),
    )
    stmt = hyp_st.recursive(
        computes | mems,
        lambda ch: hyp_st.one_of(
            hyp_st.builds(
                Branch,
                p_taken=hyp_st.floats(0.05, 0.95),
                corr=hyp_st.floats(0.0, 0.95),
                then=hyp_st.lists(ch, min_size=1, max_size=3).map(tuple),
                orelse=hyp_st.lists(ch, min_size=0, max_size=2).map(tuple),
            ),
            hyp_st.builds(
                Loop,
                trips=hyp_st.integers(1, 3),
                body=hyp_st.lists(ch, min_size=1, max_size=3).map(tuple),
            ),
        ),
        max_leaves=10,
    )
    return hyp_st.lists(stmt, min_size=1, max_size=4)


def _machine_strategy_draw(draw):
    simd = draw(hyp_st.sampled_from([4, 8]))
    warp = draw(hyp_st.sampled_from([4, 8, 16, 32, 64]))
    if warp % simd and warp > simd:
        warp = simd
    return MachineConfig(
        name=f"hyp_ws{warp}",
        warp_size=warp,
        simd_width=simd,
        # Includes the SW+/LW+ idealizations and non-default memory
        # systems; fractional bandwidth exercises non-representable
        # service times (float addition order must still agree).
        ideal_coalescing=draw(hyp_st.booleans()),
        mimd=draw(hyp_st.booleans()),
        num_sms=draw(hyp_st.sampled_from([1, 2, 3])),
        pipeline_depth=draw(hyp_st.sampled_from([8, 24])),
        core_clock_ghz=draw(hyp_st.sampled_from([1.3, 1.7])),
        num_mem_ctrls=draw(hyp_st.sampled_from([1, 3, 6])),
        dram_bw_gbps=draw(hyp_st.sampled_from([76.8, 100.0, 33.3])),
        dram_latency_cycles=draw(hyp_st.sampled_from([100, 420])),
        l1_size_bytes=draw(hyp_st.sampled_from([4096, 48 * 1024])),
        l1_ways=draw(hyp_st.sampled_from([2, 8])),
        l1_hit_latency=draw(hyp_st.sampled_from([1, 2])),
    )


if hyp is not None:
    @hyp.given(
        program=_program_strategy(),
        cfg=hyp_st.composite(_machine_strategy_draw)(),
        n_warp_groups=hyp_st.sampled_from([4, 8, 16]),
        seed=hyp_st.integers(0, 2**31 - 1),
    )
    @hyp.settings(max_examples=25, deadline=None,
                  suppress_health_check=[hyp.HealthCheck.too_slow])
    def test_engines_bit_identical_on_random_workloads(
            program, cfg, n_warp_groups, seed):
        """Both halves of the model locked on arbitrary workloads ×
        machine configs (MIMD/LW+, ideal and baseline coalescing, odd
        memory geometries included): expansion — single-phase walk ==
        two-phase Python aggregation == native aggregation core, every
        WarpStream column bit-identical — and timing — fast ==
        fast_nested == native == event, every SimResult field compared
        exactly."""
        wl = Workload("HYP", program,
                      n_threads=cfg.warp_size * n_warp_groups, seed=seed)
        stream = expand_stream_single(wl, cfg)
        trace = build_thread_trace(wl)
        for impl in AGG_IMPLS:
            _assert_streams_equal(aggregate_stream(trace, cfg, impl=impl),
                                  stream, impl)
        ref = dataclasses.asdict(
            simulate(wl.name, stream, cfg, engine="event"))
        for engine in FAST_ENGINES:
            got = dataclasses.asdict(simulate(wl.name, stream, cfg,
                                              engine=engine))
            assert got == ref, engine


# ------------------------------------------------------- golden constants

# Raw integer-exact counters for representative cells (no float tolerance:
# cycles and idle_cycles are integral in this model).
GOLDEN_CELLS = {
    # (machine, bench): (cycles, offchip_requests, idle_cycles)
    ("ws32", "BFS"): (7561.0, 793, 6685.0),
    ("ws8", "BKP"): (12289.0, 1536, 9601.0),
    ("SW+", "DYN"): (14357.0, 48, 3605.0),
    ("LW+", "MTM"): (33759.0, 4288, 31775.0),
    ("ws64", "SR2"): (4249.0, 292, 2585.0),
}

# suite_summary headline numbers (geomeans -> tight relative tolerance).
# NOTE: this 5-bench, 512-thread grid is a *regression lock*, not the paper
# reproduction — the full-suite paper claims are validated in
# tests/test_warpsim.py.
GOLDEN_SUMMARY = {
    "swplus_over_lwplus": 1.0559580942993256,
    "swplus_over_ws8": 1.0878303621199206,
    "lwplus_over_ws8": 1.030183269575431,
    "swplus_over_ws16": 1.0025453313346577,
    "lwplus_over_ws16": 0.949417724762923,
    "swplus_over_ws32": 1.0239482974193057,
    "lwplus_over_ws32": 0.9696864894044306,
    "swplus_over_ws64": 1.0588952416674289,
    "lwplus_over_ws64": 1.0027814999325821,
    "swplus_idle_reduction_vs_ws8": 0.017985380908448367,
    "swplus_idle_reduction_vs_ws16": -0.02636868003910675,
    "swplus_idle_reduction_vs_ws32": -0.03558266462257942,
    "swplus_coalescing_improvement_vs_ws32": -0.011141603825815416,
    "swplus_coalescing_improvement_vs_ws64": -0.013752561426224164,
}


def test_golden_cells(small_suite):
    for (m, b), want in GOLDEN_CELLS.items():
        r = small_suite[m][b]
        got = (r.cycles, r.offchip_requests, r.idle_cycles)
        assert got == want, (m, b, got, want)


def test_golden_suite_summary(small_suite):
    s = runner.suite_summary(small_suite)
    assert set(s) == set(GOLDEN_SUMMARY)
    for k, want in GOLDEN_SUMMARY.items():
        assert s[k] == pytest.approx(want, rel=1e-9), (k, s[k], want)


def test_suite_ignores_cache_and_parallel_mode(small_suite, tmp_path):
    """Cached + parallel execution must be invisible in the numbers."""
    from repro.core.warpsim.sweep import ResultCache
    cache = ResultCache(str(tmp_path / "c"))
    res = runner.run_suite(machines.paper_suite(), benches=GOLDEN_BENCHES,
                           n_threads=N_THREADS, cache=cache, parallel=True)
    again = runner.run_suite(machines.paper_suite(), benches=GOLDEN_BENCHES,
                             n_threads=N_THREADS, cache=cache)
    for m, per_bench in small_suite.items():
        for b, r in per_bench.items():
            assert dataclasses.asdict(res[m][b]) == dataclasses.asdict(r)
            assert dataclasses.asdict(again[m][b]) == dataclasses.asdict(r)

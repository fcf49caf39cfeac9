"""Table 1's machine at its published 16 SMs: the thread pool a study
resolves from the machine, the device engine's per-SM SW+ slots against
every host engine and the plain reference, and the engine's loop-step
and state counters."""

import collections
import dataclasses
import threading

import numpy as np
import pytest

from repro.core.warpsim import _pallas, api, machines, obs, service, sweep
from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim.sweep import cell_key, resolve_n_threads

pallas_required = pytest.mark.skipif(
    not _pallas.available(), reason="jax not importable (or WARPSIM_PALLAS=0)")

# 32 threads an SM: every engine, the device engine included, simulates the
# 16-SM machine in seconds on the CPU.
CUT_THREADS = 512
SEED = 2024


# ------------------------------------------------- thread-count resolution


def test_a_16_sm_machine_resolves_eight_times_the_pool():
    suite = machines.paper_suite(num_sms=16)
    assert {c.num_sms for c in suite.values()} == {16}
    for bench, want in (("BFS", 16384), ("HSPT", 16384), ("MP", 8192),
                        ("MU", 8192), ("NQU", 8192)):
        assert resolve_n_threads(bench, suite["SW+"], None) == want
        assert resolve_n_threads(bench, machines.paper_suite()["SW+"],
                                 None) * 8 == want
    # An explicit count wins over the machine's.
    assert resolve_n_threads("BFS", suite["ws32"], 256) == 256
    cells = sweep.SweepSpec(benches=("BFS", "MP"), machines=suite).cells()
    assert {(b, n) for _, _, b, n, _ in cells} == {("BFS", 16384),
                                                   ("MP", 8192)}
    assert cell_key("BFS", suite["ws8"], None, 3) == cell_key(
        "BFS", suite["ws8"], 16384, 3)


def test_two_sm_cell_keys_are_unchanged():
    """The key of a 2-SM cell with no thread count, as the tree before
    thread counts followed the machine computed it."""
    suite = machines.paper_suite()
    assert suite == machines.paper_suite(num_sms=2)
    assert cell_key("BFS", suite["SW+"], None, 7) == (
        "b1f8e62389b08c347d1b0a7dccf12aded08557bc0791d42038b70dd2892a1dd4")
    assert cell_key("MP", suite["ws32"], None, 7) == (
        "83c6663d946a37041761173c675a7e3ede3a3ede8055a13f781eb1c5468dcd3b")
    (_m, cfg, bench, n_threads, seed), = sweep.SweepSpec(
        benches=("BFS",), machines={"SW+": suite["SW+"]}, seeds=(7,)).cells()
    assert n_threads == 2048
    assert cell_key(bench, cfg, n_threads, seed) == cell_key(
        "BFS", suite["SW+"], None, 7)


def test_a_study_mixing_2_and_16_sms_is_two_families(tmp_path):
    mixed = {"ws8": machines.baseline(8),
             "ws8x16": machines.baseline(8, num_sms=16)}
    res = sweep.run_sweep_with_stats(
        sweep.SweepSpec(benches=("BFS",), machines=mixed), parallel=False,
        cache=sweep.ResultCache(str(tmp_path)))
    assert res[1]["trace_families"] == 2
    cells = sweep.family_major_cells(
        sweep.SweepSpec(benches=("BFS",), machines=mixed).cells())
    assert len({(b, n, s) for _, _, b, n, s in cells}) == 2


@pytest.mark.parametrize("served", [False, True], ids=["inprocess", "daemon"])
def test_table1_study_simulates_16384_threads(tmp_path, served):
    """A study of the 16-SM suite that names no thread count simulates
    16,384 threads of BFS and HSPT, in-process and through the daemon."""
    suite = machines.paper_suite(num_sms=16)
    study = api.Study(benches=("BFS", "HSPT"), machines=suite, seeds=(SEED,),
                      engine="native")
    httpd = None
    if served:
        svc = service.SweepService(str(tmp_path / "daemon"))
        httpd = service.serve(svc)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        session = api.Session(backend=api.ServiceBackend(
            url="http://%s:%d" % httpd.server_address[:2]))
    else:
        session = api.Session(cache_dir=str(tmp_path / "cache"))
    try:
        res = session.run(study)
    finally:
        if httpd is not None:
            httpd.shutdown()
            httpd.server_close()
    assert len(res.records) == 12
    assert {r.n_threads for r in res.records} == {16384}
    for r in res.records:
        want = sweep.compute_cell(r.bench, suite[r.machine], n_threads=16384,
                                  seed=SEED, engine="native")
        assert dataclasses.asdict(r.result) == dataclasses.asdict(want)


# ---------------------------------------- engines and the plain reference


@pytest.fixture(scope="module")
def device_records():
    """The 16-SM suite on the device engine (XLA:CPU here) at a cut pool:
    one family launch per benchmark."""
    if not _pallas.available():
        pytest.skip("jax not importable (or WARPSIM_PALLAS=0)")
    suite = machines.paper_suite(num_sms=16)
    res = api.Session(cache_dir=None).run(api.Study(
        benches=("BFS", "HSPT"), machines=suite, n_threads=CUT_THREADS,
        seeds=(SEED,), engine="pallas"))
    assert res.stats["family_launches"] == 2
    return suite, res.records


@pytest.mark.parametrize("engine", ["event", "fast", "native"])
def test_device_engine_matches_host_engines_at_16_sms(device_records, engine):
    suite, records = device_records
    assert {r.machine for r in records} == set(suite)
    for r in records:
        want = sweep.compute_cell(r.bench, suite[r.machine],
                                  n_threads=CUT_THREADS, seed=SEED,
                                  engine=engine)
        assert dataclasses.asdict(r.result) == dataclasses.asdict(want), (
            r.machine, r.bench)
    merged = {r.bench: r.result.merged_requests for r in records
              if r.machine == "SW+"}
    assert all(merged.values()), merged   # SW+ merges within its SMs


def test_paper16_grid_matches_the_reference_at_a_cut_pool(device_records):
    from chipbench import compare, grid
    from chipbench.reference import Reference

    suite, records = device_records
    cfg = grid.load_config("paper16-grid")
    ms = grid.machines(cfg)
    got = {n: MachineConfig(**f) for n, f in ms.items()}
    assert got == suite and list(got) == list(suite)
    assert grid.n_threads(cfg) == {
        b: resolve_n_threads(b, suite["ws8"], None) for b in grid.benches(cfg)}
    ref = Reference({r.bench: CUT_THREADS for r in records})
    answers = [((r.machine, r.bench, r.seed), dataclasses.asdict(r.result))
               for r in records]
    checks = compare.checks(answers, ref, ms, 0)
    assert compare.passed(checks), checks


# ----------------------------------------------------- per-SM slot numbering


def test_sm_slots_number_each_sms_own_blocks():
    # Two warps an SM on two SMs; warp 1 and warp 2 both touch block 9.
    cols = dict(n_warps=4, op_start=np.array([0, 1, 2, 3, 4]),
                issue=np.ones(4, dtype=np.int64),
                kind=np.ones(4, dtype=np.int8),
                blk_off=np.array([3, 0, 4, 1]), blk_len=np.array([1, 1, 2, 2]),
                blocks=np.array([9, 7, 5, 40, 9, 3]),
                nbytes=np.full(6, 64))
    slots, per_sm, distinct = _pallas._sm_slots(cols, 2)
    # SM 0 owns positions 3 (block 40) and 0 (block 9): slots by block.
    # SM 1 owns 4, 5 (blocks 9, 3) and 1, 2 (blocks 7, 5).
    assert slots.tolist() == [0, 2, 1, 1, 3, 0]
    assert (per_sm, distinct) == (4, 5)
    dims, _ = _pallas.pack_units([(cols, _pallas._cfg_scalars(
        MachineConfig(num_sms=2)))])
    assert dims[-1] == 4     # a power of four, under the distinct blocks' 8


# ---------------------------------------------------------------- counters


@pallas_required
def test_a_launch_counts_its_loop_steps_and_state():
    """Two one-warp units step in lockstep: step 1 pops a compute op in
    one and a 2-block load in the other (2 inner trips), step 2 a 3-block
    load and a 1-block store (3 trips): 2 steps + 5 trips."""
    def unit(kinds, lens):
        n = len(kinds)
        return dict(
            n_warps=1, op_start=np.array([0, n], dtype=np.int64),
            issue=np.ones(n, dtype=np.int64),
            kind=np.array(kinds, dtype=np.int8),
            blk_off=np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(
                np.int64),
            blk_len=np.array(lens, dtype=np.int64),
            blocks=np.arange(sum(lens), dtype=np.int64) * 7,
            nbytes=np.full(sum(lens), 64, dtype=np.int64))

    scal = _pallas._cfg_scalars(machines.paper_suite(num_sms=16)["SW+"])
    units = [(unit([0, 1], [0, 3]), scal), (unit([1, 2], [2, 1]), scal)]
    dom = obs.Observability()
    launches0 = _pallas.launch_count()
    with obs.bind(dom):
        _pallas._launch_units(units)
    assert _pallas.launch_count() == launches0 + 1
    assert _pallas.loop_steps(last=1) == 2 + 5
    dims, stacked = _pallas.pack_units(units)
    n_sms, nctrl, n_sets, ways, n_slots = dims
    w_pad = stacked["next0"].shape[1]
    want_state = 8 * (2 * w_pad + 2 * n_sms + nctrl
                      + 3 * n_sms * n_sets * ways + n_sms * n_slots + 3)
    assert _pallas.state_bytes(last=1) == want_state
    snap = dom.registry.snapshot()
    assert snap["warpsim_pallas_loop_steps_total"][""] == 7
    assert snap["warpsim_pallas_state_bytes"][""] == want_state
    assert _pallas.loop_steps(last=10 ** 6) is None


# ------------------------------------------------- the benchmark's readers


class _Ctx:
    def __init__(self, launches, inflight_s):
        self.launches = launches
        self.stages = {"device_inflight": (inflight_s, launches)}


@pytest.mark.parametrize("name", ["engine.device_us_per_step",
                                  "engine.state_kib_per_unit"])
def test_step_and_state_readers_read_the_windows_launches(name, monkeypatch):
    from chipbench import harness

    reader = harness.load_reader(name)
    recent = [(1000, 4096), (3000, 8192)]
    monkeypatch.setattr(_pallas, "_RECENT", collections.deque(
        [(5, 1 << 20)] + recent, maxlen=8))
    got = reader.read(_Ctx(len(recent), 0.002))
    want = {"engine.device_us_per_step": 0.002 * 1e6 / 4000,
            "engine.state_kib_per_unit": 8.0}[name]
    assert got == pytest.approx(want)
    assert reader.read(_Ctx(0, 0.0)) is None
    assert reader.read(_Ctx(9, 0.002)) is None     # more than remembered
    # A program without the counters (the tree before them): no reading.
    monkeypatch.delattr(_pallas, {"engine.device_us_per_step": "loop_steps",
                                  "engine.state_kib_per_unit": "state_bytes"
                                  }[name])
    assert reader.read(_Ctx(len(recent), 0.002)) is None

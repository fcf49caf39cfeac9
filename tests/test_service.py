"""Sweep service + work queue tests: in-flight dedup, HTTP endpoints,
lease/requeue semantics, cross-instance cache adoption, native-engine
health reporting."""

import dataclasses
import json
import os
import threading
import time
import urllib.error
import urllib.request
import warnings

import pytest

from repro.core.warpsim import _native, machines, runner
from repro.core.warpsim import service as service_mod
from repro.core.warpsim import sweep as sweep_mod
from repro.core.warpsim import work_queue as wq_mod
from repro.core.warpsim.config import MachineConfig
from repro.core.warpsim.service import (
    SweepClient, SweepService, resolve_machine, serve,
)
from repro.core.warpsim.sweep import (
    ResultCache, SweepSpec, cell_key, family_major_cells, run_sweep,
)
from repro.core.warpsim.work_queue import WorkQueue, run_worker

SMALL = dict(benches=("BFS", "DYN"), n_threads=128)


def _spec(**kw):
    base = dict(machines={"ws8": machines.baseline(8),
                          "SW+": machines.sw_plus()}, **SMALL)
    base.update(kw)
    return SweepSpec(**base)


def _wait(predicate, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.005)
    return False


@pytest.fixture()
def live(tmp_path):
    """A SweepService bound to an ephemeral HTTP port."""
    svc = SweepService(str(tmp_path / "cache"), lease_seconds=30.0)
    httpd = serve(svc)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = "http://%s:%d" % httpd.server_address[:2]
    try:
        yield svc, url
    finally:
        httpd.shutdown()
        httpd.server_close()


# ------------------------------------------------------- in-flight dedup

def test_concurrent_cold_requests_simulate_once(tmp_path, monkeypatch):
    """Two clients asking for the same uncomputed cell -> one simulation.

    The owner is held inside compute_cell until the second requester has
    demonstrably parked on the in-flight future, so the overlap the dedup
    table exists for is exercised deterministically, not by timing luck.
    """
    svc = SweepService(str(tmp_path), persist_traces=False)
    release = threading.Event()
    orig_compute = service_mod.compute_cell
    calls = []

    def slow_compute(*args, **kwargs):
        calls.append(threading.current_thread().name)
        assert release.wait(10)
        return orig_compute(*args, **kwargs)

    monkeypatch.setattr(service_mod, "compute_cell", slow_compute)
    cfg = machines.baseline(8)
    results = {}

    def request(tag):
        results[tag] = svc.cell_with_source("DYN", cfg, 128, 0)

    t1 = threading.Thread(target=request, args=("a",), name="req-a")
    t1.start()
    assert _wait(lambda: calls)                 # owner entered the compute
    t2 = threading.Thread(target=request, args=("b",), name="req-b")
    t2.start()
    assert _wait(lambda: svc.counters["dedup_waits"] == 1)
    release.set()
    t1.join(10)
    t2.join(10)

    assert len(calls) == 1                      # exactly one simulation
    assert svc.counters["simulated"] == 1
    assert svc.counters["dedup_waits"] == 1
    assert sorted(src for _, src in results.values()) == [
        "dedup", "simulated"]
    (res_a, _), (res_b, _) = results["a"], results["b"]
    assert dataclasses.asdict(res_a) == dataclasses.asdict(res_b)
    # A third request is a plain cache hit — no future, no simulation.
    res_c, src_c = svc.cell_with_source("DYN", cfg, 128, 0)
    assert src_c == "cache" and svc.counters["simulated"] == 1
    assert dataclasses.asdict(res_c) == dataclasses.asdict(res_a)


def test_cell_counts_one_miss_per_cold_cell(tmp_path):
    """Regression: the under-lock cache re-probe must not double-count
    the optimistic probe's miss (it skewed /stats hit rates ~2x low)."""
    svc = SweepService(str(tmp_path), persist_traces=False)
    svc.cell("DYN", machines.baseline(8), 128, 0)
    assert svc.cache.misses == 1 and svc.cache.hits == 0
    svc.cell("DYN", machines.baseline(8), 128, 0)
    assert svc.cache.misses == 1 and svc.cache.hits == 1


def test_sweep_empty_spec_is_empty_not_default_suite(live):
    """Regression: POST /sweep with explicit empty benches/seeds must run
    zero cells, not silently widen to the full default suite."""
    _svc, url = live
    client = SweepClient(url)
    res = client.sweep(SweepSpec(benches=(),
                                 machines={"ws8": machines.baseline(8)}))
    assert client.last_stats["cells"] == 0 and client.last_stats["simulated"] == 0
    assert all(per_b == {} for per_b in res.values())
    from repro.core.warpsim.sweep import spec_from_dict
    assert spec_from_dict({"benches": []}).cells() == []
    assert spec_from_dict({"seeds": []}).cells() == []
    assert len(spec_from_dict({}).benches) == 15    # absent -> defaults


def test_cell_after_sweep_is_cache_hit(tmp_path):
    svc = SweepService(str(tmp_path), persist_traces=False)
    spec = _spec()
    _res, stats = svc.sweep(spec)
    assert stats["simulated"] == len(spec.cells())
    res, src = svc.cell_with_source("BFS", machines.sw_plus(), 128, 0)
    assert src == "cache" and res.cycles > 0
    # Warm re-sweep: zero simulations, zero cache misses.
    _res, warm = svc.sweep(spec)
    assert warm["simulated"] == 0 and warm["cache_misses"] == 0
    assert warm["cache_hits"] == len(spec.cells())


# ---------------------------------------------------------- HTTP surface

def test_http_healthz_reports_live_engine(live):
    _svc, url = live
    # Raw wire-protocol probes in this file bypass the typed transport
    # on purpose: they assert HTTP statuses the typed client would
    # translate into ServiceError (hence the lint suppressions).
    with urllib.request.urlopen(  # warpsim-lint: disable=typed-http-boundary
            url + "/healthz", timeout=10) as resp:
        h = json.loads(resp.read())
    assert h["ok"] is True and h["model"] == sweep_mod.MODEL_VERSION
    native = h["native"]
    assert set(native) >= {"enabled", "loaded", "attempted", "error",
                           "engine"}
    pallas = h["pallas"]
    assert set(pallas) >= {"enabled", "importable", "probed", "error",
                           "engine", "launches"}
    # healthz resolves "auto" to whichever engine is actually live —
    # never to "pallas", which is strictly opt-in.
    assert h["engine"] == ("native" if native["engine"] == "native"
                           else "fast")


def test_http_cell_matches_in_process(live):
    _svc, url = live
    client = SweepClient(url)
    got = client.cell("BFS", machine="SW+", n_threads=128, seed=0)
    ref = runner.run_one("BFS", machines.sw_plus(), n_threads=128, seed=0)
    assert dataclasses.asdict(got) == dataclasses.asdict(ref)


def test_http_cell_field_overrides(live):
    _svc, url = live
    client = SweepClient(url)
    base = client.cell("DYN", machine="ws32", n_threads=128)
    tweaked = client.cell("DYN", machine="ws32", n_threads=128,
                          dram_latency_cycles=40, mimd="true")
    assert tweaked.cycles != base.cycles
    # Overrides relabel the machine "custom" (the result's machine column
    # must not claim ws32 for a non-ws32 point); otherwise bit-identical.
    ref = runner.run_one(
        "DYN", dataclasses.replace(machines.baseline(32), name="custom",
                                   dram_latency_cycles=40, mimd=True),
        n_threads=128)
    assert dataclasses.asdict(tweaked) == dataclasses.asdict(ref)


def test_http_errors(live):
    _svc, url = live
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(  # warpsim-lint: disable=typed-http-boundary
            url + "/cell?bench=BFS&machine=nope", timeout=10)
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(  # warpsim-lint: disable=typed-http-boundary
            url + "/cell", timeout=10)  # missing bench
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(  # warpsim-lint: disable=typed-http-boundary
            url + "/nope", timeout=10)
    assert e.value.code == 404


def test_http_sweep_matches_run_sweep(live):
    _svc, url = live
    client = SweepClient(url)
    spec = _spec()
    got = client.sweep(spec)
    assert client.last_stats["simulated"] == len(spec.cells())
    ref = run_sweep(spec, parallel=False)
    assert list(got) == list(ref)
    for m in ref:
        assert list(got[m]) == list(ref[m])
        for b in ref[m]:
            assert (dataclasses.asdict(got[m][b])
                    == dataclasses.asdict(ref[m][b]))
    # Warm: the service's stats snapshot reports zero re-simulation.
    client.sweep(spec)
    assert client.last_stats["simulated"] == 0
    assert client.last_stats["cache_misses"] == 0


def test_http_multi_seed_shape_and_runner_delegation(live):
    _svc, url = live
    spec = _spec(benches=("BFS",), seeds=(0, 1))
    got = SweepClient(url).sweep(spec)
    assert set(got) == {0, 1}           # seed keys decoded back to ints
    assert got[0]["ws8"]["BFS"].cycles != got[1]["ws8"]["BFS"].cycles
    # runner.run_suite(service_url=...) is the drop-in remote path.
    via_runner = runner.run_suite(
        machine_set={"ws8": machines.baseline(8)}, benches=("BFS",),
        n_threads=128, service_url=url)
    assert (dataclasses.asdict(via_runner["ws8"]["BFS"])
            == dataclasses.asdict(got[0]["ws8"]["BFS"]))


def test_stats_endpoint_counts_external_cache_writes(live, tmp_path):
    svc, url = live
    client = SweepClient(url)
    assert client.stats()["result_cache"]["entries"] == 0
    # Another "worker" writes into the same directory behind the daemon's
    # back; /stats re-scans (ResultCache.refresh) and reports it, and the
    # daemon serves it as a hit instead of re-simulating (adoption).
    spec = _spec(benches=("DYN",))
    run_sweep(spec, cache=ResultCache(svc.cache.root), parallel=False)
    assert client.stats()["result_cache"]["entries"] == len(spec.cells())
    _res, stats = svc.sweep(spec)
    assert stats["simulated"] == 0 and stats["cache_hits"] == len(spec.cells())


def test_from_env_probe_and_fallback(live, monkeypatch):
    _svc, url = live
    monkeypatch.delenv("WARPSIM_SERVICE_URL", raising=False)
    assert service_mod.from_env() is None
    monkeypatch.setenv("WARPSIM_SERVICE_URL", url)
    client = service_mod.from_env()
    assert client is not None and client.healthz()["ok"] is True
    # A dead service degrades to None with a warning, not a failure.
    monkeypatch.setattr(service_mod, "_WARNED_DEAD_URLS", set())
    monkeypatch.setenv("WARPSIM_SERVICE_URL", "http://127.0.0.1:9")
    with pytest.warns(RuntimeWarning, match="unreachable"):
        assert service_mod.from_env() is None


def test_from_env_dead_url_warns_exactly_once(monkeypatch):
    """Regression: every sweep of a figure run used to emit its own copy
    of the dead-URL warning; now the first probe warns and every repeat
    caller gets the silent fallback."""
    monkeypatch.setattr(service_mod, "_WARNED_DEAD_URLS", set())
    monkeypatch.setenv("WARPSIM_SERVICE_URL", "http://127.0.0.1:9")
    with pytest.warns(RuntimeWarning, match="unreachable"):
        assert service_mod.from_env() is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # a second warning raises
        assert service_mod.from_env() is None
    # A *different* dead URL is news and warns again.
    monkeypatch.setenv("WARPSIM_SERVICE_URL", "http://127.0.0.1:19")
    with pytest.warns(RuntimeWarning, match="unreachable"):
        assert service_mod.from_env() is None


def test_resolve_machine_params():
    assert resolve_machine({"machine": "SW+"}) == machines.sw_plus()
    assert resolve_machine({"machine": "ws64"}) == machines.baseline(64)
    assert (resolve_machine({"machine": "ws32", "simd_width": "16"})
            == machines.baseline(32, 16))
    cfg = resolve_machine({"warp_size": "16", "mimd": "1",
                           "dram_bw_gbps": "100.0"})
    assert cfg == dataclasses.replace(MachineConfig(), name="custom",
                                      warp_size=16, mimd=True,
                                      dram_bw_gbps=100.0)
    # A preset's display name must not survive onto a config it no longer
    # describes (it is part of the cell cache key and the /cell label).
    assert resolve_machine({"machine": "ws32", "warp_size": "64"}).name == \
        "custom"
    assert resolve_machine({"machine": "ws32", "warp_size": "64",
                            "name": "mine"}).name == "mine"
    with pytest.raises(ValueError):
        resolve_machine({"machine": "warp9000"})
    with pytest.raises(ValueError):
        resolve_machine({"mimd": "maybe"})


# ------------------------------------------------------------ work queue

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _cells(spec):
    return spec.cells()


def test_family_major_cells_groups_families():
    spec = _spec(benches=("BFS", "DYN"),
                 machines={"ws8": machines.baseline(8),
                           "ws16": machines.baseline(16),
                           "SW+": machines.sw_plus()})
    ordered = family_major_cells(spec.cells())
    assert sorted(map(repr, ordered)) == sorted(map(repr, spec.cells()))
    fams = [(b, nt, s) for _, _, b, nt, s in ordered]
    # Each family is one contiguous run ...
    positions = {}
    for i, f in enumerate(fams):
        positions.setdefault(f, []).append(i)
    assert len(positions) == 2
    for f, idx in positions.items():
        assert idx == list(range(idx[0], idx[-1] + 1)), f
    # ... and within a family, shared expansion keys are adjacent
    # (ws8 and SW+ collide; ws16 does not).
    first_fam = ordered[:3]
    assert {c[0] for c in first_fam[:2]} == {"ws8", "SW+"}
    assert first_fam[2][0] == "ws16"


def test_work_queue_lease_complete_drain():
    clock = FakeClock()
    q = WorkQueue(_cells(_spec()), chunk_size=1, lease_seconds=10,
                  clock=clock)
    assert q.status()["chunks"] == 4 and not q.done
    seen = []
    while True:
        chunk = q.lease("w1")
        if chunk is None:
            break
        seen.extend(chunk.cells)
        assert q.complete(chunk.chunk_id, "w1")
    assert q.done and len(seen) == 4
    assert q.status()["completed"] == 4
    assert q.complete(0, "w1")          # idempotent
    assert not q.complete(99, "w1")     # unknown chunk


def test_work_queue_requeues_on_worker_death():
    clock = FakeClock()
    q = WorkQueue(_cells(_spec(benches=("BFS",))), chunk_size=1,
                  lease_seconds=10, clock=clock)
    dead = q.lease("w-dead")            # leases chunk 0, then dies
    assert dead.chunk_id == 0
    # Before expiry the chunk is not re-granted — w2 gets the next one.
    nxt = q.lease("w2")
    assert nxt.chunk_id == 1
    assert q.lease("w2") is None and not q.done
    q.complete(1, "w2")
    # After the lease expires the dead worker's chunk is re-granted.
    clock.t = 11.0
    reclaimed = q.lease("w2")
    assert reclaimed.chunk_id == 0 and reclaimed.attempts == 2
    assert q.status()["leases_expired"] == 1
    q.complete(0, "w2")
    assert q.done
    # A late completion from the presumed-dead worker is accepted
    # (deterministic results) and counted, never an error.
    assert q.complete(0, "w-dead")
    assert q.status()["stale_completions"] == 0  # already done: no-op


def test_work_queue_renew_keeps_slow_chunk():
    """A renewing worker holds its lease past the nominal expiry; a
    worker whose lease lapsed gets renew() == False and must abandon."""
    clock = FakeClock()
    q = WorkQueue(_cells(_spec(benches=("BFS",))), chunk_size=1,
                  lease_seconds=10, clock=clock)
    slow = q.lease("w-slow")
    clock.t = 8.0
    assert q.renew(slow.chunk_id, "w-slow")     # extends to t=18
    clock.t = 15.0
    assert q.lease("w2").chunk_id != slow.chunk_id  # still held
    clock.t = 19.0                              # renewed lease lapsed now
    reclaimed = q.lease("w2")
    assert reclaimed.chunk_id == slow.chunk_id
    assert not q.renew(slow.chunk_id, "w-slow")     # lost: abandon signal
    assert q.renew(slow.chunk_id, "w2")
    assert not q.renew(99, "w2")                    # unknown chunk


def test_work_queue_compacts_after_drain():
    q = WorkQueue(_cells(_spec(benches=("BFS",))), chunk_size=2,
                  lease_seconds=10, clock=FakeClock())
    chunk = q.lease("w1")
    assert len(chunk.cells) == 2
    q.complete(chunk.chunk_id, "w1")
    assert q.done
    # Payloads are dropped once drained (daemon memory), but status still
    # reports the job's true size.
    assert q.chunks[0].cells == []
    assert q.status()["cells"] == 2


def test_work_queue_stale_completion_counted():
    clock = FakeClock()
    q = WorkQueue(_cells(_spec(benches=("BFS",))), chunk_size=2,
                  lease_seconds=10, clock=clock)
    first = q.lease("w1")
    clock.t = 11.0
    again = q.lease("w2")               # re-granted after expiry
    assert again.chunk_id == first.chunk_id
    assert q.complete(first.chunk_id, "w1")   # the "dead" worker returns
    assert q.status()["stale_completions"] == 1
    assert q.done


def test_queue_end_to_end_with_worker_death(tmp_path):
    """Two workers drain one job over HTTP; one leases a chunk and dies.

    The lease expires, the surviving worker picks the chunk up, and the
    job finishes with every cell adopted into the service cache — a sweep
    afterwards is 100% cache hits.

    Fully deterministic: the daemon's WorkQueue runs on a FakeClock and
    the surviving worker's injected `sleep` advances it past the dead
    worker's lease — expiry/requeue is exercised without wall-clock
    timing (the old version leased for 0.3 real seconds and could flake
    either way on a loaded machine).
    """
    clock = FakeClock()
    svc = SweepService(str(tmp_path / "cache"), persist_traces=False,
                       clock=clock)
    httpd = serve(svc)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        url = "http://%s:%d" % httpd.server_address[:2]
        spec = _spec()
        client = SweepClient(url)
        job = client.enqueue(spec, chunk_size=1, lease_seconds=10.0)
        assert job["chunks"] == 4 and job["cells"] == len(spec.cells())

        # Worker that leases one chunk and never completes it.
        with urllib.request.urlopen(  # warpsim-lint: disable=typed-http-boundary
                url + f"/queue/lease?job={job['job']}&worker=w-dead",
                timeout=10) as resp:
            dead_lease = json.loads(resp.read())
        assert dead_lease["chunk"] is not None

        def tick(seconds):
            # The survivor's poll sleep IS the passage of time: one poll
            # jumps the daemon's clock past the dead worker's lease.
            clock.t += max(seconds, 11.0)

        n = run_worker(url, job["job"], worker_id="w-live",
                       poll_seconds=0.05, sleep=tick)
        assert n == len(spec.cells())   # the survivor computed everything
        status = client.queue_status(job["job"])
        assert status["completed"] == 4 and status["leases_expired"] >= 1

        _res, stats = svc.sweep(spec)
        assert stats["simulated"] == 0
        assert stats["cache_hits"] == len(spec.cells())
        assert svc.counters["queue_cells_adopted"] == len(spec.cells())
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_work_queue_dict_roundtrip():
    """to_dict/from_dict restore chunk boundaries, cells, states, workers
    and counters verbatim (the daemon-restart persistence contract)."""
    clock = FakeClock()
    q = WorkQueue(_cells(_spec()), chunk_size=1, lease_seconds=10,
                  clock=clock)
    leased = q.lease("w1")
    q.complete(q.lease("w1").chunk_id, "w1")
    clock.t = 4.0                       # leased chunk has 6s remaining

    clock2 = FakeClock()
    clock2.t = 100.0                    # a "restarted daemon's" clock
    q2 = WorkQueue.from_dict(q.to_dict(), clock=clock2)
    assert q2.status() == q.status()
    assert [c.cells for c in q2.chunks] == [c.cells for c in q.chunks]
    assert q2.chunks[leased.chunk_id].worker == "w1"
    # The lease carried its *remaining* time, re-anchored to the new
    # clock: still held at +5s, reclaimable after the remaining 6s.
    clock2.t = 105.0
    assert q2.renew(leased.chunk_id, "w1")
    clock2.t = 120.0
    reclaimed = q2.lease("w2")
    assert reclaimed.chunk_id == leased.chunk_id


def test_service_queue_jobs_survive_restart(tmp_path):
    """A daemon restart must not forget half-drained sweeps: job state is
    reloaded from <cache root>/queue/jobs.json with chunk ids, completed
    work and the job-id sequence intact, and the job drains to done."""
    spec = _spec()
    svc = SweepService(str(tmp_path), persist_traces=False)
    job = svc.enqueue(spec, chunk_size=1)
    got = svc.queue_lease(job["job"], "w1")
    svc.queue_complete(job["job"], got["chunk"], "w1", [])

    svc2 = SweepService(str(tmp_path), persist_traces=False)
    st = svc2.queue_status(job["job"])
    assert st["chunks"] == 4 and st["completed"] == 1
    # Job ids keep counting up — a restart must never reuse a live id.
    job2 = svc2.enqueue(_spec(benches=("BFS",)))
    assert job2["job"] != job["job"]
    # The surviving chunks drain normally on the new daemon.
    while True:
        got = svc2.queue_lease(job["job"], "w2")
        if got["chunk"] is None:
            break
        svc2.queue_complete(job["job"], got["chunk"], "w2", [])
    assert svc2.queue_status(job["job"])["completed"] == 4

    # ... and the drained state is itself persisted for the next restart.
    svc3 = SweepService(str(tmp_path), persist_traces=False)
    assert svc3.queue_status(job["job"])["completed"] == 4


def test_service_queue_persistence_corrupt_file_degrades(tmp_path):
    """A corrupt job snapshot is dropped (and deleted) without taking the
    other jobs or the job-id sequence down with it."""
    svc = SweepService(str(tmp_path), persist_traces=False)
    job1 = svc.enqueue(_spec(benches=("BFS",)))
    job2 = svc.enqueue(_spec(benches=("DYN",)))
    with open(svc._job_path(job1["job"]), "w") as f:
        f.write("{ not json")
    fresh = SweepService(str(tmp_path), persist_traces=False)
    assert set(fresh._jobs) == {job2["job"]}    # corrupt job dropped
    assert not os.path.exists(svc._job_path(job1["job"]))
    # A fresh daemon mints ids in its own namespace: it can never reuse
    # a dead (or live) id from a previous incarnation.
    job3 = fresh.enqueue(_spec(benches=("BFS",)))
    assert job3["job"] not in {job1["job"], job2["job"]}


def test_service_queue_two_daemons_share_root_without_clobbering(tmp_path):
    """Two daemons on one cache root must not clobber each other's queue
    state.  Before the per-daemon namespace fix both minted "job-1" and
    the second daemon's snapshot silently overwrote the first's."""
    a = SweepService(str(tmp_path), persist_traces=False)
    b = SweepService(str(tmp_path), persist_traces=False)
    ja = a.enqueue(_spec(benches=("BFS",)))["job"]
    jb = b.enqueue(_spec(benches=("DYN",)))["job"]
    assert ja != jb
    # Both snapshots coexist on disk under the shared queue dir.
    assert os.path.exists(a._job_path(ja))
    assert os.path.exists(b._job_path(jb))
    # A third daemon booting on the same root adopts both jobs.
    fresh = SweepService(str(tmp_path), persist_traces=False)
    assert {ja, jb} <= set(fresh._jobs)


def test_service_queue_legacy_meta_layout_adopted(tmp_path):
    """Old layouts (un-namespaced job-<n>.json plus a meta.json sequence
    file) still load on boot: jobs are adopted verbatim by name and the
    stray meta.json is ignored rather than parsed as a job."""
    svc = SweepService(str(tmp_path), persist_traces=False)
    job = svc.enqueue(_spec(benches=("BFS",)))
    legacy = os.path.join(svc._queue_dir, "job-1.json")
    os.rename(svc._job_path(job["job"]), legacy)
    with open(os.path.join(svc._queue_dir, "meta.json"), "w") as f:
        f.write('{"job_seq": 1}')
    fresh = SweepService(str(tmp_path), persist_traces=False)
    assert set(fresh._jobs) == {"job-1"}
    assert fresh.queue_status("job-1")["chunks"] >= 1


def test_enqueue_evicts_old_jobs(tmp_path):
    """Neither finished nor abandoned jobs may accumulate without bound
    in a long-lived daemon."""
    svc = SweepService(str(tmp_path), persist_traces=False)
    empty = SweepSpec(benches=(), machines={"ws8": machines.baseline(8)})
    for _ in range(SweepService.MAX_FINISHED_JOBS + 20):
        svc.enqueue(empty)              # zero cells -> done immediately
    assert len(svc._jobs) <= SweepService.MAX_FINISHED_JOBS + 1
    # Live (undrained) jobs survive until the hard MAX_JOBS ceiling.
    live_spec = _spec(benches=("BFS",))
    for _ in range(SweepService.MAX_JOBS + 10):
        svc.enqueue(live_spec)
    assert len(svc._jobs) <= SweepService.MAX_JOBS


# ------------------------------------------------------- native reporting

def test_native_status_rereads_env(monkeypatch):
    st = _native.status()
    assert {"enabled", "loaded", "attempted", "error", "engine"} <= set(st)
    monkeypatch.setenv("WARPSIM_NATIVE", "0")
    off = _native.status()
    assert off["enabled"] is False and off["engine"] == "python"
    assert _native.available() is False   # the load gate re-reads too
    monkeypatch.delenv("WARPSIM_NATIVE")
    assert _native.status()["enabled"] is True


def test_native_failed_compile_warns_once_with_diagnostic(
        monkeypatch, tmp_path):
    """Regression: a failed compile used to be cached silently for the
    life of the process; it must surface the compiler error once."""
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_load_attempted", False)
    monkeypatch.setattr(_native, "_load_error", None)
    monkeypatch.setattr(_native, "_warned", False)
    monkeypatch.setenv("WARPSIM_NATIVE_DIR", str(tmp_path / "build"))
    monkeypatch.delenv("WARPSIM_NATIVE", raising=False)

    def broken_compiler(cmd, **kwargs):
        raise FileNotFoundError(f"{cmd[0]}: simulated missing compiler")

    monkeypatch.setattr(_native.subprocess, "run", broken_compiler)
    with pytest.warns(RuntimeWarning, match="native core unavailable"):
        assert _native.available() is False
    st = _native.status()
    assert st["loaded"] is False and st["attempted"] is True
    assert "simulated missing compiler" in st["error"]
    assert st["engine"] == "python"
    # The failure result stays cached, but the warning fires only once.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert _native.available() is False


# ------------------------------------------------------- pallas reporting

def test_pallas_status_rereads_env(monkeypatch):
    from repro.core.warpsim import _pallas
    monkeypatch.setattr(_pallas, "_probe_result", None)
    st = _pallas.status()
    assert {"enabled", "importable", "probed", "error", "engine",
            "launches"} <= set(st)
    assert st["probed"] is None           # status() alone never jits
    monkeypatch.setenv("WARPSIM_PALLAS", "0")
    off = _pallas.status()
    assert off["enabled"] is False and off["engine"] == "unavailable"
    assert _pallas.available() is False   # the launch gate re-reads too
    monkeypatch.delenv("WARPSIM_PALLAS")
    assert _pallas.status()["enabled"] is True


@pytest.mark.skipif(
    not __import__("repro.core.warpsim._pallas",
                   fromlist=["_pallas"]).available(),
    reason="jax not importable (or WARPSIM_PALLAS=0)")
def test_healthz_pallas_kill_switch_flips_on_live_daemon(
        tmp_path, monkeypatch):
    """WARPSIM_PALLAS=0 takes effect on a *running* pallas daemon: the
    next healthz re-reads the env and reports the fallback engine —
    no restart required (same contract as the WARPSIM_NATIVE switch)."""
    from repro.core.warpsim import _pallas

    svc = SweepService(str(tmp_path), engine="pallas",
                       persist_traces=False)
    h = svc.healthz()
    assert h["pallas"]["probed"] is True  # a pallas daemon self-probes
    assert h["engine"] == "pallas"

    monkeypatch.setenv("WARPSIM_PALLAS", "0")
    off = svc.healthz()
    assert off["pallas"]["enabled"] is False
    assert off["engine"] in ("native", "fast")

    monkeypatch.delenv("WARPSIM_PALLAS")
    assert svc.healthz()["engine"] == "pallas"


# ------------------------------------- device engine: one launch a family

pallas_required = pytest.mark.skipif(
    not __import__("repro.core.warpsim._pallas",
                   fromlist=["_pallas"]).available(),
    reason="jax not importable (or WARPSIM_PALLAS=0)")


def _family_study(engine="pallas", seed=0):
    """One benchmark x the paper suite at one seed: one trace family."""
    from repro.core.warpsim import api
    return api.Study(benches=("BFS",), machines=machines.paper_suite(),
                     n_threads=64, seeds=(seed,), engine=engine)


def _records(res):
    return [(r.machine, r.bench, r.seed, dataclasses.asdict(r.result))
            for r in res.records]


def _native_records():
    from repro.core.warpsim import api
    return _records(api.Session().run(_family_study("native")))


@pytest.fixture()
def pallas_live(tmp_path):
    """A device-engine SweepService bound to an ephemeral HTTP port."""
    svc = SweepService(str(tmp_path / "cache"), engine="pallas",
                       persist_traces=False)
    httpd = serve(svc)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = "http://%s:%d" % httpd.server_address[:2]
    try:
        yield svc, url
    finally:
        httpd.shutdown()
        httpd.server_close()


@pallas_required
def test_served_pallas_study_is_one_family_launch(pallas_live):
    """A served 1-benchmark x paper-suite study is one trace family, so
    one device launch, with records bit-identical to the native engine
    and to the in-process pallas study."""
    from repro.core.warpsim import _pallas, api

    svc, url = pallas_live
    before = _pallas.launch_count()
    served = api.Session(backend=api.ServiceBackend(url=url)).run(
        _family_study())
    assert _pallas.launch_count() - before == 1
    assert served.stats["family_launches"] == 1
    assert served.stats["simulated"] == 6 == svc.counters["simulated"]
    inproc = api.Session().run(_family_study())
    assert inproc.stats["family_launches"] == 1
    assert _records(served) == _native_records() == _records(inproc)


@pallas_required
def test_served_pallas_study_again_is_all_cache_hits(pallas_live):
    from repro.core.warpsim import _pallas, api

    _svc, url = pallas_live
    session = api.Session(backend=api.ServiceBackend(url=url))
    first = session.run(_family_study())
    before = _pallas.launch_count()
    again = session.run(_family_study())
    assert _pallas.launch_count() == before
    assert again.stats["family_launches"] == 0
    assert again.stats["cache_hits"] == 6 and again.stats["simulated"] == 0
    assert _records(again) == _records(first)


@pallas_required
def test_cell_read_during_family_launch_is_deduplicated(pallas_live,
                                                        monkeypatch):
    """A GET /cell for a cell the study's launch is simulating parks on
    the study's future: one simulation of that cell in total."""
    from repro.core.warpsim import _pallas, api

    svc, url = pallas_live
    entered, release = threading.Event(), threading.Event()
    real = service_mod.compute_family_pallas

    def held(*args, **kwargs):
        entered.set()
        assert release.wait(30)
        return real(*args, **kwargs)

    monkeypatch.setattr(service_mod, "compute_family_pallas", held)
    before = _pallas.launch_count()
    out = {}
    study = threading.Thread(target=lambda: out.update(study=api.Session(
        backend=api.ServiceBackend(url=url)).run(_family_study())))
    study.start()
    assert entered.wait(30)
    read = threading.Thread(target=lambda: out.update(
        cell=SweepClient(url).cell("BFS", "SW+", n_threads=64)))
    read.start()
    assert _wait(lambda: svc.counters["dedup_waits"] == 1)
    release.set()
    study.join(60)
    read.join(60)
    assert not study.is_alive() and not read.is_alive()

    assert _pallas.launch_count() - before == 1
    assert svc.counters["simulated"] == 6
    assert out["study"].stats["family_launches"] == 1
    sw = [r for r in out["study"].records if r.machine == "SW+"]
    assert dataclasses.asdict(out["cell"]) == dataclasses.asdict(sw[0].result)
    assert _records(out["study"]) == _native_records()


@pallas_required
def test_cell_in_flight_is_awaited_not_launched_again(tmp_path, monkeypatch):
    """A cell another request is already simulating stays out of the
    study's launch; the study awaits its future."""
    from repro.core.warpsim import _pallas

    svc = SweepService(str(tmp_path), engine="pallas", persist_traces=False)
    entered, release = threading.Event(), threading.Event()
    real_cell = service_mod.compute_cell

    def held_cell(*args, **kwargs):
        entered.set()
        assert release.wait(30)
        return real_cell(*args, **kwargs)

    launched = []
    real_family = service_mod.compute_family_pallas

    def recorded(bench, n_threads, seed, cfgs, **kwargs):
        launched.append([cfg.name for cfg in cfgs])
        return real_family(bench, n_threads, seed, cfgs, **kwargs)

    monkeypatch.setattr(service_mod, "compute_cell", held_cell)
    monkeypatch.setattr(service_mod, "compute_family_pallas", recorded)
    before = _pallas.launch_count()
    out = {}
    cell = threading.Thread(target=lambda: out.update(
        cell=svc.cell_with_source("BFS", machines.sw_plus(), 64, 0)))
    cell.start()
    assert entered.wait(30)
    study = threading.Thread(
        target=lambda: out.update(study=svc.study(_family_study())))
    study.start()
    assert _wait(lambda: launched and svc.counters["dedup_waits"] == 1, 60)
    release.set()
    cell.join(60)
    study.join(60)
    assert not cell.is_alive() and not study.is_alive()

    assert len(launched) == 1 and len(launched[0]) == 5
    assert machines.sw_plus().name not in launched[0]
    assert out["cell"][1] == "simulated"
    stats = out["study"].stats
    assert stats["dedup_waits"] == 1 and stats["simulated"] == 5
    assert stats["family_launches"] == 1
    assert _pallas.launch_count() - before == 2     # one cell, one family
    assert svc.counters["simulated"] == 6
    assert _records(out["study"]) == _native_records()


@pallas_required
@pytest.mark.parametrize("switch_read", ["before the family",
                                         "at the launch"])
def test_pallas_kill_switch_serves_cells_one_at_a_time(
        tmp_path, monkeypatch, switch_read):
    """WARPSIM_PALLAS=0 makes a served pallas study run cell by cell on
    the flat engines, whether the daemon sees the switch before choosing
    the family path or only when the family launches."""
    from repro.core.warpsim import _pallas

    svc = SweepService(str(tmp_path), engine="pallas", persist_traces=False)
    monkeypatch.setenv("WARPSIM_PALLAS", "0")
    monkeypatch.setattr(_pallas, "_warned", False)
    if switch_read == "at the launch":
        monkeypatch.setattr(_pallas, "available", lambda: True)
    before = _pallas.launch_count()
    with pytest.warns(RuntimeWarning, match="pallas"):
        res = svc.study(_family_study())
    assert _pallas.launch_count() == before
    assert res.stats["family_launches"] == 0
    assert res.stats["simulated"] == 6 and not svc._inflight
    monkeypatch.delenv("WARPSIM_PALLAS")
    assert _records(res) == _native_records()


@pallas_required
@pytest.mark.parametrize("after", [2, 100])
def test_service_cell_fault_fires_per_cell_after_it_is_cached(
        tmp_path, after):
    """The service.cell hook runs once per simulated cell of a family
    launch, each time after that cell is cached; a kill mid-family ends
    the rest of the family's claims with the fault."""
    from repro.core.warpsim.faults import FaultError, FaultPlan

    svc = SweepService(
        str(tmp_path), engine="pallas", persist_traces=False,
        fault_plan=FaultPlan.from_spec(f"service.cell:kill,after={after}"))
    checked = []
    real = svc.check_fault

    def check(point, marker=None):
        if point == "service.cell":
            checked.append(svc.cache.contains(marker))
        return real(point, marker)

    svc.check_fault = check
    study = _family_study()
    keys = [cell_key(b, cfg, n, s)
            for _m, cfg, b, n, s in family_major_cells(study.to_spec().cells())]
    if after < len(keys):
        with pytest.raises(FaultError):
            svc.study(study)
        assert svc.dead
        assert checked == [True] * (after + 1)
        assert [svc.cache.contains(k) for k in keys] == (
            [True] * (after + 1) + [False] * (len(keys) - after - 1))
    else:
        res = svc.study(study)
        assert checked == [True] * len(keys) and not svc.dead
        assert res.stats["family_launches"] == 1
    assert not svc._inflight


def _family_on_host(launched):
    """Stand-in for ``compute_family_pallas``: the claim/publish
    bookkeeping under test, with each cell on the native engine (no
    device compiles); appends every simulated cell key to `launched`
    and reports a launch."""
    lock = threading.Lock()

    def run(bench, n_threads, seed, cfgs, **kwargs):
        out = [sweep_mod.compute_cell(bench, cfg, n_threads=n_threads,
                                      seed=seed, engine="native")
               for cfg in cfgs]
        with lock:
            launched.extend(cell_key(bench, cfg, n_threads, seed)
                            for cfg in cfgs)
        return out, True

    return run


@pallas_required
def test_studies_owning_parts_of_each_others_family_both_finish(
        tmp_path, monkeypatch):
    """Study A owns ws8 and finds ws16 in flight; study B owns ws16 and
    finds ws8 in flight. Each launches what it owns before it waits, so
    neither waits on the other for ever."""
    from repro.core.warpsim import api

    svc = SweepService(str(tmp_path), engine="pallas", persist_traces=False)
    launched = []
    monkeypatch.setattr(service_mod, "compute_family_pallas",
                        _family_on_host(launched))
    a_first, b_both = threading.Event(), threading.Event()
    b_keys = []
    real_claim = svc._claim

    def claim(key):
        got = real_claim(key)
        name = threading.current_thread().name
        if name == "study-a" and not a_first.is_set():
            a_first.set()
            assert b_both.wait(30)
        elif name == "study-b":
            b_keys.append(key)
            if len(b_keys) == 2:
                b_both.set()
        return got

    svc._claim = claim
    suite = machines.paper_suite()
    out = {}

    def run(tag, order):
        out[tag] = svc.study(api.Study(
            benches=("BFS",), machines={m: suite[m] for m in order},
            n_threads=64, engine="pallas"))

    a = threading.Thread(target=run, args=("a", ("ws8", "ws16")),
                         name="study-a", daemon=True)
    b = threading.Thread(target=run, args=("b", ("ws16", "ws8")),
                         name="study-b", daemon=True)
    a.start()
    assert a_first.wait(30)
    b.start()
    a.join(30)
    b.join(30)
    assert not a.is_alive() and not b.is_alive()
    assert sorted(launched) == sorted(set(launched)) and len(launched) == 2
    assert out["a"].stats["dedup_waits"] + out["b"].stats["dedup_waits"] >= 1
    assert not svc._inflight


@pallas_required
def test_overlapping_family_studies_and_reads_simulate_each_cell_once(
        tmp_path, monkeypatch):
    """Stress: more threads than cores run studies over overlapping
    machine subsets of the same families, and single-cell reads, on one
    daemon. Every cell is simulated exactly once, every answer is the
    native engine's, and no claim is left in flight."""
    import concurrent.futures
    import sys

    from repro.core.warpsim import api

    svc = SweepService(str(tmp_path), engine="pallas", persist_traces=False)
    launched = []
    monkeypatch.setattr(service_mod, "compute_family_pallas",
                        _family_on_host(launched))
    suite = machines.paper_suite()
    names = list(suite)
    want = {(m, s): sweep_mod.compute_cell("BFS", suite[m], n_threads=64,
                                           seed=s, engine="native")
            for m in names for s in (0, 1)}

    def job(k):
        seed = k % 2
        if k % 3 == 2:
            m = names[k % len(names)]
            res, _src = svc.cell_with_source("BFS", suite[m], 64, seed,
                                             engine="native")
            return [(m, seed, res)]
        subset = [names[(k + j) % len(names)] for j in range(3)]
        res = svc.study(api.Study(
            benches=("BFS",), machines={m: suite[m] for m in subset},
            n_threads=64, seeds=(seed,), engine="pallas"))
        return [(r.machine, r.seed, r.result) for r in res.records]

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with concurrent.futures.ThreadPoolExecutor(
                4 * (os.cpu_count() or 1)) as pool:
            answers = [a for f in [pool.submit(job, k) for k in range(48)]
                       for a in f.result(timeout=120)]
    finally:
        sys.setswitchinterval(old)

    assert len(launched) == len(set(launched))
    assert svc.counters["simulated"] == len({(m, s) for m, s, _ in answers})
    assert not svc._inflight
    for m, seed, res in answers:
        assert dataclasses.asdict(res) == dataclasses.asdict(want[(m, seed)])


def test_a_cell_read_while_its_owner_writes_it_is_simulated_once(
        tmp_path, monkeypatch):
    """A request that probes the cache while the owner of the cell is
    writing it, midway through the write, waits on the owner instead of
    taking the half-written entry for a corrupt one and quarantining it;
    the cell lands whole and is simulated once."""
    svc = SweepService(str(tmp_path), persist_traces=False)
    cfg = machines.baseline(32)
    writing, probed = threading.Event(), threading.Event()

    class WriteHeldOpen:
        """The cache's `os`, with the owner's writes held mid-way."""

        def __getattr__(self, name):
            return getattr(os, name)

        @staticmethod
        def write(fd, data):
            if threading.current_thread().name == "owner":
                os.write(fd, data[:len(data) // 2])
                writing.set()
                assert probed.wait(30)
                return os.write(fd, data[len(data) // 2:])
            return os.write(fd, data)

    real_get = svc.cache.get

    def get(key):
        res = real_get(key)
        if threading.current_thread().name == "reader":
            probed.set()
        return res

    monkeypatch.setattr(sweep_mod, "os", WriteHeldOpen())
    monkeypatch.setattr(svc.cache, "get", get)
    got = {}

    def read(name):
        got[name] = svc.cell_with_source("BFS", cfg, 64, 0, engine="native")

    owner = threading.Thread(target=read, args=("owner",), name="owner")
    owner.start()
    assert writing.wait(30)
    reader = threading.Thread(target=read, args=("reader",), name="reader")
    reader.start()
    owner.join(30)
    reader.join(30)
    assert got["owner"][1] == "simulated" and got["reader"][1] == "dedup"
    res, source = svc.cell_with_source("BFS", cfg, 64, 0, engine="native")
    assert source == "cache" and svc.cache.corrupt == 0
    assert svc.counters["simulated"] == 1
    assert res == got["owner"][0] == got["reader"][0]


@pallas_required
def test_mesh_daemons_serving_one_family_never_wait_on_each_other(
        tmp_path, monkeypatch):
    """Two meshed device-engine daemons serve the same family at once,
    each owning part of it. Both reach their first peer fetch before
    either goes on; each has launched and published what it owns by
    then, so the owner answers the other's fetch from its cache. Every
    cell is simulated once fleet-wide, and no fetch waits out the peer
    timeout and falls back to simulating locally."""
    from repro.core.warpsim import api
    from repro.core.warpsim.mesh import MeshConfig

    launched = []
    monkeypatch.setattr(service_mod, "compute_family_pallas",
                        _family_on_host(launched))
    svcs = [SweepService(str(tmp_path / f"root{i}"), engine="pallas",
                         persist_traces=False, mesh=False)
            for i in range(2)]
    servers = [serve(svc) for svc in svcs]
    for httpd in servers:
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
    urls = ["http://%s:%d" % httpd.server_address[:2] for httpd in servers]
    peer_timeout = 10.0
    for svc, url in zip(svcs, urls):
        svc.configure_mesh(MeshConfig.build(url, urls, replication=1,
                                            peer_timeout=peer_timeout))
    suite = machines.paper_suite()

    def owned_by(svc, seed):
        return [m for m, cfg in suite.items()
                if not svc.mesh.fetch_order(cell_key("BFS", cfg, 64, seed))]

    # Ownership follows the ephemeral ports: take a family both own part of.
    seed = next(s for s in range(64)
                if all(0 < len(owned_by(svc, s)) < len(suite)
                       for svc in svcs))
    both_fetch = threading.Barrier(2, timeout=30)
    for svc in svcs:
        real_fetch = svc._peer_fetch
        fetched = set()

        def fetch(*args, _real=real_fetch, _fetched=fetched):
            name = threading.current_thread().name
            if name.startswith("study-") and name not in _fetched:
                _fetched.add(name)
                both_fetch.wait()
            return _real(*args)

        svc._peer_fetch = fetch
    out = {}

    def run(i):
        out[i] = svcs[i].study(_family_study(seed=seed))

    threads = [threading.Thread(target=run, args=(i,), name=f"study-{i}",
                                daemon=True) for i in range(2)]
    t0 = time.monotonic()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(3 * peer_timeout)
        elapsed = time.monotonic() - t0
        assert not any(t.is_alive() for t in threads)
    finally:
        for httpd in servers:
            httpd.shutdown()
            httpd.server_close()

    assert elapsed < peer_timeout
    assert sorted(launched) == sorted(set(launched)) and len(launched) == 6
    assert sum(svc.counters["simulated"] for svc in svcs) == 6
    assert [svc.counters["peer_fallbacks"] for svc in svcs] == [0, 0]
    for i, svc in enumerate(svcs):
        stats = out[i].stats
        assert stats["family_launches"] == 1
        assert stats["simulated"] == len(owned_by(svc, seed))
        assert stats["peer_hits"] == len(suite) - stats["simulated"]
        assert not svc._inflight
    assert _records(out[0]) == _records(out[1])

"""Whole runs on the CPU at a test size: correct on the program, and
``correct`` false with the timed path broken underneath.

These skip only the harness's look for a chip; the rest of a run (warm-up,
window, counters, comparison with the plain reference) is the one the chip
runs. The configurations are cut to one or two benchmarks and two machines so a
run fits in seconds.
"""

import dataclasses
import json
import math
import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import grid, harness

ROOT = harness.ROOT


@pytest.fixture(scope="module")
def roots(tmp_path_factory):
    """Small configurations and mixes in a directory of their own."""
    d = tmp_path_factory.mktemp("cells")
    (d / "configs").mkdir()
    (d / "workloads").mkdir()
    for name, axis in (("paper-grid", None), ("design-grid", "dram_bw_gbps")):
        cfg = grid.load_config(name)
        cfg["machines"] = {k: cfg["machines"][k] for k in ("ws32", "SW+")}
        keep = ("MU", "NQU") if name == "paper-grid" else ("FWAL",)
        cfg["benchmarks"] = {k: cfg["benchmarks"][k] for k in keep}
        if axis is not None:    # one design axis, at its sourced values
            cfg["axes"] = {axis: cfg["axes"][axis]}
        (d / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, extra in (("served_studies_c4", {"clients": 2}),
                        ("inprocess_studies_c1", {}),
                        ("hot_reads_zipf_c16", {"clients": 4,
                                                "fill_seeds": 1})):
        mix = dict(grid.load_traffic(name), **extra)
        (d / "workloads" / f"{name}.json").write_text(json.dumps(mix))
    return (str(d), grid.HERE)


@pytest.fixture
def cpu_run(roots):
    """Run a cell without the chip; restore jax's settings afterwards."""
    import jax

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    bench = harness.load_benchmark()

    def go(cell, seconds=1.5, trace=False, control=False):
        return harness.run(bench, cell, 20260101, seconds, trace,
                           time.perf_counter(), require_tpu=False,
                           roots=roots, control=control)

    yield go
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("cell", ["paper.cold", "design.cold", "paper.hot"])
def test_cell_runs_correct_on_cpu(cpu_run, cell):
    out = cpu_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert "setup_s" in out["metrics"]
    assert out["device"]["platform"] == "cpu"


def test_traced_run_reports_its_layers(cpu_run):
    out = cpu_run("paper.cold", trace=True)
    assert out["correct"]
    m = out["metrics"]
    assert m["engine.launches_per_cell"]["value"] == 1.0
    assert m["sweep.trace_build_ms_per_family"]["value"] > 0
    assert "window_s" in out["device"] and "breakdown" in out


@pytest.mark.parametrize("cell", ["paper.cold", "design.cold", "paper.hot"])
def test_float32_control_is_not_correct(cpu_run, cell):
    out = cpu_run(cell, seconds=1.0, control=True)
    assert not out["correct"], out["checks"]
    assert out["checks"]["records_differing"]["value"] >= 1


def _answer_altered(monkeypatch):
    from repro.core.warpsim import sweep, timing

    real = timing.loop_result

    def nudged(name, cfg, loop, totals):
        res = real(name, cfg, loop, totals)
        return dataclasses.replace(
            res, cycles=math.nextafter(res.cycles, math.inf))

    monkeypatch.setattr(sweep, "loop_result", nudged)
    monkeypatch.setattr(timing, "loop_result", nudged)


def _state_unchanged(monkeypatch):
    from repro.core.warpsim import _pallas

    def no_step(units, count_launch=True):
        return [(0.0, 0, 0, 0) for _ in units]

    monkeypatch.setattr(_pallas, "_launch_units", no_step)


def _half_batch(monkeypatch):
    from repro.core.warpsim import api

    for cls in (api.InProcessBackend, api.ServiceBackend):
        real = cls.run

        def half(self, study, session, _real=real):
            res = _real(self, study, session)
            keep = res.records[: len(res.records) // 2]
            return dataclasses.replace(res, records=keep)

        monkeypatch.setattr(cls, "run", half)


@pytest.mark.parametrize("cell", ["paper.cold", "design.cold"])
@pytest.mark.parametrize("fault", [_answer_altered, _state_unchanged,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(cpu_run, monkeypatch, cell,
                                          fault):
    fault(monkeypatch)
    out = cpu_run(cell, seconds=1.0)
    assert not out["correct"], out["checks"]


def test_hot_read_altered_answer_is_not_correct(cpu_run, monkeypatch):
    """The daemon, on a thread of this process, alters a cached cell as
    it serves it; the reader processes see only what it sends."""
    from repro.core.warpsim import service

    real = service.SweepService.cell_with_source

    def altered(self, *a, **kw):
        res, source = real(self, *a, **kw)
        return dataclasses.replace(res, l1_hits=res.l1_hits + 1), source

    monkeypatch.setattr(service.SweepService, "cell_with_source", altered)
    out = cpu_run("paper.hot", seconds=1.0)
    assert not out["correct"], out["checks"]
    assert out["checks"]["records_differing"]["value"] >= 1


def test_hot_readers_are_processes_that_end(cpu_run, monkeypatch):
    """Every read comes from a reader process, and none outlives the run."""
    from chipbench import harness as h

    started = []
    real = h.Readers.__init__

    def spy(self, *a, **kw):
        real(self, *a, **kw)
        started.extend(self.procs)

    monkeypatch.setattr(h.Readers, "__init__", spy)
    out = cpu_run("paper.hot", seconds=1.0)
    assert out["correct"], out["checks"]
    assert len(started) == 4 and len({p.pid for p in started}) == 4
    assert all(p.returncode is not None for p in started)
    assert out["checks"]["records_compared"]["value"] == out["attempted"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "paper.cold",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_tpu_prints_no_result():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_cli_without_the_program_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

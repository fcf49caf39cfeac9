"""One run of one cell: set-up, measured window, metrics, correctness.

The cell names a configuration (``configs/<name>.json``) and a traffic
mix (``workloads/<name>.json``); the metrics it reports are the readers
``metrics/<name>.py`` that ``BENCHMARK.json`` lists for it. Adding a
cell, a configuration, a mix or a metric adds files and entries only.

From the program this takes the system under test (``api.Session``,
``api.ServiceBackend``, ``service.SweepService``, ``service.SweepClient``)
and its counters (``_pallas.launch_count``, the ``obs`` stage
histograms). Everything it judges by lives here.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import importlib.util
import json
import logging
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional

from chipbench import compare, grid, reduce, traffic as traffic_mod
from chipbench.reference import Reference

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
BENCHMARK = os.path.join(ROOT, "BENCHMARK.json")

# Longest a request may take before it counts as failed: a study that
# comes back after the close still counts, one that never comes does not.
REQUEST_TIMEOUT_S = 300.0

# The nearest precision below the one a configuration states.
CONTROL_PRECISION = {"float64": "float32"}

# The study engine under test, and the host engine that fills a read
# mix's cells before the window (the cell key is engine-agnostic and the
# records are bit-identical).
ENGINE = "pallas"
FILL_ENGINE = "native"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Compilation seen through jax.monitoring
# ---------------------------------------------------------------------------


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling (or fetching
    from the persistent cache), summed over every thread, and the number
    of programs traced."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

    def __init__(self, jax):
        self.total = 0.0
        self.traced = 0
        self.names: List[str] = []
        self._lock = threading.Lock()
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        # jax logs one "Compiling <fn> with global shapes ..." line per
        # program it compiles; keep them to name what compiled when.
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._handler = logging.Handler(logging.DEBUG)
        self._handler.emit = self._on_log
        self._propagate = {}
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            self._propagate[name] = lg.propagate
            lg.addHandler(self._handler)
            lg.propagate = False

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)
        self._jax.config.update("jax_log_compiles", self._log_compiles)
        for name in self.LOGGERS:
            lg = logging.getLogger(name)
            lg.removeHandler(self._handler)
            lg.propagate = self._propagate[name]

    def _on(self, event, duration, *args, **kwargs):
        if event in self.EVENTS:
            with self._lock:
                self.total += duration
                if event == self.EVENTS[0]:
                    self.traced += 1

    def _on_log(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            with self._lock:
                self.names.append(msg[:300])

    def mark(self):
        with self._lock:
            return self.total, self.traced, len(self.names)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


class Daemon:
    """A sweep daemon served on a thread of this process."""

    def __init__(self, cache_dir: str):
        from repro.core.warpsim import service

        self.svc = service.SweepService(cache_dir, engine=ENGINE)
        self.httpd = service.serve(self.svc)
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.url = "http://%s:%d" % self.httpd.server_address[:2]
        health = service.SweepClient(self.url, timeout=600.0).healthz()
        if not health.get("ok") or health.get("engine") != ENGINE:
            # Not fatal: the requests will show what the daemon does.
            log(f"daemon unhealthy: engine {health.get('engine')!r}, "
                f"pallas {health.get('pallas')}")

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.thread.join(60)


@dataclasses.dataclass
class Request:
    """One request of the window and what came back."""

    t_issue: float
    t_done: float
    amount: int                      # cells returned
    answers: list                    # [((machine, bench, seed), record)]
    failed: bool
    simulated: int = 0
    detail: str = ""


def _records(res) -> Dict[tuple, dict]:
    return {(r.machine, r.bench, r.seed): dataclasses.asdict(r.result)
            for r in res.records}


def _study_request(run_study: Callable, spec, machines) -> Request:
    import jax

    t0 = time.perf_counter()
    name = "chipbench.study " + "+".join(spec.benches)
    try:
        with jax.profiler.TraceAnnotation(name):
            res = run_study(spec, machines)
    except Exception as e:  # noqa: BLE001 — a failed request is counted
        t1 = time.perf_counter()
        return Request(t0, t1, 0, [(c, None) for c in spec.cells()], True,
                       detail=f"{type(e).__name__}: {e}")
    t1 = time.perf_counter()
    got = _records(res)
    want = spec.cells()
    answers = [(c, got.get(c)) for c in want]
    exact = set(got) == set(want) and len(res.records) == len(want)
    return Request(t0, t1, len(res.records), answers, not exact,
                   simulated=int(res.stats.get("simulated", 0)),
                   detail="" if exact else
                   f"{len(res.records)} records for {len(want)} cells")


def closed_loop(n_clients: int, seconds: float, issue: Callable[[int], object]
                ) -> tuple:
    """`n_clients` threads each issue, wait, issue again until `seconds`
    have passed; requests in flight at the close are let finish.
    ``issue(i)`` serves request `i` (numbered in issue order) and returns
    a Request. Returns (t0, requests)."""
    lock = threading.Lock()
    state = {"next": 0}
    out: List[Request] = []
    t0 = time.perf_counter()
    t_end = t0 + seconds

    def client() -> None:
        while True:
            with lock:
                if time.perf_counter() >= t_end:
                    return
                i = state["next"]
                state["next"] += 1
            req = issue(i)
            with lock:
                out.append(req)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(seconds + REQUEST_TIMEOUT_S)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client is still waiting after the timeout")
    return t0, out


class Readers:
    """The clients of a cell-read mix, each a process of its own
    (``reader.py``), so that none shares the daemon's interpreter."""

    def __init__(self, url: str, cells: list, mix: dict, seed: int,
                 seconds: float):
        job = {"url": url, "cells": cells, "traffic": mix, "seed": seed,
               "seconds": seconds, "timeout": 60.0}
        # The readers never touch the chip, which belongs to this process.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.procs: List[subprocess.Popen] = []
        try:
            for c in range(int(mix["clients"])):
                p = subprocess.Popen(
                    [sys.executable, os.path.join(HERE, "reader.py")],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    text=True, env=env, cwd=ROOT)
                self.procs.append(p)
                p.stdin.write(json.dumps(dict(job, client=c)) + "\n")
                p.stdin.flush()
            for p in self.procs:
                if p.stdout.readline().strip() != "ready":
                    raise RuntimeError("a reader process did not start")
        except BaseException:
            self.close()
            raise

    def go(self) -> float:
        """Start every reader's window; returns its start."""
        t0 = time.perf_counter()
        for p in self.procs:
            p.stdin.write("go\n")
            p.stdin.flush()
        return t0

    def results(self, cells: list) -> tuple:
        """(one Request per read, every answer) once all have reported."""
        reqs: List[Request] = []
        answers: list = []
        for p in self.procs:
            line = p.stdout.readline()
            if not line:
                raise RuntimeError("a reader process ended with no result")
            got = json.loads(line)
            failed = set(got["failed_at"])
            for k, lat in enumerate(got["latencies_s"]):
                bad = k in failed
                reqs.append(Request(0.0, lat, 1, [], bad,
                                    detail=got["detail"] if bad else ""))
            for i, rec, count in got["answers"]:
                answers.extend([(tuple(cells[i]), rec)] * count)
        return reqs, answers

    def close(self) -> None:
        """Stop every reader still running and wait for each to end."""
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            for f in (p.stdin, p.stdout):
                f.close()
        self.procs = []


# ---------------------------------------------------------------------------
# Metric readers
# ---------------------------------------------------------------------------


def profile_options(jax):
    """Profiler settings of a traced run: no Python function tracing,
    which would slow the host path that the window measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def load_reader(name: str, roots=(HERE,)):
    path = grid.find("metrics", name, roots, ext=".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries a run of `cell` reports: end-to-end without
    tracing, per-layer with it."""
    key = "per_layer" if trace else "end_to_end"
    out = []
    for m in bench[key]:
        cells = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


@dataclasses.dataclass
class Context:
    """What a metric reader may read of one run."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    setup_s: float
    setup_compile_s: float
    t0: float                                   # window start
    requests: List[Request]
    stages: Dict[str, tuple]                    # stage -> (sum_s, count)
    launches: int
    reference: Reference
    machines: Dict[str, dict]
    trace: Optional[reduce.Trace] = None
    trace_window: Optional[tuple] = None

    @property
    def studies(self) -> List[Request]:
        return [r for r in self.requests if not r.failed]

    def cells_simulated(self) -> int:
        return sum(r.simulated for r in self.requests)

    def families(self) -> int:
        """Trace families (benchmark, workload seed) the window ran."""
        return len({(b, s) for r in self.studies
                    for (_, b, s), _ in r.answers})

    def groups(self) -> int:
        """Expansion groups: families x distinct expansion keys."""
        return self.families() * len(grid.expansion_keys(self.config))

    def stream_rows(self) -> int:
        """Macro-ops of every cell the window simulated, counted from the
        reference walk: the work, whatever engine does it."""
        return sum(self.reference.n_ops(b, s, self.machines[m])
                   for r in self.studies for (m, b, s), _ in r.answers)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def _stage_snapshot(obs) -> Dict[str, tuple]:
    return {ch.labelvalues[0]: (ch.sum, ch.count)
            for ch in obs.stage_seconds.children()}


def _stage_delta(before, after) -> Dict[str, tuple]:
    out = {}
    for k, (s, c) in after.items():
        s0, c0 = before.get(k, (0.0, 0))
        if c > c0:
            out[k] = (s - s0, c - c0)
    return out


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def run(bench: dict, cell_name: str, seed: int, seconds: float,
        trace: bool, t_start: float, require_tpu: bool = True,
        roots=(HERE,), control: bool = False) -> dict:
    """One run; returns the result object of the last output line.

    `roots` are the directories searched for configurations, mixes and
    metric readers, first match first. With `control`, every answer the
    window returned is replaced, before the comparison, by the plain
    reference computed in the precision below the configuration's: the
    control, which has to come out as not correct."""
    cell = find_cell(bench, cell_name)
    cfg = grid.load_config(cell["config"], roots)
    mix = grid.load_traffic(cell["traffic"], roots)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro import compat

    compat.init_compile_cache()
    # Cache every program, however fast it compiled, so that only the
    # first run of a cell in a checkout compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoDevice(f"no TPU: jax's default backend is {dev.platform!r}")
    if len(devices) < cell["chips"]:
        raise NoDevice(f"the cell needs {cell['chips']} chips, jax sees "
                       f"{len(devices)}")
    clock = CompileClock(jax)

    from repro.core.warpsim import _pallas, api
    from repro.core.warpsim import obs as obs_mod
    from repro.core.warpsim.config import MachineConfig

    machines = grid.machines(cfg)
    mcfg = {n: MachineConfig(**f) for n, f in machines.items()}
    reference = Reference(grid.n_threads(cfg),
                          precision=cfg.get("precision", "float64"))
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    daemons: List[Daemon] = []
    readers: Optional[Readers] = None
    n_clients = int(mix["clients"])
    tracing = False
    try:
        def session_for(root: str):
            if mix["entry"] == "served":
                d = Daemon(os.path.join(tmp, root))
                daemons.append(d)
                return d, api.Session(backend=api.ServiceBackend(
                    url=d.url, timeout=REQUEST_TIMEOUT_S))
            if mix["entry"] == "inprocess":
                return None, api.Session(cache_dir=os.path.join(tmp, root))
            raise ValueError(f"unknown entry {mix['entry']!r}")

        def run_study(session, spec):
            study = api.Study(benches=spec.benches,
                              machines={m: mcfg[m] for m in spec.machines},
                              seeds=spec.seeds, engine=ENGINE)
            return session.run(study)

        plan = traffic_mod.StudyPlan.for_config(mix, cfg, seed)
        if mix["kind"] == "studies":
            # Warm-up: the cell's own shapes, on a throwaway daemon or
            # session, at workload seeds the window does not use.
            warm = plan.warmup
            wd, wsess = session_for("warmup")
            with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
                wreqs = list(pool.map(lambda spec: _study_request(
                    lambda s, m: run_study(wsess, s), spec, machines), warm))
            if wd is not None:
                wd.close()
                daemons.remove(wd)
            for r in wreqs:
                if r.failed:
                    log(f"warm-up request failed: {r.detail}")
        elif mix["kind"] != "cell_reads":
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")

        trace_dir = os.path.join(tmp, "trace")
        if trace:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=profile_options(jax))
            tracing = True
        win = jax.profiler.TraceAnnotation(reduce.WINDOW)
        win.__enter__()
        daemon, session = session_for("window")
        obs = daemon.svc.obs if daemon is not None else obs_mod.default()
        if mix["kind"] == "cell_reads":
            for n, c in mcfg.items():
                if api.resolve_machine_name(n) != c:
                    raise ValueError(f"machine {n!r} is no preset that "
                                     "GET /cell can name")
            cells = traffic_mod.fill_cells(mix, cfg)
            with jax.profiler.TraceAnnotation("chipbench.fill"):
                fill_seeds = sorted({s for _, _, s in cells})
                session.run(api.Study(
                    benches=tuple(grid.benches(cfg)), machines=mcfg,
                    seeds=tuple(fill_seeds),
                    engine=FILL_ENGINE))
            readers = Readers(daemon.url, cells, mix, seed, seconds)
        setup_s = time.perf_counter() - t_start
        setup_compile_s = clock.total
        stages0 = _stage_snapshot(obs)
        launches0 = _pallas.launch_count()
        mark0 = clock.mark()
        log(f"set-up {setup_s:.3f} s (compile {setup_compile_s:.3f} s)")

        if mix["kind"] == "studies":
            lock = threading.Lock()

            def issue(i):
                with lock:
                    spec = plan.study(i)
                return _study_request(lambda s, m: run_study(session, s),
                                      spec, machines)

            t0, reqs = closed_loop(n_clients, seconds, issue)
            answers = [a for r in reqs for a in r.answers]
        else:
            t0 = readers.go()
            reqs, answers = readers.results(cells)
            readers.close()
        mark1 = clock.mark()
        win.__exit__(None, None, None)
        launches = _pallas.launch_count() - launches0
        stages = _stage_delta(stages0, _stage_snapshot(obs))
        stats = dev.memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        tr = tr_window = None
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
            path = reduce.find(trace_dir)
            if path is not None:
                tr = reduce.load(path)
                tr_window = tr.window()
        compiled = mark1[1] - mark0[1]
        names = clock.names[mark0[2]:mark1[2]]
        log(f"compilations inside the window: {compiled}"
            + (f" ({'; '.join(names)})" if names else ""))
    finally:
        if readers is not None:
            readers.close()
        if tracing:
            jax.profiler.stop_trace()
        for d in daemons:
            d.close()
        clock.close()
        shutil.rmtree(tmp, ignore_errors=True)

    # Correctness, once the window is closed and the program's state freed.
    failed = sum(r.failed for r in reqs)
    for detail in sorted({r.detail for r in reqs if r.failed}):
        log(f"failed request: {detail or 'no answer'}")
    if control:
        # The program's own answers are judged too, so that a control run
        # is also a reading of the program on its seed.
        own = compare.checks(answers, reference, machines, failed)
        log("the program's own answers: " + "; ".join(compare.lines(own)))
        lower = Reference(grid.n_threads(cfg), precision=CONTROL_PRECISION[
            reference.precision])
        low = {}
        for c, _ in answers:
            if c not in low:
                low[c] = lower.cell(c[1], c[2], machines[c[0]])
        answers = [(c, low[c]) for c, _ in answers]
    t_ref = time.perf_counter()
    checks = compare.checks(answers, reference, machines, failed)
    log(f"reference compared {len(answers)} of {len(answers)} answers in "
        f"{time.perf_counter() - t_ref:.3f} s")

    ctx = Context(cell=cell, config=cfg, traffic=mix, seed=seed,
                  setup_s=setup_s, setup_compile_s=setup_compile_s, t0=t0,
                  requests=reqs, stages=stages, launches=launches,
                  reference=reference, machines=machines, trace=tr,
                  trace_window=tr_window)
    metrics = {}
    for m in metrics_for(bench, cell_name, trace):
        value = load_reader(m["name"], roots).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": compare.passed(checks), "attempted": len(reqs),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        if tr is not None and tr_window is not None:
            w0, w1 = tr_window
            device["busy_s"] = reduce.busy_s(tr, w0, w1)
            device["window_s"] = w1 - w0
            out["breakdown"] = {
                "device_ops": reduce.top_programs(tr, w0, w1),
                "idle_gaps": reduce.idle_gaps(tr, w0, w1)}
        else:
            log("the profiler wrote no readable trace")
    out["checks"] = checks
    return out

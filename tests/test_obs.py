"""warpsim.obs tests: metric registry semantics + Prometheus exposition,
the X-Warpsim-Op header codec, span ring bounds, ambient-context
propagation, deterministic sampling, the counter-drift guard between the
legacy ``stats()`` views and the registry, and the chaos property that a
retried request stays ONE logical trace (attempt spans chain, traces
never fork)."""

import math
import threading

import pytest

from repro.core.warpsim import machines
from repro.core.warpsim import obs as obs_mod
from repro.core.warpsim import service as service_mod
from repro.core.warpsim.api import Study
from repro.core.warpsim.faults import FaultPlan
from repro.core.warpsim.obs import (
    DEFAULT_RING, OP_HEADER, CounterView, MetricsRegistry, Observability,
    TraceBuffer, format_op_header, parse_exposition, parse_op_header,
)
from repro.core.warpsim.service import ResilientClient, SweepService, serve


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _noop_sleep(_seconds):
    pass


class _daemon:
    """Context manager: serve `svc` on an ephemeral port, yield its URL."""

    def __init__(self, svc):
        self.svc = svc

    def __enter__(self):
        self.httpd = serve(self.svc)
        self.thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self.thread.start()
        return "http://%s:%d" % self.httpd.server_address[:2]

    def __exit__(self, *exc):
        self.httpd.shutdown()
        self.httpd.server_close()
        return False


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_counter_semantics():
    reg = MetricsRegistry(clock=FakeClock())
    c = reg.counter("warpsim_test_total", "doc")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)


def test_gauge_semantics():
    reg = MetricsRegistry(clock=FakeClock())
    g = reg.gauge("warpsim_test_gauge", "doc")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4


def test_histogram_buckets_and_timer():
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    h = reg.histogram("warpsim_test_seconds", "doc",
                      buckets=(0.1, 1.0, 10.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(100.0)     # lands in +Inf
    with h.time():
        clock.t += 2.0   # lands in the 10.0 bucket
    child = h._default()
    assert child.count == 4
    assert child.sum == pytest.approx(102.55)
    # Rendered buckets are cumulative and end at +Inf == count.
    samples = parse_exposition(reg.render())
    assert samples['warpsim_test_seconds_bucket{le="0.1"}'] == 1
    assert samples['warpsim_test_seconds_bucket{le="1"}'] == 2
    assert samples['warpsim_test_seconds_bucket{le="10"}'] == 3
    assert samples['warpsim_test_seconds_bucket{le="+Inf"}'] == 4
    assert samples["warpsim_test_seconds_count"] == 4


def test_labels_create_distinct_series():
    reg = MetricsRegistry(clock=FakeClock())
    c = reg.counter("warpsim_cells_total", "doc", labelnames=("engine",))
    c.labels(engine="fast").inc(2)
    c.labels(engine="native").inc()
    samples = parse_exposition(reg.render())
    assert samples['warpsim_cells_total{engine="fast"}'] == 2
    assert samples['warpsim_cells_total{engine="native"}'] == 1
    with pytest.raises(ValueError, match="takes labels"):
        c.labels(bench="BFS")
    with pytest.raises(ValueError, match="has labels"):
        c.inc()


def test_registration_is_idempotent_but_shape_strict():
    reg = MetricsRegistry(clock=FakeClock())
    a = reg.counter("warpsim_x_total", "doc")
    assert reg.counter("warpsim_x_total", "other doc") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("warpsim_x_total")
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("warpsim_x_total", labelnames=("k",))
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("warpsim bad name")


def test_exposition_has_help_and_type_and_parses():
    reg = MetricsRegistry(clock=FakeClock())
    reg.counter("warpsim_a_total", "things counted").inc()
    text = reg.render()
    assert "# HELP warpsim_a_total things counted" in text
    assert "# TYPE warpsim_a_total counter" in text
    assert parse_exposition(text) == {"warpsim_a_total": 1.0}
    with pytest.raises(ValueError, match="malformed"):
        parse_exposition("no_value_here\n")


def test_snapshot_flattens_histograms():
    reg = MetricsRegistry(clock=FakeClock())
    reg.counter("warpsim_a_total").inc(2)
    reg.histogram("warpsim_b_seconds", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    assert snap["warpsim_a_total"] == {"": 2.0}
    assert snap["warpsim_b_seconds"] == {".sum": 0.5, ".count": 1}


# ---------------------------------------------------------------------------
# CounterView: the legacy dict shape over registry counters
# ---------------------------------------------------------------------------


def test_counter_view_is_mapping_and_strict():
    reg = MetricsRegistry(clock=FakeClock())
    view = CounterView(reg, {"simulated": ("warpsim_sim_total", "doc"),
                             "hits": ("warpsim_hits_total", "doc")})
    view.inc("simulated")
    view.inc("hits", 3)
    assert view["simulated"] == 1
    assert dict(view) == {"simulated": 1, "hits": 3}
    assert len(view) == 2
    with pytest.raises(KeyError, match="not in this view"):
        view.inc("typo")
    assert view.metric_names() == {"simulated": "warpsim_sim_total",
                                   "hits": "warpsim_hits_total"}
    # The value genuinely lives in the registry, not a shadow dict.
    assert reg.get("warpsim_hits_total").value == 3


# ---------------------------------------------------------------------------
# Counter drift: legacy stats() views <-> registry, both directions
# ---------------------------------------------------------------------------


def _registry_counter_names(registry):
    return {n for n in registry.names()
            if isinstance(registry.get(n), obs_mod.Counter)}


def test_service_counters_match_registry_both_ways(tmp_path):
    svc = SweepService(str(tmp_path), persist_traces=False)
    view_names = set(svc.counters.metric_names().values())
    # ->: every legacy counter is a registered registry counter.
    assert view_names <= _registry_counter_names(svc.obs.registry)
    # <-: every registry counter is reachable through the legacy view —
    # nothing counts into /metrics that /stats can't see.
    assert _registry_counter_names(svc.obs.registry) <= view_names
    # The legacy dict shape is exactly the view's keys.
    assert set(svc.stats()["counters"]) == set(svc.counters)
    assert set(svc.counters) == set(service_mod._COUNTER_METRICS)


def test_client_counters_match_registry_both_ways():
    client = ResilientClient(["http://127.0.0.1:1"], sleep=_noop_sleep)
    view_names = set(client.counters.metric_names().values())
    counter_names = _registry_counter_names(client.obs.registry)
    assert view_names == counter_names
    legacy = client.client_stats()
    assert set(legacy) - {"endpoints"} == set(client.counters)
    assert set(client.counters) == set(service_mod._CLIENT_COUNTER_METRICS)


def test_bump_of_undeclared_counter_raises(tmp_path):
    # The drift guard at runtime: a typo'd bump can't mint a counter.
    svc = SweepService(str(tmp_path), persist_traces=False)
    with pytest.raises(KeyError, match="not in this view"):
        svc.bump("simualted")


# ---------------------------------------------------------------------------
# Header codec
# ---------------------------------------------------------------------------


def test_header_round_trip():
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob) as ctx:
        value = format_op_header("op-7", ctx)
        op, tid, sid = parse_op_header(value)
        assert op == "op-7"
        assert tid == ctx.trace_id
        assert sid == ctx.span_id


def test_header_bare_legacy_value_parses_as_pure_op():
    assert parse_op_header("cell-abc123") == ("cell-abc123", None, None)
    assert parse_op_header(None) == ("", None, None)
    assert parse_op_header("") == ("", None, None)


def test_header_without_context_is_just_the_op():
    assert format_op_header("op-1", None) == "op-1"
    assert obs_mod.trace_headers(None) == {}


def test_trace_headers_carry_ambient_context():
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob) as ctx:
        headers = obs_mod.trace_headers()
        op, tid, sid = parse_op_header(headers[OP_HEADER])
        assert (op, tid, sid) == ("", ctx.trace_id, ctx.span_id)


def test_non_recording_context_propagates_nothing(monkeypatch):
    monkeypatch.setenv("WARPSIM_OBS_SAMPLE", "0")
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob) as ctx:
        assert ctx.recording is False
        assert obs_mod.trace_headers() == {}
    assert ob.spans.dump() == []


# ---------------------------------------------------------------------------
# Span ring + context propagation
# ---------------------------------------------------------------------------


def test_ring_is_bounded_and_counts_lifetime():
    buf = TraceBuffer(maxlen=4)
    for i in range(10):
        buf.record({"trace": "t", "span": str(i)})
    assert len(buf) == 4
    assert buf.recorded == 10
    assert [s["span"] for s in buf.dump()] == ["6", "7", "8", "9"]


def test_ring_default_capacity_from_env(monkeypatch):
    monkeypatch.delenv("WARPSIM_OBS_RING", raising=False)
    assert TraceBuffer().maxlen == DEFAULT_RING
    monkeypatch.setenv("WARPSIM_OBS_RING", "16")
    assert TraceBuffer().maxlen == 16


def test_spans_nest_and_parent_correctly():
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob, backend="inprocess") as root:
        with obs_mod.span("inner") as inner:
            obs_mod.event("fault", point="p")
            assert inner.trace_id == root.trace_id
    spans = {s["name"]: s for s in ob.spans.dump(root.trace_id)}
    assert set(spans) == {"study", "inner", "fault"}
    assert spans["study"]["parent"] is None
    assert spans["inner"]["parent"] == root.span_id
    assert spans["fault"]["parent"] == spans["inner"]["span"]
    assert spans["study"]["attrs"] == {"backend": "inprocess"}
    assert spans["fault"]["dur_s"] == 0.0


def test_nested_start_trace_extends_instead_of_forking():
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("outer", obs=ob) as outer:
        with obs_mod.start_trace("inner", obs=ob) as inner:
            assert inner.trace_id == outer.trace_id
    assert ob.spans.traces() == [
        {"trace": outer.trace_id, "spans": 2, "root": "outer"}]


def test_join_trace_parents_to_remote_span():
    ob = Observability(clock=FakeClock())
    with obs_mod.join_trace("abcd1234", "server/study", obs=ob,
                            parent="ffff00001111"):
        pass
    (s,) = ob.spans.dump("abcd1234")
    assert s["parent"] == "ffff00001111"
    assert s["name"] == "server/study"


def test_join_trace_without_id_is_passthrough():
    ob = Observability(clock=FakeClock())
    with obs_mod.join_trace(None, "server/study", obs=ob) as ctx:
        assert ctx is None
    assert ob.spans.dump() == []


def test_activate_reenters_context_in_another_thread():
    ob = Observability(clock=FakeClock())
    got = {}
    with obs_mod.start_trace("study", obs=ob) as ctx:
        def task():
            # A bare pool thread has no ambient context...
            got["before"] = obs_mod.current()
            with obs_mod.activate(ctx):
                got["during"] = obs_mod.current()
                with obs_mod.span("pool-task"):
                    pass
        t = threading.Thread(target=task)
        t.start()
        t.join()
    assert got["before"] is None
    assert got["during"] is ctx
    names = [s["name"] for s in ob.spans.dump(ctx.trace_id)]
    assert "pool-task" in names


def test_activate_none_is_passthrough():
    with obs_mod.activate(None) as ctx:
        assert ctx is None


# ---------------------------------------------------------------------------
# Stage profiling + the WARPSIM_OBS kill switch
# ---------------------------------------------------------------------------


def test_stage_observes_histogram_and_records_span():
    clock = FakeClock()
    ob = Observability(clock=clock)
    with obs_mod.start_trace("study", obs=ob) as ctx:
        with obs_mod.stage("engine", engine="fast"):
            clock.t += 0.25
    child = ob.stage_seconds.labels(stage="engine")
    assert child.count == 1
    assert child.sum == pytest.approx(0.25)
    names = [s["name"] for s in ob.spans.dump(ctx.trace_id)]
    assert "engine" in names


def test_stage_without_trace_still_observes_histogram():
    # Library code calls stage() unconditionally; with no active trace
    # the duration still lands in the ambient (default) histogram.
    before = obs_mod.default().stage_seconds.labels(stage="t_obs_x").count
    with obs_mod.stage("t_obs_x"):
        pass
    after = obs_mod.default().stage_seconds.labels(stage="t_obs_x").count
    assert after == before + 1


def test_kill_switch_makes_hooks_no_ops(monkeypatch):
    monkeypatch.setenv("WARPSIM_OBS", "0")
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob) as ctx:
        assert ctx is None
        with obs_mod.span("inner") as inner:
            assert inner is None
        obs_mod.event("fault")
        with obs_mod.stage("engine"):
            pass
    assert ob.spans.dump() == []
    with obs_mod.join_trace("sometid", "server/x", obs=ob) as ctx:
        assert ctx is None
    assert ob.spans.dump() == []


# ---------------------------------------------------------------------------
# Occupancy of a shared serial resource
# ---------------------------------------------------------------------------


def _holds(clock, resource, plan):
    """Run holds of `resource` under an injected clock. `plan` is a list
    of ("enter"|"exit", hold id, time) in clock order; returns the
    stage histograms' (sum, count) of ``_inflight`` and ``_queued``."""
    ob = Observability(clock=clock)
    live = {}
    with obs_mod.bind(ob):
        for what, hold, t in plan:
            clock.t = t
            if what == "enter":
                live[hold] = obs_mod.occupancy(resource)
                live[hold].__enter__()
            else:
                live.pop(hold).__exit__(None, None, None)
    assert not live
    h = ob.stage_seconds
    return ((h.labels(stage=resource + "_inflight").sum,
             h.labels(stage=resource + "_inflight").count),
            (h.labels(stage=resource + "_queued").sum,
             h.labels(stage=resource + "_queued").count))


def _union(intervals):
    total, end = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


@pytest.mark.parametrize("name,holds", [
    ("overlapping", {"a": (0.0, 2.0), "b": (1.0, 3.0), "c": (2.5, 5.0)}),
    ("apart", {"a": (0.0, 1.0), "b": (2.0, 3.0), "c": (4.5, 5.0)}),
    ("out_of_order", {"a": (0.0, 3.0), "b": (1.0, 2.0)}),
    ("spans_a_gap", {"a": (0.0, 1.0), "b": (2.0, 3.0), "c": (0.5, 4.0)}),
])
def test_occupancy_inflight_is_the_union_of_the_holds(name, holds):
    events = sorted([(s, "enter", h) for h, (s, _) in holds.items()]
                    + [(e, "exit", h) for h, (_, e) in holds.items()],
                    key=lambda ev: (ev[0], ev[1] == "enter"))
    plan = [(what, h, t) for t, what, h in events]
    (inflight, n), (queued, nq) = _holds(FakeClock(), "occ_" + name, plan)
    assert n == nq == len(holds)
    assert inflight == pytest.approx(_union(holds.values()))
    assert inflight + queued == pytest.approx(
        sum(e - s for s, e in holds.values()))
    assert queued >= 0


def test_occupancy_queued_is_time_behind_an_earlier_hold():
    # b enters while a runs, and waits for it: one second queued.
    plan = [("enter", "a", 0.0), ("enter", "b", 1.0), ("exit", "a", 2.0),
            ("exit", "b", 4.0)]
    (inflight, _), (queued, _) = _holds(FakeClock(), "occ_fifo", plan)
    assert inflight == pytest.approx(4.0)
    assert queued == pytest.approx(1.0)


def test_occupancy_state_is_per_resource_and_forgets_idle_history():
    clock = FakeClock()
    _holds(clock, "occ_r1", [("enter", "a", 0.0), ("exit", "a", 5.0)])
    # Another resource, overlapping in time, is not queued behind it.
    (_, _), (queued, _) = _holds(clock, "occ_r2", [("enter", "a", 1.0),
                                                   ("exit", "a", 2.0)])
    assert queued == 0.0
    # Once a resource is idle it keeps no intervals.
    assert obs_mod._HOLDS["occ_r1"].ended == []
    assert obs_mod._HOLDS["occ_r1"].active == []


def test_occupancy_records_a_span_when_tracing():
    clock = FakeClock()
    ob = Observability(clock=clock)
    with obs_mod.start_trace("study", obs=ob) as ctx:
        with obs_mod.occupancy("occ_span", units=3):
            clock.t += 0.5
    spans = [s for s in ob.spans.dump(ctx.trace_id)
             if s["name"] == "occ_span"]
    assert len(spans) == 1 and spans[0]["dur_s"] == 0.5
    assert spans[0]["attrs"] == {"units": 3, "queued_s": 0.0}


# ---------------------------------------------------------------------------
# Profiler annotations
# ---------------------------------------------------------------------------


class _Annotations:
    """A stand-in profiler annotation factory that logs what it enters."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(("enter", name))

            def __exit__(self, *exc):
                log.append(("exit", name))

        return _Ann()


@pytest.fixture
def annotations():
    fake = _Annotations()
    prev = obs_mod.set_annotation_factory(fake)
    try:
        yield fake
    finally:
        obs_mod.set_annotation_factory(prev)


def test_stages_spans_and_holds_enter_warpsim_annotations(annotations):
    ob = Observability(clock=FakeClock())
    with obs_mod.start_trace("study", obs=ob):
        with obs_mod.span("inner"):
            with obs_mod.stage("trace_build"):
                pass
            with obs_mod.occupancy("occ_ann"):
                pass
    assert annotations.log == [
        ("enter", "warpsim.inner"), ("enter", "warpsim.trace_build"),
        ("exit", "warpsim.trace_build"), ("enter", "warpsim.occ_ann"),
        ("exit", "warpsim.occ_ann"), ("exit", "warpsim.inner")]


def test_stage_annotates_without_a_trace(annotations):
    with obs_mod.stage("t_obs_ann"):
        pass
    with obs_mod.span("untraced"):
        pass
    assert [n for _, n in annotations.log] == [
        "warpsim.t_obs_ann", "warpsim.t_obs_ann",
        "warpsim.untraced", "warpsim.untraced"]


def test_annotation_exits_when_the_stage_raises(annotations):
    with pytest.raises(RuntimeError):
        with obs_mod.stage("t_obs_raise"):
            raise RuntimeError("boom")
    assert annotations.log[-1] == ("exit", "warpsim.t_obs_raise")


def test_no_annotation_without_factory_or_with_obs_off(monkeypatch,
                                                       annotations):
    obs_mod.set_annotation_factory(None)
    with obs_mod.stage("t_obs_none"):
        pass
    obs_mod.set_annotation_factory(annotations)
    monkeypatch.setenv("WARPSIM_OBS", "0")
    with obs_mod.stage("t_obs_off"):
        pass
    with obs_mod.span("t_obs_off"):
        pass
    with obs_mod.occupancy("t_obs_off"):
        pass
    assert annotations.log == []


def test_obs_imports_no_jax():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(obs_mod))
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    assert not {m for m in mods if m.split(".")[0] == "jax"}, mods
    assert {m.split(".")[0] for m in mods} <= {
        "__future__", "contextlib", "contextvars", "dataclasses", "hashlib",
        "math", "re", "threading", "time", "uuid", "collections", "typing",
        "repro"}


def test_compat_installs_the_profiler_annotation(annotations):
    import jax

    from repro import compat

    compat.annotate_stages()
    factory = obs_mod.set_annotation_factory(annotations)
    assert factory is jax.profiler.TraceAnnotation


def test_server_stage_times_each_request(tmp_path):
    svc = SweepService(str(tmp_path), persist_traces=False)
    with _daemon(svc) as url:
        ResilientClient([url], sleep=_noop_sleep).healthz()
    assert svc.obs.stage_seconds.labels(stage="server/healthz").count == 1


# ---------------------------------------------------------------------------
# The device engine's stages, on the CPU
# ---------------------------------------------------------------------------

LAUNCH_STAGES = ("pallas_pack", "pallas_dispatch", "device_inflight",
                 "device_queued")


def _tiny_cell():
    from repro.core.warpsim.divergence import expand_stream
    from repro.core.warpsim.trace import get_workload

    cfg = machines.baseline(32)
    wl = get_workload("NQU", n_threads=64)
    return expand_stream(wl, cfg), cfg


def _launch(path):
    from repro.core.warpsim import _pallas

    stream, cfg = _tiny_cell()
    if path == "run_family":
        return _pallas.run_family([(stream, cfg)])[0]
    return _pallas.run_scheduling_loop(
        stream.n_warps, stream.op_start, stream.issue, stream.kind,
        stream.blk_off, stream.blk_len, stream.blocks, stream.nbytes, cfg)


@pytest.mark.parametrize("path", ["run_scheduling_loop", "run_family"])
def test_launch_observes_its_four_stages(path):
    from repro.core.warpsim import _pallas

    if not _pallas.available():
        pytest.skip(f"no jax: {_pallas.status()}")
    ob = Observability()
    with obs_mod.bind(ob):
        loop = _launch(path)
    assert loop[0] > 0
    h = ob.stage_seconds
    for st in LAUNCH_STAGES:
        assert h.labels(stage=st).count == 1, st
    assert h.labels(stage="device_inflight").sum > 0
    exposition = ob.registry.render()
    for st in LAUNCH_STAGES:
        assert f'warpsim_stage_seconds_count{{stage="{st}"}} 1' in exposition


def test_launch_stages_land_on_the_profiler_trace(tmp_path):
    """One launch under the profiler: the host plane of the .xplane.pb
    holds the pack stage and the device hold by their warpsim names."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from repro import compat
    from repro.core.warpsim import _pallas

    if not _pallas.available():
        pytest.skip(f"no jax: {_pallas.status()}")
    _launch("run_family")                   # compile outside the trace
    prev = obs_mod.set_annotation_factory(None)
    compat.annotate_stages()
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            _launch("run_family")
        finally:
            jax.profiler.stop_trace()
    finally:
        obs_mod.set_annotation_factory(prev)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            host.update(ev.name for line in plane.lines
                        for ev in line.events)
    assert {"warpsim.pallas_pack", "warpsim.device",
            "warpsim.pallas_dispatch"} <= host


def test_sampling_is_deterministic_per_trace_id():
    # The decision is a pure function of the trace id and the rate.
    assert obs_mod._sampled("deadbeef") is True          # default rate 1.0
    for tid in ("a1", "b2", "c3"):
        first = obs_mod._sampled(tid)
        assert all(obs_mod._sampled(tid) == first for _ in range(3))


def test_sampling_rate_extremes(monkeypatch):
    monkeypatch.setenv("WARPSIM_OBS_SAMPLE", "1.0")
    assert obs_mod._sampled("anything") is True
    monkeypatch.setenv("WARPSIM_OBS_SAMPLE", "0.0")
    assert obs_mod._sampled("anything") is False


# ---------------------------------------------------------------------------
# Chaos: a retried request stays ONE trace (attempt spans, no fork)
# ---------------------------------------------------------------------------


def test_retried_request_keeps_one_logical_span_chain(tmp_path):
    """An injected 503 on the first /study attempt: the retry re-sends
    the same op (marker-keyed plan passes it) and the SAME trace id —
    both server hops land in one trace, parented to their respective
    client attempt spans. Retries append attempts; they never fork."""
    plan = FaultPlan.from_spec("server/study:error=503,times=1")
    svc = SweepService(str(tmp_path), persist_traces=False, fault_plan=plan)
    ob = Observability()
    with _daemon(svc) as url:
        client = ResilientClient([url], sleep=_noop_sleep)
        with obs_mod.start_trace("study", obs=ob) as ctx:
            tid = ctx.trace_id
            result = client.study(Study(
                machines={"ws8": machines.baseline(8)},
                benches=("BFS",), n_threads=128))
        assert result.records
    local = ob.spans.dump(tid)
    attempts = [s for s in local if s["name"] == "client.attempt"]
    assert len(attempts) == 2                      # the 503 + the retry
    assert attempts[0]["attrs"]["op"] == attempts[1]["attrs"]["op"]
    # The daemon saw both hops on the SAME trace — nothing forked.
    server = svc.obs.spans.dump(tid)
    study_spans = [s for s in server if s["name"] == "server/study"]
    assert len(study_spans) == 2
    attempt_ids = {s["span"] for s in attempts}
    assert {s["parent"] for s in study_spans} <= attempt_ids
    # Every span the daemon recorded belongs to this one trace.
    assert {t["trace"] for t in svc.obs.spans.traces()} == {tid}
    assert svc.counters["faults_injected"] == 1

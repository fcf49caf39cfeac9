"""The benchmark's pure parts: configurations, traffic, arithmetic,
reference, and the shape of BENCHMARK.json."""

import dataclasses
import json
import os
import re
import statistics

import pytest

from chipbench import compare, grid, harness, stats, traffic
from chipbench.reference import Reference

BENCH = harness.load_benchmark()


def test_paper_grid_is_the_paper_suite():
    from repro.core.warpsim import machines, trace
    from repro.core.warpsim.config import MachineConfig

    cfg = grid.load_config("paper-grid")
    got = {n: MachineConfig(**f) for n, f in grid.machines(cfg).items()}
    assert got == machines.paper_suite()
    assert list(got) == list(machines.paper_suite())
    assert grid.benches(cfg) == list(trace.BENCHMARKS)
    assert grid.n_threads(cfg) == {b: trace.get_workload(b).n_threads
                                   for b in trace.BENCHMARKS}


def test_design_grid_192_valid_machines_in_5_expansion_keys():
    from repro.core.warpsim.config import MachineConfig

    cfg = grid.load_config("design-grid")
    ms = grid.machines(cfg)
    assert len(ms) == 192
    cfgs = [MachineConfig(**f) for f in ms.values()]
    assert {c.l1_sets for c in cfgs} == {32, 64, 96, 192}
    assert len({c.expansion_key() for c in cfgs}) == 5
    assert len(grid.expansion_keys(cfg)) == 5
    assert len({c.name for c in cfgs}) == 192
    # Every design axis names where its values come from.
    assert set(cfg["axis_sources"]) == set(cfg["axes"])


def _plan(seed, cfg="paper-grid", mix="served_studies_c4"):
    return traffic.StudyPlan.for_config(grid.load_traffic(mix),
                                        grid.load_config(cfg), seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_same_seed_same_studies_other_seed_other(seed):
    a, b, c = _plan(seed), _plan(seed), _plan(seed + 1)
    sa = [a.study(i) for i in range(40)]
    assert sa == [b.study(i) for i in range(40)]
    sc = [c.study(i) for i in range(40)]
    assert [s.seeds for s in sa] != [s.seeds for s in sc]
    # Same sizes whatever the seed: the benchmarks cycle in order.
    assert [s.benches for s in sa] == [s.benches for s in sc]
    assert sa[0].benches == ("BFS",) and sa[15].benches == ("BFS",)
    fams = [(s.benches[0], x) for s in sa for x in s.seeds]
    assert len(set(fams)) == len(fams)
    assert all(0 <= x < 2**31 for _, x in fams)


def test_warmup_seeds_unused_by_the_window():
    p = _plan(99)
    warm = p.warmup
    assert [w.benches[0] for w in warm] == grid.benches(
        grid.load_config("paper-grid"))
    win = {(s.benches[0], x) for i in range(200)
           for s in [p.study(i)] for x in s.seeds}
    assert not win & {(w.benches[0], x) for w in warm for x in w.seeds}


def test_studies_draw_from_the_pool_then_fresh_seeds():
    mix = grid.load_traffic("served_studies_c4")
    pools = mix["workload_seeds"]
    p = _plan(2**33 + 1)
    for w in p.warmup:
        assert w.seeds[0] in pools[w.benches[0]]
    for i in range(300):
        s = p.study(i)
        assert s.seeds[0] in pools[s.benches[0]]
    # A pool of two: the warm-up takes one, the window one, then fresh.
    small = dict(mix, workload_seeds={b: [10, 11] for b in pools})
    q = traffic.StudyPlan.for_config(small, grid.load_config("paper-grid"),
                                     5)
    got = [q.study(i).seeds[0] for i in range(45)]
    assert all(x in (10, 11) for x in got[:15])
    assert not {10, 11} & set(got[15:])
    assert len(set(got[15:])) == 30
    assert {w.seeds[0] for w in q.warmup} | {got[0]} == {10, 11}


def test_reads_same_seed_same_reads_other_seed_other():
    mix = grid.load_traffic("hot_reads_zipf_c16")
    cfg = grid.load_config("paper-grid")
    cells = traffic.fill_cells(mix, cfg)
    assert len(cells) == 270 and len(set(cells)) == 270
    assert {s for _, _, s in cells} == {0, 1, 2}

    def take(seed, n=500):
        streams = [traffic.read_stream(mix, len(cells), seed, c)
                   for c in range(mix["clients"])]
        assert len(streams) == 16
        return [[next(s) for _ in range(n)] for s in streams]

    assert take(5) == take(5)
    assert take(5) != take(6)
    reads = [i for client in take(5, 2000) for i in client]
    counts = sorted((reads.count(i) for i in set(reads)), reverse=True)
    assert counts[0] > 20 * counts[len(counts) // 2]    # a hot head


def test_rate_counts_in_flight_studies_to_the_last_return():
    # Window starts at 10; the close at 20 lets two studies finish late.
    done = [(12.0, 6), (15.0, 6), (21.0, 6), (26.0, 6)]
    assert stats.rate(done, 10.0) == pytest.approx(24 / 16.0)
    assert stats.rate([], 10.0) is None


def test_percentiles_over_all_reads():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 50) is None


def test_spread_is_iqr_over_median():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


def test_intervals_union_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.gaps(iv, 0.0, 5.0) == [(2.0, 3.0), (4.0, 5.0)]


@pytest.mark.parametrize("bench,machine", [
    ("MU", "ws32"), ("NQU", "SW+"), ("MP", "LW+"), ("SCN", "ws8")])
def test_reference_matches_the_event_engine(bench, machine):
    from repro.core.warpsim import machines, sweep

    cfg = grid.load_config("paper-grid")
    ref = Reference(grid.n_threads(cfg))
    m = grid.machines(cfg)[machine]
    want = sweep.compute_cell(bench, machines.paper_suite()[machine],
                              seed=424242, engine="event")
    got = ref.cell(bench, 424242, m)
    assert not compare.differs(got, dataclasses.asdict(want))


def test_float32_control_differs_and_float64_does_not():
    cfg = grid.load_config("paper-grid")
    ms = grid.machines(cfg)
    ref = Reference(grid.n_threads(cfg))
    ctl = Reference(grid.n_threads(cfg), precision="float32")
    cells = [((m, "MU", 31337), None) for m in ms]
    answers = [(c, ctl.cell(c[1], c[2], ms[c[0]])) for c, _ in cells]
    res = compare.checks(answers, ref, ms, 0)
    assert res["records_differing"]["value"] >= 1
    assert not compare.passed(res)
    answers = [(c, ref.cell(c[1], c[2], ms[c[0]])) for c, _ in cells]
    assert compare.passed(compare.checks(answers, Reference(
        grid.n_threads(cfg)), ms, 0))


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert c["file"].startswith("chipbench/")
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            data = json.load(f)
        assert data["reduced"] == c["reduced"]
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        grid.load_traffic(w["traffic"])
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
        grid.find("metrics", m["name"], ext=".py")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        for cell in m.get("workloads", cells):   # each reports its `moves`
            assert m["moves"] in {e["name"] for e in harness.metrics_for(
                BENCH, cell, False)}
    for cell in cells:           # every cell reports setup_s, another
        assert len(harness.metrics_for(BENCH, cell, False)) >= 2
        assert harness.metrics_for(BENCH, cell, True)


def test_additions_are_new_files_only(tmp_path):
    """A new mix, configuration and per-layer metric, found by name in a
    directory of their own, with no existing file edited."""
    (tmp_path / "workloads").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "metrics").mkdir()
    mix = dict(grid.load_traffic("served_studies_c4"), clients=2)
    (tmp_path / "workloads" / "served_studies_c2.json").write_text(
        json.dumps(mix))
    cfg = grid.load_config("paper-grid")
    cfg["machines"] = {"ws32": cfg["machines"]["ws32"]}
    (tmp_path / "configs" / "ws32-only.json").write_text(json.dumps(cfg))
    (tmp_path / "metrics" / "sweep.studies.py").write_text(
        "def read(ctx):\n    return float(len(ctx.studies))\n")
    roots = (str(tmp_path), grid.HERE)
    assert grid.load_traffic("served_studies_c2", roots)["clients"] == 2
    assert list(grid.machines(grid.load_config("ws32-only", roots))) == [
        "ws32"]
    assert grid.load_traffic("served_studies_c4", roots)["clients"] == 4
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "paper.c2", "config": "ws32-only",
                               "traffic": "served_studies_c2", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "sweep.studies", "unit": "studies",
                               "better": "higher", "source": "host_clock",
                               "layer": "sweep: trace build",
                               "moves": "cells_per_s",
                               "workloads": ["paper.c2"]})
    names = [m["name"] for m in harness.metrics_for(bench, "paper.c2", True)]
    assert names == ["setup.compile_s", "sweep.studies"]
    reader = harness.load_reader("sweep.studies", roots)

    class Ctx:
        studies = [1, 2, 3]

    assert reader.read(Ctx()) == 3.0

"""Trace reduction on recorded traces and on synthetic events."""

import os

import pytest

from chipbench import reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_cpu_trace_window_and_host_spans():
    tr = reduce.load(os.path.join(DATA, "cpu_window.xplane.pb"))
    w0, w1 = tr.window()
    assert 0.030 < w1 - w0 < 0.5
    names = sorted(n for _, _, n in tr.host)
    assert names == ["chipbench.study BFS", "chipbench.study BKP",
                     "chipbench.window"]
    # A CPU run has no device plane: nothing ran on a device.
    assert tr.modules == {}
    assert reduce.busy_s(tr, w0, w1) == 0.0
    assert reduce.idle_gaps(tr, w0, w1) == [["no device activity", w1 - w0]]


def _synthetic():
    ns = 1_000_000_000
    rows = [
        ("/host:CPU", "python", "chipbench.window", 0, 10 * ns),
        ("/host:CPU", "python", "chipbench.study BFS", 3 * ns // 2, 3 * ns),
        ("/host:CPU", "python", "chipbench.study BKP", 6 * ns, 3 * ns),
        ("/host:CPU", "python", "not ours", 0, 10 * ns),
        ("/device:TPU:0", "XLA Modules", "jit__simulate_one(17)",
         2 * ns, 2 * ns),
        ("/device:TPU:0", "XLA Modules", "jit__simulate_one(18)",
         7 * ns, 1 * ns),
        ("/device:TPU:0", "XLA Modules", "jit_init(3)", 3 * ns, 2 * ns),
        ("/device:TPU:0", "XLA Ops", "while", 2 * ns, 2 * ns),
        ("/device:TPU:0", "XLA Ops", "scatter", 7 * ns, ns // 2),
        ("/device:TPU:0", "Steps", "ignored", 0, 10 * ns),
    ]
    return reduce.from_events(rows)


def test_busy_is_the_union_of_program_runs():
    tr = _synthetic()
    assert tr.window() == (0.0, 10.0)
    assert reduce.busy_s(tr, 0.0, 10.0) == pytest.approx(4.0)
    assert reduce.busy_s(tr, 0.0, 2.5) == pytest.approx(0.5)


def test_program_seconds_by_stable_name():
    got = reduce.program_seconds(_synthetic(), 0.0, 10.0)
    assert got == pytest.approx({"jit__simulate_one": 3.0, "jit_init": 2.0})


def test_top_programs_and_labelled_idle_gaps():
    tr = _synthetic()
    assert reduce.top_programs(tr, 0.0, 10.0) == [
        ["jit__simulate_one", pytest.approx(3.0)],
        ["jit_init", pytest.approx(2.0)]]
    gaps = reduce.idle_gaps(tr, 0.0, 10.0)
    # Idle 0-2, 5-7 and 8-10, named by the span around each middle.
    assert gaps == [["between requests", pytest.approx(2.0)],
                    ["chipbench.study BKP", pytest.approx(2.0)],
                    ["chipbench.study BKP", pytest.approx(2.0)]]

"""The trace reduction on a small recorded trace: busy time as a union
of intervals, idle share, device time per iteration, and the breakdown."""
import json
import os

import _benchpath  # noqa: F401
import pytest

from bench import devtrace, harness, spec
from bench.gen.lp import Instance

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def trace():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        return devtrace.Trace.from_events(json.load(f))


def test_union_merges_overlaps_and_keeps_gaps():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                               (5, 9)]
    union = [(0, 3), (5, 9)]
    assert devtrace.covered(union, 2, 6) == 2
    assert devtrace.gaps(union, 0, 12) == [(3, 5), (9, 12)]
    assert devtrace.gaps(union, 4, 8) == [(4, 5)]


def test_busy_time_counts_nested_operations_once(trace):
    # 100-400 (a loop and an op inside it), 600-900, 1300-1500, 1600-1900
    assert trace.busy_ns(0, 2000) == 300 + 300 + 200 + 300
    assert trace.busy_ns(0, 1000) == 600
    assert trace.busy_ns(250, 650) == 150 + 50


def _run(trace, iterations):
    inst = Instance(name="lp", shape=(4, 8), c=None, b=None, lb=None,
                    ub=None, x_opt=None, y_opt=None, obj_opt=0.0, K=None)
    calls = [harness.Call(0.0, 1.0, [inst], [{"iterations": it}])
             for it in iterations]
    cell = spec.Cell(name="t", chips=1, config={}, mix={"entry": "stream"},
                     end_to_end=[], per_layer=[])
    # the recorded trace names its calls' spans bench.solve
    return harness.Run(cell=cell, seed=0, setup_s=0.0, calls=calls,
                       peaks={}, trace=trace, span="bench.solve")


def test_idle_share_and_device_time_per_iteration(trace):
    run = _run(trace, [400, 600])
    assert run.trace_window() == (0, 2000)
    assert run.idle_share() == pytest.approx(100.0 * (1 - 1100 / 2000))
    # 1100 ns busy inside the two spans
    assert run.device_busy_s() == pytest.approx(1100e-9)


def test_self_time_leaves_out_nested_operations():
    ops = [(0, 100, "while"), (10, 30, "a"), (40, 90, "b"), (50, 60, "c"),
           (200, 210, "d")]
    assert devtrace.self_times(ops) == [100 - 20 - 50, 20, 50 - 10, 10, 10]


def test_breakdown_ranks_ops_by_self_time_and_labels_gaps(trace):
    ops = trace.top_ops(0, 2000)
    assert ops[0] == ["dot.3", pytest.approx(300e-9)]
    assert [name for name, _ in ops] == ["dot.3", "gather.1", "while.1",
                                         "fusion.7", "fusion.8"]
    assert dict(ops)["while.1"] == pytest.approx(200e-9)
    gaps = trace.idle_gaps(0, 2000)
    # the longest gap (900-1300) spans the hand-over between the calls:
    # its midpoint 1100 lies outside both solve spans
    assert gaps[0] == [devtrace.OUTSIDE, pytest.approx(400e-9)]
    assert gaps[1] == ["bench.solve", pytest.approx(200e-9)]
    assert len(gaps) == 5


def test_events_from_profile_reads_device_ops_and_bench_spans():
    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [
            Plane("/device:TPU:0", [Line("XLA Modules", [Ev("jit_f", 0, 90)]),
                                    Line("XLA Ops", [Ev("fusion.1", 10, 5),
                                                     Ev("dot.2 = " + "f" * 200,
                                                        20, 7)])]),
            Plane("/host:CPU", [Line("python", [Ev("bench.solve", 0, 100),
                                                Ev("other", 3, 4)])]),
        ]

    ev = devtrace.events_from_profile(Profile())
    name = ("dot.2 = " + "f" * 200)[:devtrace.NAME_CHARS]
    assert ev["device_ops"] == {"/device:TPU:0": [[10, 15, "fusion.1"],
                                                  [20, 27, name]]}
    assert ev["spans"] == [[0, 100, "bench.solve"]]

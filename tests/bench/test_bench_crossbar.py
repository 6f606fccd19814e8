"""The crossbar cell through the harness on the CPU, one pool call: the
program reads correct and its ``high`` control does not; and the
arithmetic of its two metrics on a hand-built run.

The CPU run takes the configuration's ``jnp`` twin of the Pallas path
(the same programmed conductances, noise and refinement, the read as a
dense product of the decoded blocks): interpreted Pallas kernels over
the pool call's ~45k lockstep iterations would take minutes here.  The
compiled read is checked against the plain reference in
``tests/test_crossbar_t1.py`` and on the chip."""
import _benchpath  # noqa: F401
import _tinycell
import numpy as np
import pytest

from bench import control, harness, spec, xbar_reference, xbar_work
from bench.gen import lp

CELL = "crossbar-t1"


def _cpu_cell():
    c = _tinycell.tiny_cell(CELL)
    c.config = dict(c.config, kernel="jnp")
    return c


@pytest.fixture(scope="module")
def program_run():
    return _tinycell.run(_cpu_cell())


def test_program_reads_correct(program_run):
    ok, numbers, run = program_run
    assert ok, numbers
    assert numbers["not_optimal"] == 0
    assert numbers["dots_below_highest"] == 0
    cfg = run.cell.config
    # each answer carries the analog reads its ledger charged, which the
    # reference's count from the bucket's iterations bears out
    for a in run.answers:
        assert a["analog_reads"] == a["ledger"]["mvm_count"] > 0
        assert a["analog_reads"] == xbar_reference.reads(
            a["executed_iterations"], cfg["check_every"],
            cfg["lanczos_iters"])
    reads = spec.load_file(spec.metric_path(
        "analog_reads_per_instance.stream")).read(run)
    assert reads == pytest.approx(np.mean([a["analog_reads"]
                                           for a in run.answers]))


def test_high_control_is_refused():
    c = _cpu_cell()
    assert control.control_of(c) == "high"
    with control.lower_precision("high"):
        ok, numbers, _ = _tinycell.run(c)
    assert not ok, numbers
    assert numbers["dots_below_highest"] > 0


def test_entry_refuses_a_device_unlike_the_configuration():
    c = spec.resolve(spec.load_benchmark(), CELL)
    cfg = dict(c.config, device_model=dict(c.config["device_model"],
                                           sigma_read=2e-3))
    entry = spec.load_file(spec.entry_path(c.entry))
    with pytest.raises(ValueError, match="sigma_read"):
        entry.make(cfg)


class _Trace:
    """Stands in for a device trace: round spans ``spans`` and device
    operations ``ops`` (start, end, name), in nanoseconds."""

    def __init__(self, spans, ops):
        self.spans = [(lo, hi, "bench.round") for lo, hi in spans]
        self.device_ops = {"/device:TPU:0": ops}
        self.busy = {"/device:TPU:0": [(s, e) for s, e, _ in ops]}

    def spans_named(self, name):
        return [s for s in self.spans if s[2] == name]

    def busy_ns(self, lo, hi):
        return sum(max(0, min(e, hi) - max(s, lo))
                   for s, e in self.busy["/device:TPU:0"])


def _inst(shape):
    return lp.Instance(name="x", shape=shape, c=None, b=None, lb=None,
                       ub=None, x_opt=None, y_opt=None, obj_opt=0.0)


def _run(answers, trace=None, traced=None, lanczos_iters=64):
    insts = [_inst((24, 65)), _inst((402, 655))]
    cell = spec.Cell(name=CELL, chips=1,
                     config={"check_every": 100, "dtype": "float32",
                             "lanczos_iters": lanczos_iters},
                     mix={"entry": "stream"}, end_to_end=[], per_layer=[])
    calls = [harness.Call(0.0, 1.0, insts, answers[:2]),
             harness.Call(1.0, 2.0, insts, answers[2:])]
    return harness.Run(cell=cell, seed=0, setup_s=0.0, calls=calls,
                       peaks={"hbm_bytes_per_s": 819e9}, trace=trace,
                       traced=traced)


ANSWERS = [{"iterations": 2500, "executed_iterations": 2500},
           {"iterations": 81200, "executed_iterations": 81200},
           {"iterations": 3000, "executed_iterations": 3000},
           {"iterations": 9000, "executed_iterations": 9000}]
S = 10 ** 9


def test_least_read_bytes_of_an_instance():
    # 2.04 products an iteration, each over the pair of an m x n block
    assert xbar_work.read_bytes(24, 65, 1000, 100, 4) == pytest.approx(
        1000 * 2.04 * 2 * 24 * 65 * 4)


def _bytes(answers):
    return (xbar_work.read_bytes(24, 65, answers[0]["iterations"], 100, 4)
            + xbar_work.read_bytes(402, 655, answers[1]["iterations"], 100,
                                   4))


def test_hbm_share_counts_the_rounds_the_trace_holds_whole():
    share = spec.load_file(spec.metric_path("xbar_hbm_share.stream"))
    spans = [(0, 2 * S), (2 * S, 4 * S)]
    # both rounds whole: a kept event starts after each round's span
    whole = [(0, S // 2, "a"), (2 * S + S // 4, 3 * S, "b"),
             (4 * S + 1, 4 * S + 2, "c")]
    busy = (S // 2 + 3 * S // 4) * 1e-9
    assert share.read(_run(ANSWERS, _Trace(spans, whole), traced=2)) == \
        pytest.approx(100 * (_bytes(ANSWERS) + _bytes(ANSWERS[2:]))
                      / busy / 819e9)
    # the events stop inside the second round: only the first counts,
    # in bytes and in busy time
    cut = [(0, S // 2, "a"), (2 * S + S // 4, 2 * S + S // 2, "b")]
    assert share.read(_run(ANSWERS, _Trace(spans, cut), traced=2)) == \
        pytest.approx(100 * _bytes(ANSWERS) / 0.5 / 819e9)
    # no round whole, or no trace: the metric is left out
    assert share.read(_run(ANSWERS, _Trace(spans, cut[:1]),
                           traced=2)) is None
    assert share.read(_run(ANSWERS)) is None


def test_analog_reads_are_the_references_count():
    reads = spec.load_file(spec.metric_path(
        "analog_reads_per_instance.stream"))
    # the reference's count, whatever the program's ledger says
    answers = [dict(a, analog_reads=1) for a in ANSWERS]
    want = [64 + 2 * e + 4 * (e // 100)
            for e in (2500, 81200, 3000, 9000)]
    assert reads.read(_run(answers)) == pytest.approx(np.mean(want))
    assert reads.read(_run(answers, lanczos_iters=0)) == pytest.approx(
        np.mean(want) - 64)
    # an entry that reports no executed iterations: the metric is left out
    assert reads.read(_run([{"iterations": 1}] * 4)) is None

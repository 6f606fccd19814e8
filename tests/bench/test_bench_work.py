"""The arithmetic behind the metrics: the nonzero count of the kernel
metric, the scheduler's shares, the reference's KKT error and its
precision count, and the generator's known optimum."""
import _benchpath  # noqa: F401
import numpy as np
import pytest

from bench import harness, kernels, reference, spec
from bench.gen import lp


def test_least_work_of_one_iteration():
    assert kernels.products_per_iteration(100) == pytest.approx(2.04)
    assert kernels.sparse_iteration_nnz(82000, 64) == pytest.approx(
        82000 * (2 + 4 / 64))


class _Trace:
    """Stands in for a device trace whose round spans hold ``busy_ns``."""

    def __init__(self, busy_ns):
        self.busy = {"/device:TPU:0": [(0, 1)]}
        self._busy_ns = busy_ns

    def spans_named(self, name):
        return [(0, 10, name)]

    def busy_ns(self, lo, hi):
        return self._busy_ns


def _inst(name, shape, nnz=None):
    coo = None if nnz is None else (np.ones(nnz), None, None)
    return lp.Instance(name=name, shape=shape, c=None, b=None, lb=None,
                       ub=None, x_opt=None, y_opt=None, obj_opt=0.0,
                       coo=coo)


def _metric(name):
    return spec.load_file(spec.metric_path(name))


def _run(insts, answers, config=None, trace=None):
    cell = spec.Cell(name="t", chips=1, config=config or {},
                     mix={"entry": "stream"}, end_to_end=[], per_layer=[])
    return harness.Run(cell=cell, seed=0, setup_s=0.0,
                       calls=[harness.Call(0.0, 2.0, insts, answers)],
                       peaks={}, trace=trace)


def test_ell_rate_counts_logical_nonzeros_times_needed_iterations():
    insts = [_inst("a", (24, 65), nnz=1000), _inst("b", (402, 655),
                                                  nnz=9000)]
    answers = [{"iterations": 400}, {"iterations": 1300}]
    run = _run(insts, answers, {"check_every": 100}, _Trace(0.25e9))
    work = 2.04 * (1000 * 400 + 9000 * 1300)
    assert _metric("ell_gnnz_per_s.stream").read(run) == pytest.approx(
        work / 0.25 / 1e9)


def test_device_metrics_read_nothing_without_a_trace():
    run = _run([_inst("a", (8, 16), nnz=20)], [{"iterations": 100}],
               {"check_every": 100})
    for name in ("ell_gnnz_per_s.stream", "idle_share.stream"):
        assert _metric(name).read(run) is None


def test_stream_shares_from_buckets_and_lanes():
    insts = [_inst(str(m), (m, 2 * m)) for m in (300, 400, 700)]
    answers = [{"iterations": 100, "bucket": (512, 1024), "lanes": 2},
               {"iterations": 300, "bucket": (512, 1024), "lanes": 2},
               {"iterations": 200, "bucket": (1024, 2048), "lanes": 1}]
    run = _run(insts, answers)
    useful = 100 + 300 + 200
    lanes = 2 * 300 + 1 * 200
    assert _metric("lane_useful_share.stream").read(run) == pytest.approx(
        100 * useful / lanes)
    logical = sum(m * 2 * m for m in (300, 400, 700))
    padded = 2 * 512 * 1024 + 1024 * 2048
    assert _metric("padded_work_share.stream").read(run) == pytest.approx(
        100 * logical / padded)
    assert _metric("instances_per_s").read(run) == pytest.approx(1.5)
    assert _metric("iters_per_instance.stream").read(run) == \
        pytest.approx(200)


def test_lane_share_refuses_to_read_without_lane_counts():
    run = _run([_inst("a", (8, 16))],
               [{"iterations": 100, "bucket": (32, 64), "lanes": None}])
    with pytest.raises(RuntimeError, match="lane count"):
        _metric("lane_useful_share.stream").read(run)


@pytest.mark.parametrize("density", [1.0, 0.08])
def test_generated_optimum_meets_every_kkt_condition(density):
    inst = lp.table1_lp("t", 40, 30, density, 10.0,
                        np.random.default_rng(5))
    assert inst.shape == (40, 70)
    nums = reference.answer_numbers(inst, inst.x_opt, inst.y_opt)
    assert nums["kkt"] < 1e-12 and nums["obj_rel_err"] < 1e-12
    # a point off the optimum, or a dual that prices a bound wrongly
    x = inst.x_opt.copy()
    x[0] += 0.5
    assert reference.answer_numbers(inst, x, inst.y_opt)["kkt"] > 1e-3
    y = inst.y_opt + 0.5
    assert reference.answer_numbers(inst, inst.x_opt, y)["kkt"] > 1e-3


HLO = """\
  %dot.1 = f32[4,32]{1,0} dot(f32[4,32,64]{2,1,0} %a, f32[4,64]{1,0} %b), lhs_batch_dims={0}, operand_precision={highest,highest}
  %convolution.2 = f32[4,1,512]{2,1,0:T(1,128)} convolution(%f, %g), window={size=4}, operand_precision={high,high}
  %dot.3 = f32[8]{0} dot(f32[8,8]{1,0} %c, f32[8]{0} %d), lhs_contracting_dims={1}
  %dot.4 = s32[8]{0} dot(s32[8,8]{1,0} %e, s32[8]{0} %h)
  %reduce.5 = f32[] reduce(%multiply.2, %constant.1), metadata={op_name="jit(f)/dot_general"}
"""


def test_products_below_highest_are_counted_from_the_hlo():
    # the HIGH convolution and the default-precision dot; not the
    # integer dot, nor a product XLA turned into multiply and reduce
    assert reference.dots_below_highest([HLO]) == 2
    assert reference.dots_below_highest([HLO.splitlines()[0]]) == 0

"""The crossbar-simulated Table-1 deployment against its plain reference
(``bench/xbar_reference.py``, numpy, no import of the program): the
Pallas read, the programming targets and their error, the refined
solve's answers and status, and each report's energy ledger."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PDHGOptions, engine
from repro.crossbar import EPIRAM, CrossbarBatchSolver, encode_core
from repro.crossbar.refine import solve_crossbar_refined
from repro.crossbar.solver import _array_dims
from repro.kernels import crossbar_mvm as xbar_kernel
from repro.kernels import ops
from repro.lp.problem import StandardLP
from repro.runtime.batch import pad_problem, prep_scale

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import reference, xbar_reference  # noqa: E402
from bench.gen import lp as gen  # noqa: E402

# the configuration's device constants, as the reference reads them
DEVICE = {k: getattr(EPIRAM, k) for k in (
    "crossbar_rows", "crossbar_cols", "avg_write_pulses",
    "write_pulse_energy_j", "write_pulse_latency_s",
    "read_energy_per_cell_j", "read_latency_s", "ecc")}


# ------------------------------------------------------------- the read ---

def _conductances(rng, lanes, side):
    levels = EPIRAM.g_levels - 1
    g = rng.integers(0, levels + 1, size=(2, lanes, side, side)) / levels
    return g.astype(np.float32)


@pytest.mark.parametrize("side", [128, 192, 512, 1152])
def test_pallas_read_matches_reference_under_vmap(side, x64):
    """The interpreted kernel, vmapped over 3 lanes at the M sides of the
    Table-1 tile buckets (192 is padded to the kernel's 128 tile inside
    the read), against the float64 reference.  1e-6 is a float32 product
    at HIGHEST; one bfloat16 pass (~1e-3, as the TPU's default precision
    would give) fails it, which the last assertion shows."""
    rng = np.random.default_rng(side)
    gp, gn = _conductances(rng, 3, side)
    v = rng.normal(size=(3, side)).astype(np.float32)
    scale = rng.uniform(0.5, 2.0, size=3).astype(np.float32)
    noise = (EPIRAM.sigma_read
             * rng.normal(size=(3, side))).astype(np.float32)
    got = jax.vmap(lambda a, b, s, z, x: ops.crossbar_mvm(
        a, b, x, s, z, interpret=True))(gp, gn, scale, noise, v)
    want = xbar_reference.read(gp, gn, scale, noise, v)
    err = np.linalg.norm(np.asarray(got, np.float64) - want) / \
        np.linalg.norm(want)
    assert err <= 1e-6, err
    one_pass = jnp.einsum("lrc,lc->lr", jnp.asarray(gp - gn, jnp.bfloat16),
                          jnp.asarray(v, jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    bf16 = (scale[:, None] * (1 + noise)) * np.asarray(one_pass, np.float64)
    assert np.linalg.norm(bf16 - want) / np.linalg.norm(want) > 1e-4


def test_read_precision_follows_the_programs_setting():
    """The kernel reads ``symblock.MVM_PRECISION`` when it is traced, so
    the benchmark's ``high`` control reaches the read: Mosaic contracts
    at float32 or in one bfloat16 pass, and the control gets the pass."""
    from bench import control

    z = jnp.zeros((128, 128), jnp.float32)
    v = jnp.zeros((128, 1), jnp.float32)

    def precisions():
        text = str(jax.make_jaxpr(lambda: xbar_kernel.crossbar_mvm_padded(
            z, z, v, v, interpret=True))())
        return {p for p in ("HIGHEST", "HIGH", "DEFAULT")
                if f"precision=(Precision.{p}," in text}

    assert precisions() == {"HIGHEST"}
    with control.lower_precision("high"):
        assert precisions() == {"DEFAULT"}
    assert precisions() == {"HIGHEST"}


# the Table-1 tile buckets, and one whose m starts its K^T block
# mid-tile and whose 380-wide M leaves padding inside the array
WINDOWS = [(64, 64), (64, 128), (192, 320), (448, 704), (130, 250)]


def _operator_reads(m, n):
    """Both windowed products of ``engine.crossbar_operator``, vmapped
    over 3 lanes of planted conductances (nonzero in the zero blocks of
    M and in the array's padding too), each beside its input, the
    columns that input fills and the rows the product keeps."""
    R, C = _array_dims(m, n, EPIRAM)
    rng = np.random.default_rng([m, n])
    gp, gn = _conductances(rng, 3, R)
    scale = rng.uniform(0.5, 2.0, size=3).astype(np.float32)
    keys = jax.random.split(jax.random.PRNGKey(m + n), 3)
    for mode, cols, rows in (("fwd", slice(m, m + n), slice(0, m)),
                             ("adj", slice(0, m), slice(m, m + n))):
        x = rng.normal(size=(3, cols.stop - cols.start)).astype(np.float32)
        got = jax.vmap(lambda a, b, s, v, k: getattr(
            engine.crossbar_operator(a, b, s, m, n,
                                     sigma_read=EPIRAM.sigma_read,
                                     interpret=True), mode)(v, k))(
            gp, gn, scale, x, keys)
        noise = jax.vmap(lambda k: EPIRAM.sigma_read * jnp.clip(
            jax.random.normal(k, (R,), jnp.float32), -4.0, 4.0))(keys)
        v = np.zeros((3, C), np.float32)
        v[:, cols] = x
        yield (gp, gn, scale, noise, v, rows), np.asarray(got)


@pytest.mark.parametrize("m, n", WINDOWS)
def test_windowed_read_equals_the_whole_pair_read(m, n, x64):
    """The operator's products visit only their block's tiles; each
    equals the read of the whole pair, sliced, bit for bit, whatever the
    skipped tiles hold."""
    for (gp, gn, scale, noise, v, rows), got in _operator_reads(m, n):
        whole = jax.vmap(lambda a, b, s, z, x: ops.crossbar_mvm(
            a, b, x, s, z, interpret=True))(gp, gn, scale, noise, v)
        np.testing.assert_array_equal(got, np.asarray(whole)[:, rows])


@pytest.mark.parametrize("m, n", WINDOWS)
def test_windowed_read_matches_reference(m, n, x64):
    for (gp, gn, scale, noise, v, rows), got in _operator_reads(m, n):
        want = xbar_reference.read(gp, gn, scale, np.asarray(noise),
                                   v)[:, rows]
        err = np.linalg.norm(got.astype(np.float64) - want) / \
            np.linalg.norm(want)
        assert err <= 1e-6, err


# ------------------------------------------------------------ programming ---

def test_encode_targets_equal_reference_quantize(x64):
    rng = np.random.default_rng(0)
    W = rng.normal(size=(512, 512)).astype(np.float32)
    W[rng.random(W.shape) < 0.3] = 0.0
    gp, gn, s, nz = encode_core(jnp.asarray(W), jax.random.PRNGKey(1),
                                EPIRAM.g_levels, 0.0)
    qp, qn, qs = xbar_reference.quantize(W, EPIRAM.g_levels)
    assert float(s) == qs
    np.testing.assert_array_equal(np.asarray(gp), qp)
    np.testing.assert_array_equal(np.asarray(gn), qn)
    assert int(nz) == xbar_reference.programmed_pairs(W, EPIRAM.g_levels)


def test_programming_error_is_unbiased_at_sigma_program(x64):
    rng = np.random.default_rng(1)
    W = rng.normal(size=(512, 512)).astype(np.float32)
    gp, gn, _, _ = encode_core(jnp.asarray(W), jax.random.PRNGKey(2),
                               EPIRAM.g_levels, EPIRAM.sigma_program)
    qp, qn, _ = xbar_reference.quantize(W, EPIRAM.g_levels)
    got = np.concatenate([np.asarray(gp).ravel(), np.asarray(gn).ravel()])
    target = np.concatenate([qp.ravel(), qn.ravel()]).astype(np.float64)
    # cells at the top level are clipped at 1 and cells at 0 stay 0
    keep = (target > 0) & (target < 1)
    rel = got[keep] / target[keep] - 1.0
    assert rel.size >= 100_000
    sigma = EPIRAM.sigma_program
    assert abs(rel.mean()) < 5 * sigma / np.sqrt(rel.size)
    assert abs(rel.std() / sigma - 1.0) < 0.1


# ------------------------------------------------- solves, status, ledger ---

# three instances of the first Table-1 bucket, (64, 64), from the
# benchmark pool's call 2 (gen-ip016, gen-ip021, gen-ip054), whose analog
# solves converge in a few thousand iterations
POOL, CALL = 1, 2
PICK = {1: ("gen-ip016", 24, 28), 2: ("gen-ip021", 28, 35),
        4: ("gen-ip054", 27, 30)}
OPTS = PDHGOptions(max_iters=40000, tol=1e-3, check_every=100,
                   dtype=np.float32, refine_rounds=3, refine_tol=1e-4)


def _instances():
    return [gen.table1_lp(name, m, n, 1.0, 10.0,
                          np.random.default_rng([POOL, CALL, j]))
            for j, (name, m, n) in PICK.items()]


def _program(inst):
    f32 = np.float32
    return StandardLP(c=inst.c.astype(f32), K=inst.K.astype(f32),
                      b=inst.b.astype(f32), lb=inst.lb.astype(f32),
                      ub=inst.ub.astype(f32), name=inst.name)


def _solve(opts, kernel="jnp"):
    insts = _instances()
    solver = CrossbarBatchSolver(opts, device=EPIRAM, kernel=kernel)
    return insts, solver.solve_stream([_program(i) for i in insts]), solver


@pytest.fixture(scope="module")
def refined():
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        yield _solve(OPTS)
    finally:
        jax.config.update("jax_enable_x64", old)


def _check_answers(insts, reports):
    for inst, rep in zip(insts, reports):
        nums = reference.answer_numbers(inst, rep.result.x, rep.result.y)
        assert rep.result.status == "optimal", (inst.name, rep.result.merit)
        assert rep.result.merit <= OPTS.refine_tol
        assert nums["kkt"] <= 1e-4, (inst.name, nums)


def test_refined_crossbar_reaches_the_target_jnp(refined):
    insts, reports, _ = refined
    _check_answers(insts, reports)


def test_refined_crossbar_reaches_the_target_pallas(x64):
    insts, reports, solver = _solve(OPTS, kernel="pallas")
    _check_answers(insts, reports)
    assert solver.opts.kernel == "pallas"


def test_refined_answer_above_refine_tol_is_not_optimal(x64):
    """A planted case: one round, and a target float32 cannot reach.
    The inner stop (tol) is met, so the answer would read optimal if
    tol were the bar; its digital merit is above refine_tol, so it reads
    iteration_limit, in the batched and in the eager path."""
    opts = dataclasses.replace(OPTS, refine_rounds=1, refine_tol=1e-12)
    insts, reports, _ = _solve(opts)
    for rep in reports:
        assert opts.refine_tol < rep.result.merit <= opts.tol
        assert rep.result.status == "iteration_limit"
    eager = solve_crossbar_refined(_program(insts[0]), opts, EPIRAM)
    assert opts.refine_tol < eager.result.merit <= opts.tol
    assert eager.result.status == "iteration_limit"
    # without a refine_tol the target is tol again
    eager = solve_crossbar_refined(
        _program(insts[0]), dataclasses.replace(opts, refine_tol=0.0),
        EPIRAM)
    assert eager.result.status == "optimal"


@pytest.mark.parametrize("name, m, n, bucket, share", [
    ("neos5", 402, 253, (448, 704), 24 / 81),
    ("gen-ip016", 24, 28, (64, 64), 1.0)])
def test_tile_counters(name, m, n, bucket, share, x64):
    """``xbar_tiles_read`` counts the tiles the windowed products visit,
    ``xbar_tiles_array`` those whole-pair reads would: 24 of 81 a read
    at M 1152, and the single tile at M 128."""
    inst = gen.table1_lp(name, m, n, 1.0, 10.0, np.random.default_rng(0))
    opts = dataclasses.replace(OPTS, max_iters=100, refine_rounds=0)
    solver = CrossbarBatchSolver(opts, device=EPIRAM, kernel="pallas")
    reports = solver.solve_stream([_program(inst)])
    assert solver._bucket(*inst.K.shape) == bucket
    stats = solver.last_stream_stats
    R, C = _array_dims(*bucket, EPIRAM)
    whole = -(-R // xbar_kernel.TILE_R) * -(-C // xbar_kernel.TILE_C)
    assert stats["xbar_tiles_array"] == reports[0].pdhg_mvms * whole
    assert stats["xbar_tiles_read"] == \
        pytest.approx(share * stats["xbar_tiles_array"], rel=1e-12)


def _programmed_pairs(lp, bucket):
    """Nonzero-target pairs of the bucket's programmed M, from the
    program's scaling and the reference's quantization."""
    mb, nb = bucket
    p = pad_problem(lp, mb, nb)
    Ks = np.asarray(prep_scale(*(jnp.asarray(a) for a in (
        p.K, p.b, p.c, p.lb, p.ub)), OPTS)[0])
    R, C = _array_dims(mb, nb, EPIRAM)
    M = np.zeros((R, C), Ks.dtype)
    M[:mb, mb:mb + nb] = Ks
    M[mb:mb + nb, :mb] = Ks.T
    return xbar_reference.programmed_pairs(M, EPIRAM.g_levels), R * C


def test_each_ledger_equals_the_reference_ledger(refined):
    insts, reports, solver = refined
    for inst, rep in zip(insts, reports):
        lp = _program(inst)
        m, n = lp.K.shape
        nz, pairs_total = _programmed_pairs(lp, solver._bucket(m, n))
        want = xbar_reference.ledger(
            DEVICE, nz, (m + n) ** 2, pairs_total,
            xbar_reference.reads(rep.executed_iterations, OPTS.check_every,
                                 OPTS.lanczos_iters))
        got = dataclasses.asdict(rep.ledger)
        for k, v in want.items():
            assert got[k] == pytest.approx(v, rel=1e-12), k


def test_refinement_writes_no_cells(refined, x64):
    _, reports, solver = refined
    _, plain, _ = _solve(dataclasses.replace(OPTS, refine_rounds=0))
    assert [r.ledger.cells_written for r in plain] == \
        [r.ledger.cells_written for r in reports]
    assert [r.ledger.write_energy_j for r in plain] == \
        [r.ledger.write_energy_j for r in reports]
    stats = solver.last_stream_stats
    assert stats["analog_mvms"] == sum(r.ledger.mvm_count for r in reports)
    assert stats["digital_mvms"] == 3 * (2 + 2 * OPTS.refine_rounds)
    assert stats["cells_written"] == sum(r.ledger.cells_written
                                         for r in reports)
    assert stats["write_energy_j"] == pytest.approx(
        sum(r.ledger.write_energy_j for r in reports))
    assert stats["read_energy_j"] == pytest.approx(
        sum(r.ledger.read_energy_j for r in reports))

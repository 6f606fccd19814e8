"""End-to-end behaviour tests for the paper's system.

The full pipeline of Figure 1: model preparation (Ruiz + preconditioning)
-> encode-once to the accelerator -> Lanczos step sizing -> PDHG -> KKT
stopping -> unscale, on every backend (exact / noisy / crossbar-sim /
distributed), validated against ground truth (bundled simplex or
constructed optima)."""
import jax
import numpy as np
import pytest

from repro.core import NoiseModel, PDHGOptions, solve, solve_jit
from repro.crossbar import EPIRAM, TAOX_HFOX, solve_crossbar_jit
from repro.crossbar.array import crossbar_accel_factory
from repro.lp import (
    assignment_lp,
    pagerank_lp,
    random_standard_lp,
    simplex,
    table1_instance,
)


def test_pipeline_exact_vs_simplex(x64):
    """Figure-1 'first function': RRAM-solver answer vs ground truth."""
    lp = table1_instance("gen-ip002")
    gt = simplex.solve(lp)
    assert gt.status == "optimal"
    r = solve_jit(lp, PDHGOptions(max_iters=40000, tol=1e-7))
    assert r.status == "optimal"
    assert abs(r.obj - gt.obj) / abs(gt.obj) < 1e-5


def test_pipeline_all_table1_instances(x64):
    """Every Table-1-shaped instance solves to its known optimum."""
    for name in ("gen-ip016", "gen-ip021", "gen-ip036", "gen-ip054"):
        lp = table1_instance(name)
        r = solve_jit(lp, PDHGOptions(max_iters=60000, tol=1e-6))
        rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel < 1e-4, (name, rel, r.status)


def test_pipeline_noisy_backend_converges(x64):
    lp = random_standard_lp(16, 28, seed=0)
    r = solve(lp, PDHGOptions(max_iters=12000, tol=1e-4, check_every=100),
              noise=NoiseModel("multiplicative", 1e-3))
    rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
    assert rel < 2e-2


def test_pipeline_crossbar_host_loop(x64):
    """Full device-physics path through Algorithm 2 host iterations."""
    lp = random_standard_lp(12, 20, seed=1)
    fac = crossbar_accel_factory(device=TAOX_HFOX)
    r = solve(lp, PDHGOptions(max_iters=6000, tol=1e-4, check_every=100,
                              lanczos_iters=24), accel_factory=fac)
    rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
    # conductance quantization + programming error perturb the problem
    # itself; the paper's Table-2 gaps reach 2.98e-2 — same band here
    assert rel < 5e-2
    led = fac.ledger
    assert led.mvm_count == r.mvm_calls
    assert led.write_energy_j > 0 and led.read_energy_j > 0


def test_pipeline_crossbar_jit_both_devices(x64):
    """Programming error and read noise are random draws, so one seed's
    gap is one sample: the band holds for the median over five seeds."""
    lp = random_standard_lp(16, 28, seed=2)
    for dev in (EPIRAM, TAOX_HFOX):
        rels = []
        for seed in range(5):
            rep = solve_crossbar_jit(
                lp, PDHGOptions(max_iters=15000, tol=1e-5, check_every=100,
                                lanczos_iters=32, seed=seed), device=dev)
            rels.append(abs(rep.result.obj - lp.obj_opt) / abs(lp.obj_opt))
        # paper Table-2 gap band
        assert np.median(rels) < 5e-2, (dev.name, rels)


def test_assignment_lp_integral_solution(x64):
    """Assignment LP optimum is integral (total unimodularity)."""
    lp = assignment_lp(4, seed=0)
    r = solve_jit(lp, PDHGOptions(max_iters=40000, tol=1e-7))
    gt = simplex.solve(lp)
    assert abs(r.obj - gt.obj) / abs(gt.obj) < 1e-4
    X = r.x.reshape(4, 4)
    assert np.allclose(X.sum(0), 1, atol=1e-3)
    assert np.allclose(X.sum(1), 1, atol=1e-3)
    assert np.all((X < 1e-2) | (X > 1 - 1e-2))   # integral


def test_pagerank_lp(x64):
    lp = pagerank_lp(64, seed=0)
    r = solve_jit(lp, PDHGOptions(max_iters=40000, tol=1e-7))
    assert r.status == "optimal"
    assert abs(r.x.sum() - 1.0) < 1e-4            # pagerank sums to 1
    assert np.all(r.x >= -1e-8)


def test_energy_factors_match_paper_magnitudes(x64):
    """The headline claim: orders-of-magnitude energy savings vs GPU.

    Uses the same cost models as the benchmark harness; asserts the
    factor ranges of Tables 2-3 (10x..5000x energy, >=1x latency for the
    PDHG phase on TaOx-HfOx)."""
    from repro.crossbar import Ledger, RTX6000

    lp = random_standard_lp(24, 41, seed=3)
    m, n = lp.K.shape
    opts = PDHGOptions(max_iters=15000, tol=1e-5, check_every=100,
                       lanczos_iters=32)
    rep = solve_crossbar_jit(lp, opts, device=TAOX_HFOX)
    gpu = Ledger()
    res = solve_jit(lp, opts)
    RTX6000.h2d(8 * (m * n + m + n), gpu)
    for _ in range(res.iterations):
        RTX6000.pdhg_iteration(m, n, gpu)
    e_factor = gpu.total_energy_j / rep.ledger.total_energy_j
    t_factor = gpu.total_latency_s / rep.ledger.total_latency_s
    assert e_factor > 10, e_factor
    assert t_factor > 1, t_factor

"""Step-size schedules (``PDHGOptions.step_rule``) + norm reuse.

The tentpole contract has three legs, each pinned here:

  * ``"fixed"`` is BITWISE-identical to the pre-step_rule solver on
    every backend — it is the default ``step_rule`` of the static solve
    options and the traced loop is unchanged (no extra carry, no extra
    ops).
  * ``"adaptive"`` (data-driven primal-weight init + PDLP rebalancing at
    restart events + down-only step safeguard) converges at least as
    fast as fixed on scale-imbalanced instances and never worse than
    modestly on balanced ones, at equal-or-better KKT residuals.
  * ``"strongly_convex"`` is the explicit opt-in for the accelerated
    theta schedule; option validation refuses the incoherent combos.

Plus the satellite subsystems: the ``norm_backend`` estimator switch
and ``BatchSolver(norm_reuse=True)`` cross-instance norm reuse.
"""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PDHGOptions, engine, solve, solve_jit
from repro.core.lanczos import (
    NORM_BACKENDS,
    lanczos_svd_jit,
    power_iteration_mv,
)
from repro.core.pdhg import opts_static, prepare
from repro.core.symblock import build_sym_block
from repro.lp import random_standard_lp
from repro.runtime import BatchSolver
from repro.runtime.batch import NORM_REFINE_ITERS


def _imbalanced(m=20, n=32, seed=1, cscale=100.0):
    """Objective and rhs in mismatched units — Ruiz equilibration of K
    cannot see it, the primal weight can."""
    lp = random_standard_lp(m, n, seed=seed)
    return dc.replace(lp, c=lp.c * cscale)


# -------------------------------------------- fixed = bitwise legacy ---

def test_fixed_rule_bitwise_matches_12_tuple_core(x64):
    """``step_rule="fixed"`` is the default: the static options of the
    default and of an explicit "fixed" are equal, so they share one
    trace and give bitwise-equal iterates, for both the jnp and pallas
    update kernels and for the megakernel window mode."""
    lp = random_standard_lp(8, 14, seed=2)
    opts = PDHGOptions()
    scaled, T, Sigma = prepare(lp, opts)
    Keff = np.sqrt(np.asarray(Sigma))[:, None] * np.asarray(scaled.K) \
        * np.sqrt(np.asarray(T))[None, :]
    rho = float(np.linalg.svd(Keff, compute_uv=False)[0])
    key = jax.random.PRNGKey(5)
    core = jax.jit(engine.solve_core, static_argnums=(10,))
    args = (scaled.K, scaled.K.T, scaled.b, scaled.c, scaled.lb,
            scaled.ub, T, Sigma, rho, key)
    base = PDHGOptions(max_iters=256, tol=1e-30, check_every=64)

    for kernel, mega in (("jnp", False), ("pallas", False),
                         ("jnp", True)):
        default = opts_static(dc.replace(base, kernel=kernel,
                                         megakernel=mega))
        fixed = opts_static(dc.replace(base, kernel=kernel, megakernel=mega,
                                       step_rule="fixed"))
        assert default == fixed and hash(default) == hash(fixed)
        x_def, y_def, it_def, m_def = core(*args, default)
        x_fix, y_fix, it_fix, m_fix = core(*args, fixed)
        assert int(it_def) == int(it_fix)
        np.testing.assert_array_equal(np.asarray(x_def), np.asarray(x_fix))
        np.testing.assert_array_equal(np.asarray(y_def), np.asarray(y_fix))
        np.testing.assert_array_equal(np.asarray(m_def), np.asarray(m_fix))

    # ...and the adaptive rule is LIVE: same args, different trajectory
    x_ad, _, _, _ = core(*args, opts_static(dc.replace(
        base, step_rule="adaptive")))
    x_fix, _, _, _ = core(*args, opts_static(base))
    assert not np.array_equal(np.asarray(x_ad), np.asarray(x_fix))


def test_fixed_rule_bitwise_on_batch_and_sparse_paths(x64):
    """An explicit step_rule="fixed" option must serve bit-identical
    results through BatchSolver (dense and sparse-ELL pipelines)."""
    opts = PDHGOptions(max_iters=512, tol=1e-6, check_every=64)
    fixed = dc.replace(opts, step_rule="fixed")
    dense = random_standard_lp(8, 14, seed=3)
    sparse = random_standard_lp(12, 20, seed=4).sparsified()
    for lp in (dense, sparse):
        r0 = BatchSolver(opts).solve_stream([lp])[0]
        r1 = BatchSolver(fixed).solve_stream([lp])[0]
        assert r0.iterations == r1.iterations
        np.testing.assert_array_equal(r0.x, r1.x)
        np.testing.assert_array_equal(r0.y, r1.y)


def test_step_rule_is_in_batch_cache_key(x64):
    """adaptive and fixed trace different loops; the executable cache
    must never cross-serve them."""
    lp = random_standard_lp(8, 14, seed=1)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64)
    s_fix = BatchSolver(opts)
    s_ad = BatchSolver(dc.replace(opts, step_rule="adaptive"))
    s_fix.solve_stream([lp])
    s_ad.solve_stream([lp])
    assert set(s_fix._cache).isdisjoint(set(s_ad._cache))
    assert opts_static(s_fix.opts) != opts_static(s_ad.opts)


# ----------------------------------------------- option validation ---

def test_step_rule_validation():
    with pytest.raises(ValueError, match="step_rule"):
        opts_static(PDHGOptions(step_rule="bogus"))
    # strongly_convex is the explicit opt-in for gamma > 0 ...
    with pytest.raises(ValueError, match="gamma"):
        opts_static(PDHGOptions(step_rule="strongly_convex", gamma=0.0))
    # ... and the other rules refuse a silently-ignored gamma
    with pytest.raises(ValueError, match="gamma"):
        opts_static(PDHGOptions(step_rule="adaptive", gamma=0.1))
    with pytest.raises(ValueError, match="gamma"):
        opts_static(PDHGOptions(step_rule="fixed", gamma=0.1))
    opts_static(PDHGOptions(step_rule="strongly_convex", gamma=0.1))


def test_norm_backend_validation():
    with pytest.raises(ValueError, match="norm_backend"):
        solve_jit(random_standard_lp(6, 10, seed=0),
                  PDHGOptions(norm_backend="qr", max_iters=8))


# ------------------------------------------------- adaptive behavior ---

def test_adaptive_beats_fixed_on_imbalanced_instances(x64):
    """The acceptance scenario: on objective/rhs scale-imbalanced LPs the
    primal-weight machinery must converge in at most the fixed-rule
    iteration count (typically far fewer), at equal-or-better KKT."""
    opts_f = PDHGOptions(max_iters=8000, tol=1e-4, check_every=64)
    opts_a = dc.replace(opts_f, step_rule="adaptive")
    wins = 0
    for seed, cscale in ((1, 100.0), (2, 100.0), (3, 0.01)):
        lp = _imbalanced(seed=seed, cscale=cscale)
        rf = solve_jit(lp, opts_f)
        ra = solve_jit(lp, opts_a)
        assert ra.status == "optimal"
        assert ra.iterations <= rf.iterations
        if ra.iterations < rf.iterations:
            wins += 1
        # equal-or-better: the returned iterate satisfies the SAME tol
        # the fixed rule was asked for (fixed may overshoot below it by
        # running longer; that is not a quality bar adaptive must match)
        assert float(ra.residuals.max) <= opts_a.tol
    assert wins >= 2   # strictly faster on most instances, not a tie


def test_adaptive_host_and_jit_agree_on_status(x64):
    """Host driver and jitted core run the same engine rebalance math;
    they must agree on convergence (iterates may differ slightly: the
    host checks every iteration near the end, the core on boundaries)."""
    lp = _imbalanced(m=20, n=32, seed=1)
    opts = PDHGOptions(max_iters=12000, tol=1e-4, check_every=64,
                       step_rule="adaptive")
    rh = solve(lp, opts)
    rj = solve_jit(lp, opts)
    assert rh.status == rj.status == "optimal"
    np.testing.assert_allclose(rh.obj, rj.obj, rtol=1e-3, atol=1e-6)


def test_adaptive_megakernel_matches_stepped_loop(x64):
    """tau/sigma only move at check boundaries, OUTSIDE the fused
    window — megakernel and stepped adaptive runs must agree."""
    lp = _imbalanced(m=10, n=18, seed=6)
    opts = PDHGOptions(max_iters=2000, tol=1e-4, check_every=64,
                       step_rule="adaptive")
    r_ref = solve_jit(lp, opts)
    r_meg = solve_jit(lp, dc.replace(opts, megakernel=True))
    assert r_meg.iterations == r_ref.iterations
    assert r_meg.status == r_ref.status
    np.testing.assert_allclose(r_meg.x, r_ref.x, atol=1e-8, rtol=1e-8)


def test_strongly_convex_rule_converges(x64):
    """gamma > 0 under the explicit rule: the accelerated theta schedule
    still converges to the optimum (iterates shrink tau, grow sigma)."""
    lp = random_standard_lp(12, 20, seed=7)
    opts = PDHGOptions(max_iters=20000, tol=1e-5,
                       step_rule="strongly_convex", gamma=0.05)
    r = solve_jit(lp, opts)
    assert r.status == "optimal"
    assert lp.obj_opt is not None
    np.testing.assert_allclose(r.obj, lp.obj_opt, rtol=1e-2, atol=1e-4)


# ------------------------------------- iteration quantization (audit) ---

def test_jit_iterations_quantized_to_check_every(x64):
    """Jitted paths exit only at check boundaries, so
    ``PDHGResult.iterations`` is a multiple of check_every (megakernel
    and stepped alike), and the MVM ledger charges exactly the
    ``engine.mvm_accounting`` formula for that count.  The HOST driver
    checks cheaply every iteration once past the first boundary — its
    count may be finer; this asymmetry is the documented contract."""
    lp = random_standard_lp(10, 18, seed=8)
    opts = PDHGOptions(max_iters=2000, tol=1e-5, check_every=48)
    for o in (opts, dc.replace(opts, megakernel=True)):
        r = solve_jit(lp, o)
        assert r.status == "optimal"
        assert r.iterations % o.check_every == 0
        assert r.mvm_calls == engine.mvm_accounting(
            r.iterations, o.check_every, o.lanczos_iters)


# -------------------------------------------------- norm backends ---

def test_power_backend_matches_lanczos_estimate(x64):
    """Both estimators target ||Sigma^1/2 K T^1/2||_2 through the
    symmetric block; on random LPs they agree with the exact SVD to the
    tolerance the step sizes care about."""
    assert set(NORM_BACKENDS) == {"lanczos", "power"}
    lp = random_standard_lp(16, 28, seed=9)
    scaled, T, Sigma = prepare(lp, PDHGOptions())
    Keff = jnp.sqrt(Sigma)[:, None] * scaled.K * jnp.sqrt(T)[None, :]
    exact = float(np.linalg.svd(np.asarray(Keff), compute_uv=False)[0])
    M = build_sym_block(Keff)
    lan = float(lanczos_svd_jit(M, k_max=64))
    pw = float(power_iteration_mv(lambda v: M @ v, M.shape[0], M.dtype,
                                  iters=200))
    assert abs(lan - exact) / exact < 1e-6
    assert abs(pw - exact) / exact < 1e-3

    r_l = solve_jit(lp, PDHGOptions(max_iters=2000, tol=1e-5))
    r_p = solve_jit(lp, PDHGOptions(max_iters=2000, tol=1e-5,
                                    norm_backend="power"))
    assert r_l.status == r_p.status == "optimal"
    np.testing.assert_allclose(r_l.obj, r_p.obj, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------- norm reuse ---

def test_norm_reuse_seeds_repeat_instances(x64):
    """Second pass over the same stream: every bucket is served by the
    seeded executable (short power refine instead of full Lanczos), the
    ledger charges NORM_REFINE_ITERS, and results still converge to the
    same objectives."""
    lps = [random_standard_lp(8, 14, seed=s) for s in (0, 1)]
    opts = PDHGOptions(max_iters=1500, tol=1e-4, check_every=64)
    solver = BatchSolver(opts, norm_reuse=True)
    r1 = solver.solve_stream(lps)
    assert solver.last_stream_stats["norm_seeded_buckets"] == 0
    r2 = solver.solve_stream(lps)
    assert solver.last_stream_stats["norm_seeded_buckets"] >= 1
    for a, b in zip(r1, r2):
        assert b.status == a.status
        np.testing.assert_allclose(b.obj, a.obj, rtol=1e-4, atol=1e-6)
        if a.iterations == b.iterations:
            # identical trajectory => ledger differs ONLY by the norm
            # charge: full Lanczos (pass 1) vs the seeded refine (pass 2)
            assert a.mvm_calls - b.mvm_calls \
                == opts.lanczos_iters - NORM_REFINE_ITERS


def test_norm_cache_isolated_by_fingerprint(x64):
    """Different sparsity patterns in the same shape bucket must not
    share cache entries; dense entries key on the bucket shape."""
    from repro.lp import sparse_random_standard_lp

    solver = BatchSolver(PDHGOptions(max_iters=256, tol=1e-30,
                                     check_every=64), norm_reuse=True)
    a = sparse_random_standard_lp(10, 18, density=0.3, seed=0)
    b = sparse_random_standard_lp(10, 18, density=0.3, seed=3)
    solver.solve_stream([a, b])
    fps = {solver._norm_fingerprint(lp) for lp in (a, b)}
    assert len(fps) == 2                      # patterns differ => keys do
    assert set(solver._norm_cache) == fps
    # reuse off => cache never populated
    cold = BatchSolver(PDHGOptions(max_iters=128, tol=1e-30))
    cold.solve_stream([random_standard_lp(8, 14, seed=0)])
    assert cold._norm_cache == {}

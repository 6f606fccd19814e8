"""Engine parity: ONE iteration core, N operator backends, 2 update
backends.

Every solver path (host / jit / batch / crossbar / distributed) runs
``core.engine``'s step; these tests pin that the backends agree iterate-
for-iterate in exact mode, that the MVM-ledger accounting is the single
``engine.mvm_accounting`` formula everywhere, and that the ``kernel``
flag (jnp vs fused Pallas) never leaks across executable caches."""
import dataclasses as dc
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import NoiseModel, PDHGOptions, engine, solve, solve_jit
from repro.core.pdhg import opts_static, prepare
from repro.core.symblock import encode_exact
from repro.lp import random_standard_lp
from repro.runtime import BatchSolver


def _prepped(seed=0, m=8, n=14):
    """Common preconditioned problem + exact operator norm."""
    lp = random_standard_lp(m, n, seed=seed)
    scaled, T, Sigma = prepare(lp, PDHGOptions())
    Keff = np.sqrt(np.asarray(Sigma))[:, None] * np.asarray(scaled.K) \
        * np.sqrt(np.asarray(T))[None, :]
    rho = float(np.linalg.svd(Keff, compute_uv=False)[0])
    return lp, scaled, T, Sigma, rho


# ------------------------------------------------------ backend parity ---

def test_engine_backends_identical_iterates(x64):
    """host-style accel / jit dense / vmapped batch / fused Pallas all run
    the SAME seeded 2-check exact-mode solve to identical iterates."""
    _, scaled, T, Sigma, rho = _prepped(seed=0)
    m, n = scaled.K.shape
    b, c, lb, ub = scaled.b, scaled.c, scaled.lb, scaled.ub
    key = jax.random.PRNGKey(42)
    static = (128, 1e-30, 0.95, 1.0, 0.0, 64, 0.5, 0.0, "jnp")

    # (a) jitted dense engine (the solve_jit / batch core)
    core = jax.jit(engine.solve_core, static_argnums=(10,))
    x_jit, y_jit, it_jit, _ = core(scaled.K, scaled.K.T, b, c, lb, ub,
                                   T, Sigma, rho, key, static)
    assert int(it_jit) == 128

    # (b) eager host-style engine over an Accel handle
    op = engine.accel_operator(encode_exact(scaled.K))
    key2, x0, y0 = engine.draw_init(key, m, n, lb, ub, scaled.K.dtype)
    x_acc, y_acc, _, _ = engine.pdhg_loop(
        op, engine.JNP_UPDATES, b, c, lb, ub, T, Sigma, x0, y0,
        0.95 / rho, 0.95 * rho / rho**2, key2,
        max_iters=128, tol=1e-30, gamma=0.0, check_every=64,
        restart_beta=0.5)

    # (c) vmapped batch-of-2 engine; slot 0 carries the same key
    keys = jnp.stack([key, jax.random.PRNGKey(7)])
    xs, ys, _, _ = jax.jit(jax.vmap(
        lambda k: engine.solve_core(scaled.K, scaled.K.T, b, c, lb, ub,
                                    T, Sigma, rho, k, static)))(keys)

    # (d) fused Pallas update backend
    x_pal, y_pal, _, _ = core(scaled.K, scaled.K.T, b, c, lb, ub,
                              T, Sigma, rho, key,
                              static[:-1] + ("pallas",))

    for tag, (xv, yv) in {
        "accel": (x_acc, y_acc),
        "batch": (xs[0], ys[0]),
        "pallas": (x_pal, y_pal),
    }.items():
        np.testing.assert_allclose(np.asarray(xv), np.asarray(x_jit),
                                   rtol=1e-12, atol=1e-12, err_msg=tag)
        np.testing.assert_allclose(np.asarray(yv), np.asarray(y_jit),
                                   rtol=1e-12, atol=1e-12, err_msg=tag)
    # distinct key in slot 1 => genuinely different trajectory
    assert not np.allclose(np.asarray(xs[1]), np.asarray(x_jit))


def test_pdhg_loop_reports_merit_of_returned_iterate(x64):
    """Regression (ISSUE 4): the check block used to carry
    ``min(merit, merit_avg)``, adopting the AVERAGED iterate's merit even
    when ``use_avg`` was False — so a stream whose averaged merit dips
    below the current iterate's (without being adopted) exited reporting
    a residual the returned solution does not satisfy, and every jitted
    path derived ``converged``/``status`` from that lie.

    The contrived residual_fn below distinguishes the two evaluations
    structurally (the averaged check passes x_prev == x): the averaged
    merit (0.5) dips below the current one (2.0) but stays above tol
    with restarts disabled, so the average is never adopted — the loop
    must report 2.0, the merit of the iterate it actually returns.
    """
    _, scaled, T, Sigma, rho = _prepped(seed=3)
    m, n = scaled.K.shape
    op = engine.dense_operator(scaled.K, scaled.K.T)
    key, x0, y0 = engine.draw_init(jax.random.PRNGKey(0), m, n,
                                   scaled.lb, scaled.ub, scaled.K.dtype)

    def residual_fn(x, x_prev, y, Kx, KTy):
        is_avg = jnp.all(x == x_prev)
        return jnp.where(is_avg, jnp.asarray(0.5, x.dtype),
                         jnp.asarray(2.0, x.dtype))

    x, y, it, merit = engine.pdhg_loop(
        op, engine.JNP_UPDATES, scaled.b, scaled.c, scaled.lb, scaled.ub,
        T, Sigma, x0, y0, 0.95 / rho, 0.95 / rho, key,
        max_iters=8, tol=0.1, gamma=0.0, check_every=8,
        restart_beta=0.0, residual_fn=residual_fn)
    assert int(it) == 8
    # the returned iterate's merit, NOT the (lower) unadopted average's
    assert float(merit) == 2.0
    assert not float(merit) <= 0.1          # must not claim convergence


def test_pdhg_loop_adopted_average_reports_average_merit(x64):
    """Counterpart: when the averaged iterate IS adopted (its merit
    beats tol), the reported merit must be the average's — the returned
    vector satisfies it."""
    _, scaled, T, Sigma, rho = _prepped(seed=3)
    m, n = scaled.K.shape
    op = engine.dense_operator(scaled.K, scaled.K.T)
    key, x0, y0 = engine.draw_init(jax.random.PRNGKey(0), m, n,
                                   scaled.lb, scaled.ub, scaled.K.dtype)

    def residual_fn(x, x_prev, y, Kx, KTy):
        is_avg = jnp.all(x == x_prev)
        return jnp.where(is_avg, jnp.asarray(0.05, x.dtype),
                         jnp.asarray(2.0, x.dtype))

    x, y, it, merit = engine.pdhg_loop(
        op, engine.JNP_UPDATES, scaled.b, scaled.c, scaled.lb, scaled.ub,
        T, Sigma, x0, y0, 0.95 / rho, 0.95 / rho, key,
        max_iters=64, tol=0.1, gamma=0.0, check_every=8,
        restart_beta=0.0, residual_fn=residual_fn)
    # averaged merit 0.05 <= tol -> average adopted, loop exits truthfully
    assert int(it) == 8
    assert float(merit) == 0.05


def test_solve_jit_kernel_pallas_matches_jnp(x64):
    """Public API: the fused-Pallas executable reproduces the jnp one."""
    lp = random_standard_lp(8, 14, seed=1)
    mk = lambda k: PDHGOptions(  # noqa: E731
        max_iters=256, tol=1e-30, check_every=64, kernel=k)
    r_jnp = solve_jit(lp, mk("jnp"))
    r_pal = solve_jit(lp, mk("pallas"))
    np.testing.assert_allclose(r_pal.x, r_jnp.x, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(r_pal.y, r_jnp.y, rtol=1e-9, atol=1e-12)
    assert r_pal.iterations == r_jnp.iterations
    assert r_pal.mvm_calls == r_jnp.mvm_calls


def test_batch_solver_kernel_parity_and_cache_isolation(x64):
    """BatchSolver(kernel="pallas") matches jnp to fp tolerance and the
    executable cache signatures never collide across kernels."""
    lp = random_standard_lp(8, 14, seed=2)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64)
    s_jnp = BatchSolver(opts)
    s_pal = BatchSolver(opts, kernel="pallas")
    r_jnp = s_jnp.solve_stream([lp])[0]
    r_pal = s_pal.solve_stream([lp])[0]
    np.testing.assert_allclose(r_pal.x, r_jnp.x, rtol=1e-9, atol=1e-12)
    assert r_pal.mvm_calls == r_jnp.mvm_calls > 0
    assert s_pal.opts.kernel == "pallas"
    # kernel choice is part of the signature: no silent cross-kernel hits
    assert set(s_jnp._cache).isdisjoint(set(s_pal._cache))
    assert opts_static(s_jnp.opts)[8] != opts_static(s_pal.opts)[8]


def test_crossbar_pallas_operator_matches_dense_decode(x64):
    """kernel="pallas" routes the crossbar pipeline's MVMs through the
    differential-pair Pallas kernel against the programmed M; with read
    noise off, iterates must match the dense-decode path."""
    from repro.crossbar import EPIRAM, CrossbarBatchSolver

    dev = dc.replace(EPIRAM, name="epiram-quiet", sigma_read=0.0)
    lp = random_standard_lp(10, 18, seed=3)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64,
                       lanczos_iters=8)
    rep_jnp = CrossbarBatchSolver(opts, device=dev).solve_stream([lp])[0]
    rep_pal = CrossbarBatchSolver(opts, device=dev,
                                  kernel="pallas").solve_stream([lp])[0]
    np.testing.assert_allclose(rep_pal.result.x, rep_jnp.result.x,
                               rtol=1e-8, atol=1e-10)
    assert rep_pal.ledger.mvm_count == rep_jnp.ledger.mvm_count
    # noisy device still converges through the kernel operator
    noisy = CrossbarBatchSolver(
        PDHGOptions(max_iters=2000, tol=1e-3, check_every=64,
                    lanczos_iters=16),
        device=EPIRAM, kernel="pallas").solve_stream([lp])[0]
    rel = abs(noisy.result.obj - lp.obj_opt) / abs(lp.obj_opt)
    assert rel < 5e-2, rel


def test_dist_matches_jit_iterates_on_single_device_mesh(x64):
    """The shard_map path runs the same engine loop: on an unpadded
    1-device mesh its iterates coincide with solve_jit (restart off so
    the psum-reduced merit formula cannot steer the trajectory)."""
    from repro.distributed.pdhg_dist import solve_dist
    from repro.launch.mesh import make_mesh

    lp = random_standard_lp(10, 18, seed=0)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64,
                       restart=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    r_dist = solve_dist(lp, mesh, opts)
    r_jit = solve_jit(lp, opts)
    assert r_dist.iterations == r_jit.iterations == 128
    np.testing.assert_allclose(r_dist.x, r_jit.x, rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(r_dist.y, r_jit.y, rtol=1e-9, atol=1e-12)


# -------------------------------------------------------- MVM ledger ---

def test_mvm_accounting_is_the_single_formula_everywhere(x64):
    """jit / batch / dist / crossbar all report engine.mvm_accounting."""
    from repro.crossbar import TAOX_HFOX, CrossbarBatchSolver
    from repro.distributed.pdhg_dist import solve_dist
    from repro.launch.mesh import make_mesh

    lp = random_standard_lp(8, 14, seed=4)
    opts = PDHGOptions(max_iters=512, tol=1e-30, check_every=64)

    r_jit = solve_jit(lp, opts)
    assert r_jit.mvm_calls == engine.mvm_accounting(
        r_jit.iterations, opts.check_every, opts.lanczos_iters)

    r_b = BatchSolver(opts).solve_stream([lp])[0]
    assert r_b.mvm_calls == engine.mvm_accounting(
        r_b.iterations, opts.check_every, opts.lanczos_iters)

    r_d = solve_dist(lp, make_mesh((1, 1), ("data", "model")), opts)
    assert r_d.mvm_calls == engine.mvm_accounting(
        r_d.iterations, opts.check_every, opts.lanczos_iters)

    rep = CrossbarBatchSolver(
        PDHGOptions(max_iters=256, tol=1e-30, check_every=64,
                    lanczos_iters=16),
        device=TAOX_HFOX).solve_stream([lp])[0]
    assert rep.result.mvm_calls == engine.mvm_accounting(
        rep.result.iterations, 64, 16)
    assert rep.ledger.mvm_count == rep.lanczos_mvms + rep.pdhg_mvms


# ---------------------------------------- noisy residual checks (jit) ---

def test_jit_and_host_merits_agree_in_distribution_under_read_noise(x64):
    """Regression: the jitted merit check used noiseless K products while
    the host path (and the 4-MVMs-per-check ledger charge) issues NOISY
    device MVMs.  Both paths now measure the same noise-floor merit: at
    sigma_read=0.05 the final in-loop merits must agree in distribution
    (same decade), and sit clearly above the clean tolerance."""
    lp = random_standard_lp(8, 14, seed=2)
    sigma = 0.05
    host_merits, jit_merits = [], []
    for s in range(4):
        opts = PDHGOptions(max_iters=384, tol=1e-12, check_every=64,
                           seed=s)
        r_h = solve(lp, opts,
                    noise=NoiseModel(kind="multiplicative", sigma=sigma))
        host_merits.append(float(r_h.residuals.max))
        r_j = solve_jit(lp, opts, sigma_read=sigma)
        jit_merits.append(r_j.merit)
    gmean = lambda v: float(np.exp(np.mean(np.log(v))))  # noqa: E731
    gh, gj = gmean(host_merits), gmean(jit_merits)
    assert gj < 10 * gh and gh < 10 * gj, (host_merits, jit_merits)
    # the noise floor is visible to the jitted check (a noiseless check
    # would let merit collapse toward the true residual of the average)
    assert min(jit_merits) > 1e-6, jit_merits


# ------------------------------------------------- interpret defaults ---

def test_padded_kernel_wrappers_autodetect_interpret():
    """Regression: the low-level ``*_padded`` wrappers hardcoded
    interpret=True — a real-TPU caller would silently run interpreted.
    They now default through the shared backend detection."""
    from repro.kernels import crossbar_mvm as xbar
    from repro.kernels import interpret_default, ops
    from repro.kernels import pdhg_update as upd

    for fn in (xbar.crossbar_mvm_padded, upd.primal_update_padded,
               upd.dual_update_padded, ops.crossbar_mvm, ops.primal_update,
               ops.dual_update):
        default = inspect.signature(fn).parameters["interpret"].default
        assert default is None, fn
    assert interpret_default() == (jax.default_backend() == "cpu")
    # and the auto-detected default actually runs on this backend
    col = jnp.ones((upd.BLOCK, 1), jnp.float32)
    out = upd.dual_update_padded(col, 0 * col, col, col,
                                 jnp.ones((1, 1), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2 * np.ones((upd.BLOCK, 1)))


# ------------------------------------------------------- single home ---

def test_step_math_lives_only_in_engine():
    """Acceptance guard: the PDHG half-iteration (extrapolation / theta
    adaptation) appears in core/engine.py and NOWHERE else."""
    root = pathlib.Path(repro.__file__).parent
    for rel in ("core/pdhg.py", "runtime/batch.py",
                "distributed/pdhg_dist.py", "crossbar/solver.py"):
        src = (root / rel).read_text()
        assert "theta_k * (x - x_prev)" not in src, rel
        assert "jnp.sqrt(1.0 + 2.0" not in src, rel
    assert "jnp.sqrt(1.0 + 2.0" in (root / "core/engine.py").read_text()


# ------------------------------------------------------------- launch ---

def test_launch_solve_kernel_flag(x64, capsys):
    """--kernel pallas runs green end-to-end on CPU (interpret mode)."""
    from repro.launch.solve import main

    res = main(["--instance", "rand:6x10", "--backend", "exact",
                "--kernel", "pallas", "--max-iters", "2000",
                "--tol", "1e-4"])
    assert res.status == "optimal"
    out = capsys.readouterr().out
    assert "status=optimal" in out

    results = main(["--backend", "batch", "--kernel", "pallas",
                    "--instances", "rand:6x10,rand:8x12",
                    "--max-iters", "2000", "--tol", "1e-4"])
    assert all(r.converged for r in results)


def _dot_precisions(jaxpr):
    """``precision`` of every dot_general in a jaxpr, sub-jaxprs (loop
    bodies, pallas_call kernels) included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn.params["precision"])
        for val in eqn.params.values():
            for sub in val if isinstance(val, (list, tuple)) else [val]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _dot_precisions(sub)
    return found


HIGHEST = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)


def test_launch_solve_f32_matmuls_at_full_precision(x64, monkeypatch):
    """The CLI's f32 solve multiplies at ``HIGHEST`` precision (a TPU
    would otherwise multiply f32 in one bf16 pass) through the library's
    own MVMs: the process-wide default precision is left alone."""
    from repro.launch import solve as cli

    real_core = engine.solve_core
    seen = []

    def spy_core(*args, **kw):
        seen.append((jax.config.jax_default_matmul_precision,
                     jax.make_jaxpr(real_core, static_argnums=(10,))(
                         *args, **kw)))
        return real_core(*args, **kw)

    monkeypatch.setattr(engine, "solve_core", spy_core)
    res = cli.main(["--instance", "rand:6x10", "--backend", "exact",
                    "--dtype", "float32", "--max-iters", "2000",
                    "--tol", "1e-4"])
    assert res.status == "optimal" and res.x.dtype == np.float32
    [(default, jaxpr)] = seen
    assert default is None
    precs = _dot_precisions(jaxpr.jaxpr)
    assert len(precs) >= 2 and all(p == HIGHEST for p in precs)


@pytest.mark.parametrize("path", ["accel", "lanczos", "sharded",
                                  "crossbar_kernel", "megakernel"])
def test_solver_mvms_multiply_at_highest_precision(path):
    """Every operator backend's MVM, Pallas kernels included, carries
    ``HIGHEST`` precision itself, whatever the caller's default."""
    from repro.core.lanczos import lanczos_svd_jit
    from repro.kernels import pdhg_megakernel as mega

    m, n = 8, 16
    K = jnp.ones((m, n), jnp.float32)
    vm, vn = jnp.ones((m,), jnp.float32), jnp.ones((n,), jnp.float32)
    if path == "accel":
        fn, args = encode_exact(K).mvm_full, (jnp.ones((m + n,)),)
    elif path == "lanczos":
        fn, args = lanczos_svd_jit, (jnp.ones((m + n, m + n)),)
    elif path == "sharded":
        op = engine.sharded_operator(K, "rows", "cols")
        fn = jax.vmap(lambda x, y: (op.fwd(x), op.adj(y)),
                      axis_name="cols", spmd_axis_name=None)
        fn = jax.vmap(fn, axis_name="rows")
        args = (vn[None, None], vm[None, None])
    elif path == "crossbar_kernel":
        g = jnp.ones((m + n, m + n), jnp.float32)
        op = engine.crossbar_operator(g, g, 1.0, m, n, interpret=True)
        fn, args = (lambda x: op.fwd(x)), (vn,)
    else:
        def fn(x, y):
            return mega.fused_dense_steps(
                K, K.T, vm, vn, vn, vn, vn, vm, x, x, x, y, 1.0, 1.0,
                n_steps=2, gamma=0.0, interpret=True)
        args = (vn, vm)
    precs = _dot_precisions(jax.make_jaxpr(fn)(*args).jaxpr)
    assert precs and all(p == HIGHEST for p in precs), precs


def test_megakernel_vmem_budget_binds_compiled_only():
    """Interpreted kernels have no VMEM: a bucket past the budget runs
    interpreted and is refused, with a message, only when compiled."""
    from repro.kernels import pdhg_megakernel as mega

    m, n = 1024, 1024
    assert mega.vmem_estimate(m, n, np.float32) > mega.VMEM_BUDGET
    K = jax.ShapeDtypeStruct((m, n), jnp.float32)
    vm = jax.ShapeDtypeStruct((m,), jnp.float32)
    vn = jax.ShapeDtypeStruct((n,), jnp.float32)
    sc = jax.ShapeDtypeStruct((), jnp.float32)

    def fused(interpret):
        return jax.eval_shape(
            lambda *a: mega.fused_dense_steps(
                *a, n_steps=2, gamma=0.0, interpret=interpret),
            K, K, vm, vn, vn, vn, vn, vm, vn, vn, vn, vm, sc, sc)

    assert fused(True)[0].shape == (n,)
    with pytest.raises(ValueError, match="VMEM"):
        fused(False)


# ------------------------------------- restart flag + megakernel mode ---

def test_restart_false_matches_legacy_nan_trick_bitwise(x64):
    """``restart=False`` rides as an explicit static boolean.  The old
    encoding (restart_beta=0.0) only worked because ``0.0 * inf == NaN``
    and NaN comparisons are false inside the jitted body; the explicit
    flag must reproduce it bitwise — same iterates, same merit — and the
    average is provably never adopted (a restart=True run on the same
    seed differs)."""
    _, scaled, T, Sigma, rho = _prepped(seed=3)
    b, c, lb, ub = scaled.b, scaled.c, scaled.lb, scaled.ub
    key = jax.random.PRNGKey(7)
    core = jax.jit(engine.solve_core, static_argnums=(10,))
    args = (scaled.K, scaled.K.T, b, c, lb, ub, T, Sigma, rho, key)

    legacy = (512, 1e-30, 0.95, 1.0, 0.0, 64, 0.0, 0.0, "jnp")
    flag = (512, 1e-30, 0.95, 1.0, 0.0, 64, 0.5, 0.0, "jnp",
            False, "ell", False)
    on = (512, 1e-30, 0.95, 1.0, 0.0, 64, 0.5, 0.0, "jnp",
          True, "ell", False)

    x_leg, y_leg, it_leg, m_leg = core(*args, legacy)
    x_off, y_off, it_off, m_off = core(*args, flag)
    x_on, y_on, _, _ = core(*args, on)

    assert int(it_leg) == int(it_off)
    np.testing.assert_array_equal(np.asarray(x_leg), np.asarray(x_off))
    np.testing.assert_array_equal(np.asarray(y_leg), np.asarray(y_off))
    np.testing.assert_array_equal(np.asarray(m_leg), np.asarray(m_off))
    # the flag is live: restarts DO change the trajectory on this seed
    assert not np.array_equal(np.asarray(x_on), np.asarray(x_off))


def test_mvm_accounting_restart_flag_and_batch_ledger(x64):
    """restart=False residual checks cost 2 MVMs (no averaged-iterate
    pair); every reporting surface charges the flag it actually ran."""
    assert engine.mvm_accounting(128, 64, 16) \
        == engine.mvm_accounting(128, 64, 16, restart=True)
    assert engine.mvm_accounting(128, 64, 16, restart=True) \
        - engine.mvm_accounting(128, 64, 16, restart=False) == 2 * 2

    lp = random_standard_lp(8, 14, seed=4)
    opts = PDHGOptions(max_iters=256, tol=1e-30, check_every=64,
                       restart=False)
    r = solve_jit(lp, opts)
    assert r.mvm_calls == engine.mvm_accounting(
        r.iterations, opts.check_every, opts.lanczos_iters, restart=False)
    rb = BatchSolver(opts).solve_stream([lp])[0]
    assert rb.mvm_calls == engine.mvm_accounting(
        rb.iterations, opts.check_every, opts.lanczos_iters, restart=False)


def test_dense_megakernel_matches_per_step_loop(x64):
    """megakernel=True fuses each check_every window into ONE launch
    (restart/residual check hoisted out) — iterates must match the
    per-step loop to fp tolerance at sigma_read=0, with the identical
    iteration count."""
    lp = random_standard_lp(10, 18, seed=6)
    opts = PDHGOptions(max_iters=2000, tol=1e-6, check_every=64)
    mega = dc.replace(opts, megakernel=True)
    r_ref = solve_jit(lp, opts)
    r_meg = solve_jit(lp, mega)
    assert r_meg.iterations == r_ref.iterations
    assert r_meg.status == r_ref.status
    np.testing.assert_allclose(r_meg.x, r_ref.x, atol=1e-9, rtol=1e-9)
    np.testing.assert_allclose(r_meg.y, r_ref.y, atol=1e-9, rtol=1e-9)


def test_megakernel_rejects_read_noise():
    """Per-MVM noise keys cannot be split inside a fused launch; the
    static-tuple builder refuses the combination up front."""
    with pytest.raises(ValueError, match="noiseless-only"):
        opts_static(PDHGOptions(megakernel=True), 0.05)


def test_megakernel_batch_cache_key_disjoint(x64):
    """The megakernel flag is part of the executable cache key: serving
    the same bucket with and without it must compile twice, never
    cross-serve."""
    lp = random_standard_lp(8, 14, seed=1)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64)
    solver = BatchSolver(opts)
    solver.solve_stream([lp])
    solver_m = BatchSolver(dc.replace(opts, megakernel=True))
    solver_m.solve_stream([lp])
    assert set(solver._cache).isdisjoint(set(solver_m._cache))
    assert opts_static(solver.opts) != opts_static(solver_m.opts)

"""Sparse LP serving: SparseCOO model, sparse operator backend, and the
COO bucket pipeline (ISSUE 4 tentpole).

The acceptance contract: a >=95%-sparse stream must flow through the
batch scheduler with NO dense (B, m_pad, n_pad) materialization, match
the dense path's iterates at sigma_read=0, and stack in
nonzero-proportional host memory (>=4x smaller than the dense stack).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PDHGOptions, engine
from repro.lp import SparseCOO, random_standard_lp, sparse_lp_stream, \
    sparse_random_standard_lp
from repro.runtime import BatchSolver
from repro.runtime import batch as batch_mod
from repro.runtime.batch import (
    nnz_bucket,
    pad_problem,
    stack_problems_sparse,
)

OPTS = PDHGOptions(max_iters=20000, tol=1e-5, check_every=64)


@pytest.fixture(params=["dense", "gather"])
def ell_operator(request, monkeypatch):
    """The operator every ELL bucket multiplies by, forced through the
    rule's constant: a dense K (no ratio of shapes stops it), or the
    gather (no dtype goes dense)."""
    from repro.kernels import sparse_mvm

    ratio = {4: np.inf, 8: np.inf} if request.param == "dense" else {}
    monkeypatch.setattr(sparse_mvm, "DENSE_ELEMENTS_PER_SLOT", ratio)
    return request.param


def _dense_buckets(solver):
    return solver.last_stream_stats["dense_operator_buckets"]


# ------------------------------------------------------------ SparseCOO ---

def test_sparse_coo_matvec_and_transpose_match_dense(rng):
    K = rng.normal(size=(7, 11)) * (rng.random((7, 11)) < 0.3)
    sp = SparseCOO.from_dense(K)
    assert sp.nnz == np.count_nonzero(K)
    x, y = rng.normal(size=11), rng.normal(size=7)
    np.testing.assert_allclose(sp @ x, K @ x)
    np.testing.assert_allclose(sp.T @ y, K.T @ y)
    np.testing.assert_allclose(sp.toarray(), K)
    np.testing.assert_allclose(sp.T.toarray(), K.T)


def test_sparse_coo_duplicate_indices_sum(rng):
    sp = SparseCOO([1.0, 2.0, 5.0], [0, 0, 1], [1, 1, 0], (2, 3))
    dense = sp.toarray()
    assert dense[0, 1] == 3.0 and dense[1, 0] == 5.0
    np.testing.assert_allclose(sp @ np.ones(3), dense @ np.ones(3))


def test_standard_lp_sparse_roundtrip():
    lp = sparse_random_standard_lp(12, 24, density=0.2, seed=0)
    assert lp.is_sparse
    dense = lp.densified()
    assert not dense.is_sparse
    np.testing.assert_allclose(dense.K, lp.K.toarray())
    back = dense.sparsified()
    assert back.is_sparse
    np.testing.assert_allclose(back.K.toarray(), dense.K)
    # known optimum is feasible under the COO matvec
    assert np.linalg.norm(lp.K @ lp.x_opt - lp.b) < 1e-10


def test_sparse_generator_density_and_coverage():
    lp = sparse_random_standard_lp(64, 128, density=0.05, seed=3)
    assert 0.02 < lp.K.density < 0.10
    # coverage guarantee: no zero rows or columns
    assert np.all(np.bincount(lp.K.row, minlength=64) > 0)
    assert np.all(np.bincount(lp.K.col, minlength=128) > 0)


# ----------------------------------------------------- padding / stacking ---

def test_pad_problem_sparse_never_densifies():
    lp = sparse_random_standard_lp(10, 20, density=0.2, seed=1)
    padded = pad_problem(lp, 16, 32)
    assert isinstance(padded.K, SparseCOO)
    assert padded.K.shape == (16, 32)
    assert padded.K.nnz == lp.K.nnz          # same data, bigger shape
    # padding preserves the optimum semantics: pinned extra vars
    assert np.all(padded.lb[20:] == 0) and np.all(padded.ub[20:] == 0)


def test_stack_problems_sparse_layout():
    lps = [sparse_random_standard_lp(8, 16, density=0.3, seed=s)
           for s in range(3)]
    nnz = nnz_bucket(max(lp.K.nnz for lp in lps))
    data, idx, b, c, lb, ub = stack_problems_sparse(lps, m=16, n=32,
                                                    nnz=nnz)
    assert data.shape == (3, nnz) and idx.shape == (3, nnz, 2)
    assert b.shape == (3, 16) and c.shape == (3, 32)
    assert idx.dtype == np.int32
    # nnz padding is explicit zeros at (0, 0): contraction-neutral
    k = lps[0].K.nnz
    assert np.all(data[0, k:] == 0) and np.all(idx[0, k:] == 0)
    # stacked operator reproduces each instance
    K0 = np.zeros((16, 32))
    np.add.at(K0, (idx[0, :, 0], idx[0, :, 1]), data[0])
    np.testing.assert_allclose(K0[:8, :16], lps[0].K.toarray())


# ------------------------------------------------- engine sparse operator ---

def test_sparse_operator_iterate_parity_with_dense(x64):
    """sparse_operator must reproduce dense_operator's PDHG trajectory
    at sigma_read=0 (the ISSUE-4 parity requirement)."""
    from jax.experimental import sparse as jsparse

    lp = sparse_random_standard_lp(12, 24, density=0.25, seed=2)
    K = jnp.asarray(lp.K.toarray())
    K_sp = jsparse.BCOO(
        (jnp.asarray(lp.K.data), jnp.asarray(
            np.stack([lp.K.row, lp.K.col], axis=1))), shape=lp.K.shape)
    b, c = jnp.asarray(lp.b), jnp.asarray(lp.c)
    lb, ub = jnp.asarray(lp.lb), jnp.asarray(lp.ub)
    T = jnp.ones(24); Sigma = jnp.ones(12)
    key, x0, y0 = engine.draw_init(jax.random.PRNGKey(0), 12, 24, lb, ub,
                                   K.dtype)
    tau = sigma = 0.9 / float(jnp.linalg.norm(K, 2))

    states = {}
    for name, op in (("dense", engine.dense_operator(K, K.T)),
                     ("sparse", engine.sparse_operator(K_sp))):
        state = engine.init_state(x0, y0, tau, sigma, gamma=0.0)
        for _ in range(50):
            state = engine.pdhg_step(op, engine.JNP_UPDATES, b, c, lb, ub,
                                     T, Sigma, 0.0, state)
        states[name] = state
    np.testing.assert_allclose(states["sparse"].x, states["dense"].x,
                               atol=1e-12, rtol=1e-10)
    np.testing.assert_allclose(states["sparse"].y, states["dense"].y,
                               atol=1e-12, rtol=1e-10)


def test_solve_core_auto_mounts_sparse_operator(x64):
    """solve_core on a BCOO K must run without a dense K anywhere and
    agree with the dense solve_core bit-for-bit at sigma_read=0 apart
    from MVM summation order (allclose)."""
    from jax.experimental import sparse as jsparse
    from repro.core.pdhg import opts_static

    lp = sparse_random_standard_lp(10, 20, density=0.3, seed=4)
    Kd = jnp.asarray(lp.K.toarray())
    K_sp = jsparse.BCOO(
        (jnp.asarray(lp.K.data), jnp.asarray(
            np.stack([lp.K.row, lp.K.col], axis=1))), shape=lp.K.shape)
    b, c = jnp.asarray(lp.b), jnp.asarray(lp.c)
    lb, ub = jnp.asarray(lp.lb), jnp.asarray(lp.ub)
    T, Sigma = jnp.ones(20), jnp.ones(10)
    rho = float(jnp.linalg.norm(Kd, 2))
    static = opts_static(PDHGOptions(max_iters=512, tol=1e-9,
                                     check_every=64))
    key = jax.random.PRNGKey(1)
    xd, yd, itd, md = engine.solve_core(Kd, Kd.T, b, c, lb, ub, T, Sigma,
                                        rho, key, static)
    xs, ys, its, ms = engine.solve_core(K_sp, None, b, c, lb, ub, T,
                                        Sigma, rho, key, static)
    assert int(its) == int(itd)
    np.testing.assert_allclose(np.asarray(xs), np.asarray(xd), atol=1e-8)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(yd), atol=1e-8)


# ------------------------------------------------------- stream serving ---

def test_sparse_stream_solves_without_dense_materialization(x64,
                                                            monkeypatch):
    """The acceptance assertion: a sparse stream through BatchSolver may
    NEVER materialize a dense (B, m_pad, n_pad) stack — dense stacking
    is poisoned for the duration and host bytes are audited."""
    def _poisoned(*a, **k):
        raise AssertionError(
            "dense stack_problems called for a sparse stream")

    monkeypatch.setattr(batch_mod, "stack_problems", _poisoned)
    lps = sparse_lp_stream(4, density=0.05, seed=0)
    solver = BatchSolver(PDHGOptions(max_iters=20000, tol=1e-4,
                                     check_every=64))
    results = solver.solve_stream(lps)
    stats = solver.last_stream_stats
    assert stats["dense_stack_bytes"] == 0
    assert stats["sparse_stack_bytes"] > 0
    for lp, r in zip(lps, results):
        assert r.sparse
        rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel < 1e-3, (lp.name, rel)
        assert r.x.shape == (lp.K.shape[1],)


def test_sparse_stream_host_memory_at_least_4x_smaller(x64):
    """>=95%-sparse 16-instance stream: the sparse stack must be >=4x
    smaller on host than the dense stack of the same stream (the
    acceptance criterion's memory leg).

    Pinned to ``sparse_kernel="bcoo"`` — the COO stacking is the
    memory-optimal backend (nnz-proportional); the default ELL backend
    trades bounded width padding for scatter-free wall clock and only
    guarantees ~2x here."""
    lps = sparse_lp_stream(16, density=0.05, seed=0)
    assert all(lp.K.density <= 0.05 + 1e-9 for lp in lps)
    opts = PDHGOptions(max_iters=64, tol=1e-30, check_every=64,
                       lanczos_iters=8, sparse_kernel="bcoo")
    sp = BatchSolver(opts)
    sp.solve_stream(lps)
    dn = BatchSolver(opts)
    dn.solve_stream([lp.densified() for lp in lps])
    mem_sparse = sp.last_stream_stats["sparse_stack_bytes"]
    mem_dense = dn.last_stream_stats["dense_stack_bytes"]
    assert mem_sparse > 0 and mem_dense > 0
    assert mem_dense >= 4 * mem_sparse, (mem_dense, mem_sparse)


def test_sparse_stream_matches_dense_stream(x64, ell_operator):
    """Sparse pipeline vs densified dense pipeline on the same stream:
    same iteration counts and matching objectives (sigma_read=0), with
    the ELL buckets on either operator."""
    lps = sparse_lp_stream(3, density=0.05, seed=0)
    opts = PDHGOptions(max_iters=4000, tol=1e-5, check_every=64)
    sparse = BatchSolver(opts)
    rs = sparse.solve_stream(lps)
    n_buckets = sparse.last_stream_stats["n_buckets"]
    assert _dense_buckets(sparse) == (n_buckets if ell_operator == "dense"
                                      else 0)
    rd = BatchSolver(opts).solve_stream([lp.densified() for lp in lps])
    for a, d in zip(rs, rd):
        assert a.iterations == d.iterations, (a.name, a.iterations,
                                              d.iterations)
        assert abs(a.obj - d.obj) / max(abs(d.obj), 1e-12) < 1e-9
        np.testing.assert_allclose(a.x, d.x, atol=1e-6)


def test_sparse_and_dense_buckets_are_cache_disjoint(x64):
    """A sparse and a dense instance of the SAME shape must compile
    separate executables (different pipelines) and both solve."""
    sp_lp = sparse_random_standard_lp(8, 14, density=0.3, seed=0)
    dn_lp = random_standard_lp(8, 14, seed=0)
    solver = BatchSolver(PDHGOptions(max_iters=2000, tol=1e-4,
                                     check_every=64, lanczos_iters=16))
    results = solver.solve_stream([sp_lp, dn_lp])
    assert solver.cache_misses == 2          # one sparse, one dense exe
    assert results[0].sparse and not results[1].sparse
    for lp, r in zip((sp_lp, dn_lp), results):
        rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel < 1e-2, (lp.name, rel)


def test_crossbar_batch_solver_densifies_sparse(x64):
    """The crossbar tier programs every physical cell: sparse instances
    must densify on entry and still serve correctly."""
    from repro.crossbar import EPIRAM, CrossbarBatchSolver

    lp = sparse_random_standard_lp(8, 14, density=0.3, seed=1)
    opts = PDHGOptions(max_iters=2000, tol=1e-3, check_every=64,
                       lanczos_iters=16)
    rep = CrossbarBatchSolver(opts, device=EPIRAM).solve_stream([lp])[0]
    rel = abs(rep.result.obj - lp.obj_opt) / abs(lp.obj_opt)
    assert rel < 5e-2, rel


def test_sparse_stream_buckets_on_nnz_too(x64):
    """An nnz outlier must not inflate its shape bucket: same-shape
    instances with far-apart nonzero counts compile separate (smaller)
    executables instead of padding everyone to the outlier."""
    thin = sparse_random_standard_lp(64, 128, density=0.04, seed=0)
    fat = sparse_random_standard_lp(64, 128, density=0.5, seed=1)
    assert nnz_bucket(thin.K.nnz) != nnz_bucket(fat.K.nnz)
    solver = BatchSolver(PDHGOptions(max_iters=64, tol=1e-30,
                                     check_every=64, lanczos_iters=8))
    solver.solve_stream([thin, fat])
    assert solver.last_stream_stats["n_buckets"] == 2
    assert solver.cache_misses == 2
    # the thin instance's stack is nnz-proportional, not outlier-sized
    expected_thin = nnz_bucket(thin.K.nnz)
    expected_fat = nnz_bucket(fat.K.nnz)
    assert expected_thin * 4 < expected_fat


def test_sparse_duplicate_indices_match_densified(x64):
    """Duplicate COO entries sum (the BCOO convention): a duplicate-
    bearing instance must solve identically to its densified copy —
    the stacking coalesces before the scatter preconditioners."""
    base = sparse_random_standard_lp(8, 14, density=0.4, seed=5)
    K = base.K
    # split the first entry into two stored halves at the same (r, c)
    dup = SparseCOO(
        np.concatenate([[K.data[0] / 2, K.data[0] / 2], K.data[1:]]),
        np.concatenate([[K.row[0]], K.row]),
        np.concatenate([[K.col[0]], K.col]), K.shape)
    np.testing.assert_allclose(dup.toarray(), K.toarray())
    lp_dup = dataclasses.replace(base, K=dup)
    opts = PDHGOptions(max_iters=2000, tol=1e-5, check_every=64,
                       lanczos_iters=16)
    r_dup = BatchSolver(opts).solve_stream([lp_dup])[0]
    r_dense = BatchSolver(opts).solve_stream([base.densified()])[0]
    assert r_dup.iterations == r_dense.iterations
    np.testing.assert_allclose(r_dup.x, r_dense.x, atol=1e-8)


def test_nnz_bucket_rounds_to_pow2():
    assert nnz_bucket(1) == 16
    assert nnz_bucket(16) == 16
    assert nnz_bucket(17) == 32
    assert nnz_bucket(900) == 1024


# --------------------------------------- ELL backend (ISSUE 6 tentpole) ---

def _zero_k_lp(m=6, n=10):
    """Feasible degenerate LP with an all-zero K (nnz=0): K@x = 0 = b,
    optimum is the lower bound wherever c > 0."""
    sp = SparseCOO(np.zeros(0), np.zeros(0, np.int64),
                   np.zeros(0, np.int64), (m, n))
    c = np.linspace(0.5, 1.5, n)
    return batch_mod.StandardLP(c=c, K=sp, b=np.zeros(m),
                                lb=np.zeros(n), ub=np.ones(n),
                                name="zeroK", x_opt=np.zeros(n),
                                obj_opt=0.0)


def test_ell_from_coo_matches_dense(x64, rng):
    from repro.kernels.sparse_mvm import ell_from_coo, ell_matvec

    K = rng.normal(size=(9, 13)) * (rng.random((9, 13)) < 0.3)
    sp = SparseCOO.from_dense(K)
    data, cols = ell_from_coo(sp.data, sp.row, sp.col, sp.shape)
    assert data.shape == cols.shape and data.shape[0] == 9
    # width == the densest row; padded slots carry (0.0, col 0): inert
    widths = (K != 0).sum(axis=1)
    assert data.shape[1] == widths.max()
    v = rng.normal(size=13)
    np.testing.assert_allclose(np.asarray(ell_matvec(
        jnp.asarray(data), jnp.asarray(cols), jnp.asarray(v))), K @ v,
        rtol=1e-12, atol=1e-12)
    # explicit padding beyond the max width must not change the product
    data_w, cols_w = ell_from_coo(sp.data, sp.row, sp.col, sp.shape,
                                  width=widths.max() + 3)
    np.testing.assert_allclose(np.asarray(ell_matvec(
        jnp.asarray(data_w), jnp.asarray(cols_w), jnp.asarray(v))), K @ v,
        rtol=1e-12, atol=1e-12)


def test_ell_from_coo_drops_explicit_zeros_and_pads_empty_rows(x64):
    from repro.kernels.sparse_mvm import coo_row_widths, ell_from_coo, \
        ell_matvec

    # row 1 entirely empty; row 0 holds an explicit zero (must be dropped)
    data = np.array([0.0, 2.0, 3.0])
    row = np.array([0, 0, 2])
    col = np.array([1, 3, 0])
    d, c = ell_from_coo(data, row, col, (3, 4))
    assert d.shape == (3, 1)                   # densest TRUE row has 1 nnz
    assert np.all(d[1] == 0.0)                 # empty row fully padded
    wf, wa = coo_row_widths(row, col, data, (3, 4))
    assert wf == 1 and wa == 1                 # explicit zero not counted
    v = np.array([1.0, 10.0, 100.0, 1000.0])
    np.testing.assert_allclose(
        np.asarray(ell_matvec(jnp.asarray(d), jnp.asarray(c),
                              jnp.asarray(v))),
        np.array([2000.0, 0.0, 3.0]))


def test_ell_matvec_is_one_xla_gather(x64, rng):
    """ELL has one path on every backend: an XLA gather + axis-1 sum,
    with no Pallas kernel behind it, exact on row counts that are not a
    multiple of any tile."""
    from repro.kernels.sparse_mvm import ell_from_coo, ell_matvec

    K = rng.normal(size=(150, 40)) * (rng.random((150, 40)) < 0.1)
    sp = SparseCOO.from_dense(K)
    data, cols = ell_from_coo(sp.data, sp.row, sp.col, sp.shape)
    args = (jnp.asarray(data), jnp.asarray(cols),
            jnp.asarray(rng.normal(size=40)))
    jaxpr = str(jax.make_jaxpr(ell_matvec)(*args))
    assert "gather[" in jaxpr and "pallas_call" not in jaxpr
    np.testing.assert_allclose(np.asarray(ell_matvec(*args)),
                               K @ np.asarray(args[2]), rtol=1e-10,
                               atol=1e-10)


def test_ell_width_bucket_pow2_floor():
    from repro.kernels.sparse_mvm import MIN_ELL_WIDTH, ell_width_bucket

    assert ell_width_bucket(0) == MIN_ELL_WIDTH
    assert ell_width_bucket(3) == 4
    assert ell_width_bucket(4) == 4
    assert ell_width_bucket(5) == 8
    assert ell_width_bucket(100) == 128


def test_stack_problems_ell_layout(x64):
    from repro.runtime.batch import stack_problems_ell

    lps = sparse_lp_stream(3, [(12, 24)], density=0.2, seed=1)
    data_f, cols_f, data_a, cols_a, b, c, lb, ub = stack_problems_ell(lps)
    B = 3
    assert data_f.shape[:2] == (B, 12) and data_a.shape[:2] == (B, 24)
    assert cols_f.dtype == np.int32 and cols_a.dtype == np.int32
    for k, lp in enumerate(lps):
        K = lp.K.toarray()
        v = np.linspace(-1, 1, 24)
        got = (data_f[k] * v[cols_f[k]]).sum(axis=1)
        np.testing.assert_allclose(got, K @ v, rtol=1e-12, atol=1e-12)
        w = np.linspace(-1, 1, 12)
        got_a = (data_a[k] * w[cols_a[k]]).sum(axis=1)
        np.testing.assert_allclose(got_a, K.T @ w, rtol=1e-12, atol=1e-12)


def test_ell_and_bcoo_stream_parity(x64, ell_operator):
    """The acceptance contract of the kernel swap: at sigma_read=0 the
    ELL pipeline, on either operator, and the BCOO pipeline serve the
    SAME stream to the same iterates (fp tolerance) with identical
    iteration counts."""
    lps = sparse_lp_stream(6, density=0.08, seed=3)
    ell = BatchSolver(OPTS)                                # default = ELL
    r_ell = ell.solve_stream(lps)
    assert _dense_buckets(ell) == (ell.last_stream_stats["n_buckets"]
                                   if ell_operator == "dense" else 0)
    r_bcoo = BatchSolver(dataclasses.replace(
        OPTS, sparse_kernel="bcoo")).solve_stream(lps)
    for re_, rb in zip(r_ell, r_bcoo):
        assert re_.iterations == rb.iterations
        assert re_.status == rb.status
        np.testing.assert_allclose(re_.x, rb.x, rtol=1e-7, atol=1e-9)
        np.testing.assert_allclose(re_.y, rb.y, rtol=1e-7, atol=1e-9)


def test_ell_megakernel_refused(x64):
    """The fused window exists for the dense operator only: asking for
    it on a sparse stream is refused by name, never served unfused."""
    lps = sparse_lp_stream(2, density=0.08, seed=5)
    for kernel in ("ell", "bcoo"):
        opts = dataclasses.replace(OPTS, megakernel=True,
                                   sparse_kernel=kernel)
        with pytest.raises(ValueError, match="dense operator only"):
            BatchSolver(opts).solve_stream(lps)


def test_ell_bucket_signature_carries_both_widths(x64):
    """ELL buckets key on (forward, adjoint) width buckets — streams
    mixing densities compile separate executables and never cross-serve;
    the BCOO backend keeps its bare-nnz signature."""
    lo = sparse_random_standard_lp(24, 40, density=0.04, seed=0)
    hi = sparse_random_standard_lp(24, 40, density=0.5, seed=1)
    solver = BatchSolver(OPTS)
    sig_lo = solver._sparse_signature(lo)
    sig_hi = solver._sparse_signature(hi)
    assert sig_lo[0] == "ell" and sig_hi[0] == "ell"
    assert sig_lo != sig_hi
    bcoo = BatchSolver(dataclasses.replace(OPTS, sparse_kernel="bcoo"))
    assert isinstance(bcoo._sparse_signature(lo), int)


def test_degenerate_zero_nnz_instances_serve_cleanly(x64, ell_operator):
    """An all-zero K (nnz=0) must flow through BOTH sparse backends, and
    the ELL one on either operator — width/nnz bucketing, stacking,
    preconditioning, solve — without NaNs (rho=0 is guarded) and land on
    the box optimum."""
    from repro.kernels.sparse_mvm import ell_from_coo
    from repro.runtime.batch import stack_problems_ell

    zk = _zero_k_lp()
    # conversion/stacking layer holds up at zero width
    d, c = ell_from_coo(zk.K.data, zk.K.row, zk.K.col, zk.K.shape)
    assert d.shape == (6, 0)
    stacked = stack_problems_ell([zk])
    assert stacked[0].shape == (1, 6, 0)
    assert nnz_bucket(0) > 0

    opts = dataclasses.replace(OPTS, max_iters=2000)
    for kernel in ("ell", "bcoo"):
        solver = BatchSolver(dataclasses.replace(opts, sparse_kernel=kernel))
        r = solver.solve_stream([zk])[0]
        assert _dense_buckets(solver) == int(kernel == "ell"
                                             and ell_operator == "dense")
        assert np.all(np.isfinite(r.x)) and np.all(np.isfinite(r.y))
        assert r.status in ("optimal", "iteration_limit")
        np.testing.assert_allclose(r.x, np.zeros(10), atol=1e-4)

    # a zero-K instance mixed into a healthy stream serves in one pass
    healthy = sparse_lp_stream(3, [(6, 10)], density=0.3, seed=9)
    results = BatchSolver(opts).solve_stream([zk] + healthy)
    assert all(np.all(np.isfinite(r.x)) for r in results)


# ------------------------------------- the ELL bucket's operator choice ---

# (lanes, m_pad, n_pad, wf, wa) of every ELL bucket the benchmark's
# Table-1 rounds build (table1-coo traffic)
TABLE1_ELL_BUCKETS = [(2, 32, 64, 32, 32), (1, 32, 64, 64, 32),
                      (1, 32, 128, 64, 32), (1, 64, 128, 32, 64),
                      (1, 256, 512, 32, 32), (1, 512, 1024, 64, 64)]


def _ell_bucket(spec, lanes):
    """(lanes, m_pad, n_pad, wf, wa) of the bucket a ``sprand:MxN:d``
    instance lands in."""
    m, n, density = spec
    lp = sparse_random_standard_lp(m, n, density=density, seed=0)
    solver = BatchSolver(OPTS)
    (mb, nb), (_, wf, wa) = solver._bucket(m, n), solver._sparse_signature(lp)
    return lanes, mb, nb, wf, wa


@pytest.mark.parametrize("bucket", TABLE1_ELL_BUCKETS)
def test_table1_ell_buckets_go_dense_in_float32(bucket):
    from repro.kernels.sparse_mvm import ell_goes_dense

    assert ell_goes_dense(*bucket, np.dtype(np.float32).itemsize)


@pytest.mark.parametrize("itemsize", [4, 8])
def test_large_ell_buckets_keep_the_gather(itemsize):
    """The paper's sparse class, 65536x131072 at 1.5e-4 (34 GB dense in
    float32), stays on the gather, and so does a bucket that is dense
    enough but past the byte cap."""
    from repro.kernels.sparse_mvm import (DENSE_OPERATOR_MAX_BYTES,
                                          ell_goes_dense)

    a5 = _ell_bucket((65536, 131072, 1.5e-4), 8)
    assert a5[3:] == (64, 32)
    assert not ell_goes_dense(*a5, itemsize)
    # half as many slots as dense elements: dense on shape, and one
    # lane fits, but enough lanes overflow the cap
    m, n, w = 1024, 2048, 256
    one = 1 * m * n * itemsize
    assert one <= DENSE_OPERATOR_MAX_BYTES
    assert ell_goes_dense(1, m, n, w, w, itemsize)
    lanes = DENSE_OPERATOR_MAX_BYTES // one + 1
    assert not ell_goes_dense(lanes, m, n, w, w, itemsize)


def test_chip_smoke_sparse_bucket_keeps_the_gather():
    """``chip_smoke.py``'s sparse phase, 8 x 4096x8192 at 2.4e-3 in the
    library's default float64: 64 dense elements a slot, past what an
    emulated float64 product is worth, so it gathers (PERF.md)."""
    from repro.kernels.sparse_mvm import ell_goes_dense

    bucket = _ell_bucket((4096, 8192, 2.4e-3), 8)
    assert bucket == (8, 4096, 8192, 64, 32)
    assert not ell_goes_dense(*bucket, np.dtype(np.float64).itemsize)


def _while_body_ops(hlo: str):
    """Instruction lines of every computation a ``while`` body of the
    HLO module reaches (fusions, nested loops and calls included)."""
    import re

    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    callee = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
    todo = [m.group(1) for lines in comps.values() for line in lines
            if " while(" in line
            for m in re.finditer(r"body=%([\w.\-]+)", line)]
    seen = set()
    while todo:
        name = todo.pop()
        if name not in seen:
            seen.add(name)
            todo += [m.group(1) for line in comps[name]
                     for m in callee.finditer(line)]
    return [line for name in seen for line in comps[name]]


def test_small_ell_bucket_program_has_no_gather_in_its_loops(ell_operator):
    """A small float32 ELL bucket compiled for the CPU: on the dense
    operator no ``while`` body gathers (the ELL prep's gathers run once,
    before the loops), on the gather they do; either way every f32
    product asks for HIGHEST."""
    import re

    lp = sparse_random_standard_lp(20, 34, density=0.2, seed=0)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    lp = dataclasses.replace(
        lp, K=SparseCOO(f32(lp.K.data), lp.K.row, lp.K.col, lp.K.shape),
        b=f32(lp.b), c=f32(lp.c), lb=f32(lp.lb), ub=f32(lp.ub))
    solver = BatchSolver(PDHGOptions(max_iters=128, tol=1e-30,
                                     check_every=64, lanczos_iters=8,
                                     dtype=np.float32))
    solver.solve_stream([lp])
    hlo, = solver.hlo_texts()
    body = _while_body_ops(hlo)
    assert body
    gathers = [line for line in body if re.search(r"\bgather\(", line)]
    assert bool(gathers) == (ell_operator == "gather")
    dots = [line for line in hlo.splitlines()
            if re.search(r"= f32\[[^]]*\]\S* (dot|convolution)\(", line)]
    assert dots and all("operand_precision={highest,highest}" in line
               for line in dots)

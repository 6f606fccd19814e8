"""Shape-bucketed batch solving of heterogeneous LP streams.

The paper frames RRAM crossbars as *shared* linear-optimization
accelerators: many independent LP instances arrive with arbitrary shapes
and must be served together.  Same-shape stacking (the old
``distributed/batch_solve.py`` contract) breaks down there — every new
``(m, n)`` would recompile.  This scheduler:

  1. rounds every instance up to a ``(m_pad, n_pad)`` bucket (padding is
     exact: extra primal coordinates are pinned at lb=ub=0, extra rows
     are all-zero with b=0, so the optimum is unchanged).  Buckets are
     powers of two by default, or — in device-tile mode — multiples of
     the physical crossbar tile dimensions (e.g. 64x64 EpiRAM tiles), so
     padded instances map exactly onto whole tiles and the energy ledger
     sees the true programmed array,
  2. stacks each bucket and dispatches it through a vmapped jitted PDHG
     pipeline (Ruiz + diagonal preconditioning + Lanczos + while_loop) —
     the zero-collective data-parallel path: with a mesh, instances shard
     across devices and each device solves its slice locally,
  3. caches the compiled executable per (bucket, batch, dtype, options,
     noise, device) signature so repeat traffic never re-lowers, and
  4. strips padding and returns per-instance results in input order.

Every instance gets its own PRNG key (derived from ``opts.seed`` and its
position in the stream), so iterate initialization and read-noise streams
are decorrelated across a bucket.

Past toy sizes, two more concerns take over (ROADMAP item 2):

  * **Sparse streams.**  A ``StandardLP`` whose K is a ``SparseCOO``
    routes through a dedicated sparse bucket pipeline selected by
    ``PDHGOptions.sparse_kernel``.  The default ``"ell"`` backend
    converts COO to ELL — forward (B, m, Wf) AND adjoint
    (B, n, Wa) layouts, widths power-of-two bucketed like ``nnz_bucket``
    — so Ruiz equilibration, Pock–Chambolle diagonals, Lanczos and both
    solve MVMs are gathers + axis-1 reductions with no scatter anywhere
    (the wall-clock path; ``kernels.sparse_mvm``).  ``"bcoo"`` keeps the
    nnz-proportional COO stacking ((B, nnz) data + (B, nnz, 2) indices,
    ``engine.sparse_operator`` scatter contractions) — the
    memory-optimal path.  Neither ever materializes a dense
    (B, m_pad, n_pad) stack.
  * **Async serving.**  ``solve_stream`` submits EVERY bucket to its
    compiled executable first (JAX dispatch is asynchronous; the host
    never blocks between buckets) and only then collects results,
    preferring buckets whose device buffers are already ready.  Large
    buckets donate their stacked operator buffer to the executable
    (``jax.jit(..., donate_argnums=...)``) on backends that support
    donation, so peak device memory stays ~one bucket-stack.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import engine
from ..core.lanczos import (
    NORM_BACKENDS,
    lanczos_svd_jit_mv,
    power_iteration_mv,
)
from ..core.pdhg import PDHGOptions
from ..core.pdhg import opts_static  # noqa: F401  (canonical home; re-export)
from ..kernels.sparse_mvm import (
    coo_row_widths,
    ell_from_coo,
    ell_matvec,
    ell_width_bucket,
)
from ..lp.problem import SparseCOO, StandardLP
from . import sanitize

MIN_BUCKET = 8
MIN_NNZ_BUCKET = 16
# donate the stacked operator buffer to the executable past this size
# (on backends that implement donation; CPU silently ignores it)
DONATE_MIN_BYTES = 32 << 20
# norm-reuse serving (``BatchSolver(norm_reuse=True)``): instances whose
# (shape bucket, sparsity fingerprint) already has a cached operator-norm
# estimate run this many power-iteration refinement MVMs instead of the
# full ``opts.lanczos_iters``-step estimate
NORM_REFINE_ITERS = 8


# ------------------------------------------------------------- bucketing ---

def _ceil_to(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def bucket_dims(m: int, n: int, min_size: int = MIN_BUCKET,
                tile: Optional[Tuple[int, int]] = None) -> Tuple[int, int]:
    """Round ``(m, n)`` up to its bucket.

    Default mode rounds to the enclosing power of two.  With
    ``tile=(rows, cols)`` (device-tile mode) dims snap to multiples of the
    physical crossbar tile instead, so a bucket always fills whole tiles:
    ``bucket_dims(8, 70, tile=(64, 64)) == (64, 128)``.
    """
    if tile is not None:
        tr, tc = tile
        return _ceil_to(max(int(m), 1), tr), _ceil_to(max(int(n), 1), tc)
    up = lambda v: max(min_size, 1 << (int(v) - 1).bit_length())  # noqa: E731
    return up(m), up(n)


def nnz_bucket(nnz: int, min_size: int = MIN_NNZ_BUCKET) -> int:
    """Round a nonzero count up to its power-of-two bucket (so repeat
    sparse traffic with drifting nnz reuses compiled executables)."""
    return max(min_size, 1 << (max(int(nnz), 1) - 1).bit_length())


def pad_problem(lp: StandardLP, m_pad: int, n_pad: int) -> StandardLP:
    """Embed ``lp`` in an (m_pad, n_pad) problem with identical optimum.

    Extra variables are pinned (lb=ub=0, c=0); extra rows are zero with
    b=0.  Any solution of the padded problem restricts to one of the
    original and vice versa.  Padding is dtype-preserving (an f32 stream
    pads in f32 — the old ``np.zeros`` default doubled host memory) and
    sparse-preserving (a SparseCOO K just grows its logical shape; the
    nonzeros are never densified).
    """
    m, n = lp.K.shape
    assert m_pad >= m and n_pad >= n, ((m, n), (m_pad, n_pad))
    dt = lp.K.dtype
    if isinstance(lp.K, SparseCOO):
        K = lp.K.with_shape(m_pad, n_pad)
    else:
        K = np.zeros((m_pad, n_pad), dt)
        K[:m, :n] = lp.K
    b = np.zeros(m_pad, dt)
    b[:m] = lp.b
    c = np.zeros(n_pad, dt)
    c[:n] = lp.c
    lb = np.zeros(n_pad, dt)
    ub = np.zeros(n_pad, dt)
    lb[:n] = lp.lb
    ub[:n] = lp.ub
    x_opt = None
    if lp.x_opt is not None:
        x_opt = np.zeros(n_pad, np.asarray(lp.x_opt).dtype)
        x_opt[:n] = lp.x_opt
    return StandardLP(c=c, K=K, b=b, lb=lb, ub=ub, name=lp.name,
                      x_opt=x_opt, obj_opt=lp.obj_opt)


def stack_problems(lps: Sequence[StandardLP], m: Optional[int] = None,
                   n: Optional[int] = None) -> tuple:
    """Pad a list of StandardLPs to a common shape and DENSE-stack.

    Target dims default to the max over the list (the legacy
    ``distributed.batch_solve`` behaviour); buckets pass them explicitly.
    Sparse members are densified — sparse streams should go through
    ``stack_problems_sparse`` instead, which never materializes
    (B, m, n).
    """
    lps = [lp.densified() for lp in lps]
    m = m if m is not None else max(lp.K.shape[0] for lp in lps)
    n = n if n is not None else max(lp.K.shape[1] for lp in lps)
    padded = [pad_problem(lp, m, n) for lp in lps]
    return tuple(
        np.stack([getattr(p, f) for p in padded])
        for f in ("K", "b", "c", "lb", "ub"))


def stack_problems_sparse(lps: Sequence[StandardLP],
                          m: Optional[int] = None,
                          n: Optional[int] = None,
                          nnz: Optional[int] = None) -> tuple:
    """Stack sparse StandardLPs WITHOUT densifying K.

    Returns ``(data (B, nnz), idx (B, nnz, 2) int32, b, c, lb, ub)``.
    Shape padding is purely logical (zero rows / pinned variables, as in
    ``pad_problem``); nnz padding appends explicit zero entries at
    (0, 0), which contribute nothing to any contraction or scaling.
    """
    assert lps and all(isinstance(lp.K, SparseCOO) for lp in lps), \
        "stack_problems_sparse needs SparseCOO operators"
    m = m if m is not None else max(lp.K.shape[0] for lp in lps)
    n = n if n is not None else max(lp.K.shape[1] for lp in lps)
    nnz = nnz if nnz is not None else max(lp.K.nnz for lp in lps)
    B = len(lps)
    dt = lps[0].K.dtype
    data = np.zeros((B, nnz), dt)
    idx = np.zeros((B, nnz, 2), np.int32)
    vecs = {f: np.zeros((B, dim), dt)
            for f, dim in (("b", m), ("c", n), ("lb", n), ("ub", n))}
    for k, lp in enumerate(lps):
        # coalesce duplicates: the pipeline's scatter preconditioners
        # reduce over stored entries, so parity with the densified
        # problem requires one entry per (row, col)
        K = lp.K.coalesce()
        assert K.shape[0] <= m and K.shape[1] <= n and K.nnz <= nnz, \
            (K.shape, K.nnz, (m, n, nnz))
        data[k, :K.nnz] = K.data
        idx[k, :K.nnz, 0] = K.row
        idx[k, :K.nnz, 1] = K.col
        for f, arr in vecs.items():
            v = getattr(lp, f)
            arr[k, :v.shape[0]] = v
    return (data, idx, vecs["b"], vecs["c"], vecs["lb"], vecs["ub"])


def stack_problems_ell(lps: Sequence[StandardLP],
                       m: Optional[int] = None,
                       n: Optional[int] = None,
                       wf: Optional[int] = None,
                       wa: Optional[int] = None) -> tuple:
    """Stack sparse StandardLPs in ELL form.

    Returns ``(data_f (B, m, wf), cols_f (B, m, wf) int32,
    data_a (B, n, wa), cols_a (B, n, wa) int32, b, c, lb, ub)``.
    The forward layout is the ELL form of K, the adjoint layout the ELL
    form of K^T — storing both keeps every pipeline reduction and both
    solve MVMs scatter-free.  ``wf``/``wa`` default to the exact max
    row/column occupancy over the list (buckets pass their power-of-two
    widths explicitly).  ELL padding slots carry (data 0, col 0), the
    same inertness contract as ``stack_problems_sparse``'s (0, 0)
    entries; explicit zero nonzeros are dropped during conversion, so
    they never widen a row.
    """
    assert lps and all(isinstance(lp.K, SparseCOO) for lp in lps), \
        "stack_problems_ell needs SparseCOO operators"
    m = m if m is not None else max(lp.K.shape[0] for lp in lps)
    n = n if n is not None else max(lp.K.shape[1] for lp in lps)
    if wf is None or wa is None:
        widths = [coo_row_widths(lp.K.row, lp.K.col, lp.K.data,
                                 lp.K.shape) for lp in lps]
        wf = wf if wf is not None else max(w[0] for w in widths)
        wa = wa if wa is not None else max(w[1] for w in widths)
    B = len(lps)
    dt = lps[0].K.dtype
    data_f = np.zeros((B, m, wf), dt)
    cols_f = np.zeros((B, m, wf), np.int32)
    data_a = np.zeros((B, n, wa), dt)
    cols_a = np.zeros((B, n, wa), np.int32)
    vecs = {f: np.zeros((B, dim), dt)
            for f, dim in (("b", m), ("c", n), ("lb", n), ("ub", n))}
    for k, lp in enumerate(lps):
        # coalesce first: ELL stores one slot per (row, col), so
        # duplicates must merge for parity with the densified problem
        K = lp.K.coalesce()
        assert K.shape[0] <= m and K.shape[1] <= n, (K.shape, (m, n))
        data_f[k], cols_f[k] = ell_from_coo(K.data, K.row, K.col,
                                            (m, n), width=wf)
        data_a[k], cols_a[k] = ell_from_coo(K.data, K.col, K.row,
                                            (n, m), width=wa)
        for f, arr in vecs.items():
            v = getattr(lp, f)
            arr[k, :v.shape[0]] = v
    return (data_f, cols_f, data_a, cols_a,
            vecs["b"], vecs["c"], vecs["lb"], vecs["ub"])


# -------------------------------------------------------------- pipeline ---

def _single_solve(K, b, c, lb, ub, T, Sigma, rho, key, static):
    # The iteration core is core.engine's; ``static[-1]`` (opts.kernel)
    # selects the jnp vs fused-Pallas update backend per executable.
    return engine.solve_core(
        K, K.T, b, c, lb, ub, T, Sigma, rho, key, static)


def prep_scale(K, b, c, lb, ub, opts: PDHGOptions):
    """Ruiz + diagonal preconditioning (Algorithm 4 step 0), vmappable.

    Returns the scaled problem, the diagonal step scalings (T, Sigma) and
    the unscaling diagonals (D1, D2).  Operator-norm estimation is NOT
    included — callers estimate rho on whichever operator they actually
    execute (exact K here, the programmed crossbar blocks in
    ``crossbar.solver``).
    """
    from ..core.precondition import apply_ruiz, diagonal_precondition

    scaled = apply_ruiz(K, b, c, lb, ub, iters=opts.ruiz_iters)
    T, Sigma = diagonal_precondition(scaled.K)
    return (scaled.K, scaled.b, scaled.c, scaled.lb, scaled.ub, T, Sigma,
            scaled.D1, scaled.D2)


def _check_norm_backend(opts: PDHGOptions) -> None:
    if opts.norm_backend not in NORM_BACKENDS:
        raise ValueError(f"unknown norm_backend {opts.norm_backend!r}; "
                         f"expected one of {NORM_BACKENDS}")


def _estimate_norm_mv(mv, dim: int, dtype, opts: PDHGOptions,
                      rho_seed=None):
    """RAW operator-norm estimate (no Lemma-2 margin) on a symmetric
    matvec, per ``opts.norm_backend``.  With a ``rho_seed`` (the
    norm-reuse serving path: a cached estimate for this sparsity
    fingerprint) only a short power refinement runs and the result is
    floored at the seed — same-pattern instances share spectra, so the
    cached maximum is already the safe bet and the refinement just
    catches genuinely hotter coefficient draws."""
    if rho_seed is not None:
        est = power_iteration_mv(mv, dim, dtype, iters=NORM_REFINE_ITERS)
        return jnp.maximum(est, jnp.asarray(rho_seed, est.dtype))
    if opts.norm_backend == "power":
        return power_iteration_mv(mv, dim, dtype,
                                  iters=opts.lanczos_iters)
    return lanczos_svd_jit_mv(mv, dim, dtype, k_max=opts.lanczos_iters)


def _prep_one(K, b, c, lb, ub, rho_seed=None, *, opts: PDHGOptions):
    from ..core.symblock import build_sym_block, mv

    (Ks, bs, cs, lbs, ubs, T, Sigma, D1, D2) = prep_scale(
        K, b, c, lb, ub, opts)
    if opts.norm_override is not None:
        rho = jnp.asarray(opts.norm_override, Ks.dtype)
    else:
        Keff = jnp.sqrt(Sigma)[:, None] * Ks * jnp.sqrt(T)[None, :]
        M = build_sym_block(Keff)
        rho = _estimate_norm_mv(lambda v: mv(M, v), M.shape[0], M.dtype,
                                opts, rho_seed)
    return (Ks, bs, cs, lbs, ubs, T, Sigma, rho, D1, D2)


def make_bucket_pipeline(opts: PDHGOptions, sigma_read: float = 0.0,
                         norm_seeded: bool = False):
    """vmapped prep + solve over a stacked (B, m, n) bucket.

    ``keys`` carries one PRNG key per instance (iterate init + read-noise
    streams).  Returns (xs, ys, iterations, merits, rhos) in the ORIGINAL
    (unscaled) coordinates — ``rhos`` is the per-instance RAW norm
    estimate (pre-margin), which the norm-reuse cache records.  With
    ``norm_seeded`` the pipeline takes an extra per-instance
    ``rho_seeds`` argument and runs the short refinement instead of the
    full estimate (see ``_estimate_norm_mv``).  Pure function of the
    stacked arrays — safe to jit/AOT.
    """
    static = opts_static(opts, sigma_read)
    _check_norm_backend(opts)

    def _run(Ks, bs, cs, lbs, ubs, keys, rho_seeds=None):
        prep = functools.partial(_prep_one, opts=opts)
        if rho_seeds is None:
            prepped = jax.vmap(prep)(Ks, bs, cs, lbs, ubs)
        else:
            prepped = jax.vmap(prep)(Ks, bs, cs, lbs, ubs, rho_seeds)
        (Ks2, bs2, cs2, lbs2, ubs2, Ts, Sigs, rhos, D1s, D2s) = prepped
        rhos_used = rhos
        if opts.norm_override is None:
            # only the (noisy) estimate gets the Lemma-2 margin;
            # an explicit norm_override is trusted as-is (= solve_jit)
            rhos_used = engine.lemma2_margin(rhos, sigma_read)
        solver = functools.partial(_single_solve, static=static)
        xs, ys, its, merits = jax.vmap(solver)(
            Ks2, bs2, cs2, lbs2, ubs2, Ts, Sigs, rhos_used, keys)
        return D2s * xs, D1s * ys, its, merits, rhos

    if norm_seeded:
        def pipeline(Ks, bs, cs, lbs, ubs, keys, rho_seeds):
            return _run(Ks, bs, cs, lbs, ubs, keys, rho_seeds)
    else:
        def pipeline(Ks, bs, cs, lbs, ubs, keys):
            return _run(Ks, bs, cs, lbs, ubs, keys)

    return pipeline


# ------------------------------------------------------- sparse pipeline ---

def _coo_matvec(data, row, col, v, out_dim: int):
    """COO contraction ``out[row] += data * v[col]`` (scatter-add); the
    sparse twin of one dense MVM, vmappable and while_loop-safe."""
    return jnp.zeros(out_dim, v.dtype).at[row].add(data * v[col])


def _prep_one_sparse(data, idx, b, c, lb, ub, opts: PDHGOptions):
    """Sparse Ruiz + Pock–Chambolle diagonals on COO nonzeros.

    Mirrors ``precondition.apply_ruiz`` / ``diagonal_precondition``
    exactly (same eps, same sqrt-of-inf-norm update), but every row/col
    reduction is a scatter over the stored entries — padded zero entries
    at (0, 0) contribute nothing.  Returns the scaled nonzeros plus the
    same tuple layout as the dense ``prep_scale``.
    """
    dt = data.dtype
    m, n = b.shape[0], c.shape[0]
    row, col = idx[:, 0], idx[:, 1]
    eps = 1e-12
    D1 = jnp.ones(m, dt)
    D2 = jnp.ones(n, dt)
    d = data
    for _ in range(opts.ruiz_iters):
        ad = jnp.abs(d)
        r = jnp.sqrt(jnp.zeros(m, dt).at[row].max(ad))
        cc = jnp.sqrt(jnp.zeros(n, dt).at[col].max(ad))
        r = jnp.where(r < eps, 1.0, r)
        cc = jnp.where(cc < eps, 1.0, cc)
        D1 = D1 / r
        D2 = D2 / cc
        d = data * D1[row] * D2[col]
    bs = D1 * b
    cs = D2 * c
    lbs = jnp.where(jnp.isfinite(lb), lb / D2, lb)
    ubs = jnp.where(jnp.isfinite(ub), ub / D2, ub)
    ad = jnp.abs(d)
    T = 1.0 / jnp.maximum(jnp.zeros(n, dt).at[col].add(ad), eps)
    Sigma = 1.0 / jnp.maximum(jnp.zeros(m, dt).at[row].add(ad), eps)
    return d, bs, cs, lbs, ubs, T, Sigma, D1, D2


def make_sparse_bucket_pipeline(opts: PDHGOptions, sigma_read: float = 0.0,
                                norm_seeded: bool = False):
    """vmapped sparse prep + solve over a stacked COO bucket.

    Inputs are the ``stack_problems_sparse`` layout: (B, nnz) data,
    (B, nnz, 2) indices, plus the dense vectors and per-instance keys.
    The operator-norm estimate runs a matvec-only Lanczos (or power
    iteration, per ``opts.norm_backend``; a short seeded refinement
    with ``norm_seeded``) on the symmetric block of
    Sigma^{1/2} K T^{1/2} (two COO contractions per iteration); the
    solve itself mounts ``engine.sparse_operator`` on a BCOO built from
    the scaled nonzeros.  No dense (m, n) array ever exists on host or
    device.  Returns an extra trailing ``rhos`` (raw per-instance norm
    estimates) like ``make_bucket_pipeline``.
    """
    static = opts_static(opts, sigma_read)
    _check_norm_backend(opts)

    def one(kd, ki, b, c, lb, ub, key, rho_seed=None):
        m, n = b.shape[0], c.shape[0]
        (d, bs, cs, lbs, ubs, T, Sigma, D1, D2) = _prep_one_sparse(
            kd, ki, b, c, lb, ub, opts)
        if opts.norm_override is not None:
            rho_raw = jnp.asarray(opts.norm_override, kd.dtype)
            rho = rho_raw
        else:
            row, col = ki[:, 0], ki[:, 1]
            deff = d * jnp.sqrt(Sigma)[row] * jnp.sqrt(T)[col]

            def mv(v):         # symmetric block M' of Keff, matvec-only
                top = _coo_matvec(deff, row, col, v[m:], m)
                bot = _coo_matvec(deff, col, row, v[:m], n)
                return jnp.concatenate([top, bot])

            rho_raw = _estimate_norm_mv(mv, m + n, kd.dtype, opts,
                                        rho_seed)
            rho = engine.lemma2_margin(rho_raw, sigma_read)
        K_sp = jsparse.BCOO((d, ki), shape=(m, n))
        x, y, it, merit = engine.solve_core(
            K_sp, None, bs, cs, lbs, ubs, T, Sigma, rho, key, static)
        return D2 * x, D1 * y, it, merit, rho_raw

    if norm_seeded:
        def pipeline(Kdata, Kidx, bs, cs, lbs, ubs, keys, rho_seeds):
            return jax.vmap(one)(Kdata, Kidx, bs, cs, lbs, ubs, keys,
                                 rho_seeds)
    else:
        def pipeline(Kdata, Kidx, bs, cs, lbs, ubs, keys):
            return jax.vmap(one)(Kdata, Kidx, bs, cs, lbs, ubs, keys)

    return pipeline


# ---------------------------------------------------------- ELL pipeline ---

def _row_reduce(a, reduce_fn):
    """axis-1 reduction of an (m, W) ELL value array, total-safe at
    W == 0 (an all-zero operator's ELL form has zero width)."""
    if a.shape[1] == 0:
        return jnp.zeros(a.shape[0], a.dtype)
    return reduce_fn(a, axis=1)


def _prep_one_ell(df, cf, da, ca, b, c, lb, ub, opts: PDHGOptions):
    """Sparse Ruiz + Pock–Chambolle diagonals on ELL nonzeros.

    Mirrors ``_prep_one_sparse`` (same eps, same guard, same update
    order — the scaling diagonals come out bit-identical), but every
    row/column reduction is a vectorized axis-1 max/sum on the layout
    that already has it contiguous: row stats on the forward ELL,
    column stats on the adjoint ELL.  No scatter anywhere.  Padding
    slots (data 0, col 0) scale to 0 and never move a max or a sum.
    """
    dt = df.dtype
    eps = 1e-12
    m, n = b.shape[0], c.shape[0]
    D1 = jnp.ones(m, dt)
    D2 = jnp.ones(n, dt)
    sf, sa = df, da
    for _ in range(opts.ruiz_iters):
        r = jnp.sqrt(_row_reduce(jnp.abs(sf), jnp.max))
        cc = jnp.sqrt(_row_reduce(jnp.abs(sa), jnp.max))
        r = jnp.where(r < eps, 1.0, r)
        cc = jnp.where(cc < eps, 1.0, cc)
        D1 = D1 / r
        D2 = D2 / cc
        sf = df * D1[:, None] * D2[cf]
        sa = da * D2[:, None] * D1[ca]
    bs = D1 * b
    cs = D2 * c
    lbs = jnp.where(jnp.isfinite(lb), lb / D2, lb)
    ubs = jnp.where(jnp.isfinite(ub), ub / D2, ub)
    T = 1.0 / jnp.maximum(_row_reduce(jnp.abs(sa), jnp.sum), eps)
    Sigma = 1.0 / jnp.maximum(_row_reduce(jnp.abs(sf), jnp.sum), eps)
    return sf, sa, bs, cs, lbs, ubs, T, Sigma, D1, D2


def make_ell_bucket_pipeline(opts: PDHGOptions, sigma_read: float = 0.0,
                             norm_seeded: bool = False):
    """vmapped ELL prep + solve over a stacked ELL bucket.

    Inputs are the ``stack_problems_ell`` layout plus per-instance keys.
    The operator-norm estimate runs a matvec-only Lanczos with two ELL
    gathers per iteration; the solve mounts ``engine.sparse_ell_operator``
    (which has no fused window: ``opts.megakernel`` is refused).  Like
    the COO pipeline, no
    dense (m, n) array ever exists on host or device — but unlike it,
    no iteration-path op is a scatter, which is what makes sparse win
    on wall clock and not just memory.  Returns an extra trailing
    ``rhos`` (raw per-instance norm estimates) like
    ``make_bucket_pipeline``; ``norm_seeded`` swaps the full estimate
    for the short cached-seed refinement.
    """
    static = opts_static(opts, sigma_read)
    _check_norm_backend(opts)

    def one(df, cf, da, ca, b, c, lb, ub, key, rho_seed=None):
        m, n = b.shape[0], c.shape[0]
        (sf, sa, bs, cs, lbs, ubs, T, Sigma, D1, D2) = _prep_one_ell(
            df, cf, da, ca, b, c, lb, ub, opts)
        if opts.norm_override is not None:
            rho_raw = jnp.asarray(opts.norm_override, df.dtype)
            rho = rho_raw
        else:
            rtS, rtT = jnp.sqrt(Sigma), jnp.sqrt(T)
            deff_f = sf * rtS[:, None] * rtT[cf]
            deff_a = sa * rtT[:, None] * rtS[ca]

            def mv(v):         # symmetric block M' of Keff, matvec-only
                top = ell_matvec(deff_f, cf, v[m:])
                bot = ell_matvec(deff_a, ca, v[:m])
                return jnp.concatenate([top, bot])

            rho_raw = _estimate_norm_mv(mv, m + n, df.dtype, opts,
                                        rho_seed)
            rho = engine.lemma2_margin(rho_raw, sigma_read)
        op = engine.sparse_ell_operator(sf, cf, sa, ca, sigma_read)
        x, y, it, merit = engine.solve_core(
            None, None, bs, cs, lbs, ubs, T, Sigma, rho, key, static,
            operator=op)
        return D2 * x, D1 * y, it, merit, rho_raw

    if norm_seeded:
        def pipeline(df, cf, da, ca, bs, cs, lbs, ubs, keys, rho_seeds):
            return jax.vmap(one)(df, cf, da, ca, bs, cs, lbs, ubs, keys,
                                 rho_seeds)
    else:
        def pipeline(df, cf, da, ca, bs, cs, lbs, ubs, keys):
            return jax.vmap(one)(df, cf, da, ca, bs, cs, lbs, ubs, keys)

    return pipeline


# ------------------------------------------------------------- scheduler ---

@dataclasses.dataclass
class BatchItemResult:
    """Per-instance result with padding stripped."""

    name: str
    x: np.ndarray
    y: np.ndarray
    obj: float
    iterations: int
    merit: float
    converged: bool
    bucket: Tuple[int, int]
    mvm_calls: int = 0          # device MVMs (engine.mvm_accounting)
    sparse: bool = False        # served by a sparse (ELL/COO) pipeline

    @property
    def status(self) -> str:
        # a non-finite merit means the iterate blew up — that is
        # divergence, not a clean iteration limit (converged is already
        # False: NaN <= tol compares false)
        if not np.isfinite(self.merit):
            return "diverged"
        return "optimal" if self.converged else "iteration_limit"


def _donation_supported() -> bool:
    """Buffer donation is a no-op on CPU; only claim it where XLA
    implements it (keeps executable cache keys stable per platform)."""
    try:
        return jax.local_devices()[0].platform in ("gpu", "cuda", "rocm",
                                                   "tpu")
    except Exception:                      # pragma: no cover - no devices
        return False


def _outputs_ready(out) -> bool:
    """True when every device buffer of a dispatched result is ready
    (computation finished) — drives completion-order collection."""
    return all(leaf.is_ready() for leaf in jax.tree_util.tree_leaves(out)
               if hasattr(leaf, "is_ready"))


class BatchSolver:
    """Shape-bucketing scheduler with a compiled-executable cache.

    One instance amortizes compilation across calls: the first stream
    touching a ``(bucket, batch, dtype)`` signature lowers + compiles the
    bucket pipeline (a cache MISS); every later stream with the same
    signature reuses the executable (a HIT).  ``mesh`` shards the batch
    dimension over ``batch_axes`` — zero collectives during the solve.

    ``tile`` switches bucketing to device-tile mode (multiples of the
    physical crossbar dims); ``sigma_read`` adds multiplicative per-MVM
    read noise inside the vmapped solver; ``kernel`` ("jnp" | "pallas")
    selects the engine's update backend (all three are part of the
    executable cache key — executables never cross kernels).  Subclasses
    (``crossbar.solver.CrossbarBatchSolver``) override
    ``_make_pipeline``/``_collect``/``_device_signature`` to run full
    device physics in the same bucketed harness.

    Sparse instances (``lp.is_sparse``) are bucketed separately (shape
    bucket + power-of-two nnz bucket) and served by the COO pipeline
    when the solver ``supports_sparse`` (the crossbar subclass programs
    every physical cell, so it densifies instead).  ``async_dispatch``
    submits all buckets before collecting any result (set False for
    blocking per-bucket dispatch, e.g. to bound device memory on tiny
    hosts); ``donate_min_bytes`` is the stacked-operator size beyond
    which the input buffer is donated to the executable.
    ``last_stream_stats`` records, per ``solve_stream`` call, the host
    bytes each stacking path materialized, dispatch/collect timings, and
    ``compiles`` — the number of XLA compilations the call triggered
    (``runtime.sanitize``; a warm pass over a bucket mix served before
    must report 0).  ``transfer_sanitize=True`` additionally runs every
    executable under ``sanitize.no_implicit_transfers()``, so an
    accidental per-call host<->device transfer raises instead of
    silently serializing dispatch.

    ``norm_reuse=True`` turns on the cross-instance operator-norm cache:
    every served instance's raw norm estimate is recorded under its
    (shape bucket, sparsity-pattern fingerprint) key, and a bucket whose
    instances ALL have cached estimates is served by a seeded executable
    that replaces the full ``lanczos_iters``-step estimate with a
    ``NORM_REFINE_ITERS``-step power refinement floored at the cached
    value (``_estimate_norm_mv``).  The seeded twin executable is
    compiled EAGERLY on the cold pass, so warm streams stay at zero
    compiles; the cache changes step sizes (a refined estimate instead
    of the full one), so it is opt-in — the default ``False`` path is
    bit-identical to not having the feature.
    """

    supports_sparse = True

    def __init__(self, opts: PDHGOptions = PDHGOptions(), *,
                 mesh=None, batch_axes: Tuple[str, ...] = ("data",),
                 min_bucket: int = MIN_BUCKET,
                 sigma_read: float = 0.0,
                 tile: Optional[Tuple[int, int]] = None,
                 kernel: Optional[str] = None,
                 async_dispatch: bool = True,
                 donate_min_bytes: int = DONATE_MIN_BYTES,
                 transfer_sanitize: bool = False,
                 norm_reuse: bool = False):
        if kernel is not None:
            # convenience override; the kernel choice rides in opts and
            # therefore in every executable cache signature
            opts = dataclasses.replace(opts, kernel=kernel)
        self.opts = opts
        self.mesh = mesh
        self.batch_axes = tuple(batch_axes)
        self.min_bucket = min_bucket
        self.sigma_read = float(sigma_read)
        self.tile = None if tile is None else (int(tile[0]), int(tile[1]))
        self.async_dispatch = bool(async_dispatch)
        self.donate_min_bytes = int(donate_min_bytes)
        self.transfer_sanitize = bool(transfer_sanitize)
        self.norm_reuse = bool(norm_reuse)
        self._cache = {}
        self._norm_cache: dict = {}
        self._seeded_idxs: set = set()
        self.cache_hits = 0
        self.cache_misses = 0
        self.last_stream_stats: dict = {}

    # -- subclass hooks -----------------------------------------------

    def _bucket(self, m: int, n: int) -> Tuple[int, int]:
        return bucket_dims(m, n, min_size=self.min_bucket, tile=self.tile)

    def _make_pipeline(self, norm_seeded: bool = False):
        return make_bucket_pipeline(self.opts, self.sigma_read,
                                    norm_seeded=norm_seeded)

    def _make_sparse_pipeline(self, norm_seeded: bool = False):
        return make_sparse_bucket_pipeline(self.opts, self.sigma_read,
                                           norm_seeded=norm_seeded)

    def _make_ell_pipeline(self, norm_seeded: bool = False):
        return make_ell_bucket_pipeline(self.opts, self.sigma_read,
                                        norm_seeded=norm_seeded)

    def _device_signature(self):
        """Hashable device component of the executable cache key."""
        return None

    # -- executable cache ---------------------------------------------

    def _batch_quantum(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.batch_axes]))

    def _padded_batch(self, n_items: int) -> int:
        pow2 = 1 << (n_items - 1).bit_length()
        return _ceil_to(pow2, self._batch_quantum())

    def _sharding(self):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(self.batch_axes))

    def _cache_key(self, shape_sig, B: int, dtype, donate: bool):
        return (shape_sig, B, jnp.dtype(dtype).name, bool(donate),
                opts_static(self.opts, self.sigma_read),
                # prep-stage options that shape the pipeline but live
                # outside the solve-core static tuple
                (self.opts.ruiz_iters, self.opts.lanczos_iters,
                 self.opts.norm_override, self.opts.norm_backend),
                self.tile,
                self._device_signature(),
                None if self.mesh is None else
                (tuple(self.mesh.axis_names),
                 tuple(self.mesh.devices.shape), self.batch_axes))

    def _compile(self, key, pipeline, args, donate: bool):
        hit = self._cache.get(key)
        if hit is not None:
            self.cache_hits += 1
            return hit
        self.cache_misses += 1
        donate_argnums = (0,) if donate else ()
        compiled = jax.jit(pipeline, donate_argnums=donate_argnums) \
            .lower(*args).compile()
        self._cache[key] = compiled
        return compiled

    def _sds(self, shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=self._sharding())

    @staticmethod
    def _key_template():
        """Shape/dtype template for one per-instance PRNG key slot.

        The constant key never produces random bits: executables are
        lowered from abstract shapes only, and the real per-instance
        keys are threaded at call time by ``_instance_keys``.
        """
        return jax.random.PRNGKey(0)  # jaxlint: disable=R2

    def _executable(self, mb: int, nb: int, B: int, dtype, *,
                    donate: bool = False, seeded: bool = False):
        sig = ("dense", mb, nb) + (("normseed",) if seeded else ())
        key = self._cache_key(sig, B, dtype, donate)
        k0 = self._key_template()
        args = (self._sds((B, mb, nb), dtype), self._sds((B, mb), dtype),
                self._sds((B, nb), dtype), self._sds((B, nb), dtype),
                self._sds((B, nb), dtype), self._sds((B, *k0.shape),
                                                     k0.dtype))
        if seeded:
            args = args + (self._sds((B,), dtype),)
        return self._compile(key, self._make_pipeline(norm_seeded=seeded)
                             if seeded else self._make_pipeline(),
                             args, donate)

    def _executable_sparse(self, mb: int, nb: int, nnz: int, B: int,
                           dtype, *, donate: bool = False,
                           seeded: bool = False):
        sig = ("sparse", mb, nb, nnz) + (("normseed",) if seeded else ())
        key = self._cache_key(sig, B, dtype, donate)
        k0 = self._key_template()
        args = (self._sds((B, nnz), dtype),
                self._sds((B, nnz, 2), jnp.int32),
                self._sds((B, mb), dtype), self._sds((B, nb), dtype),
                self._sds((B, nb), dtype), self._sds((B, nb), dtype),
                self._sds((B, *k0.shape), k0.dtype))
        if seeded:
            args = args + (self._sds((B,), dtype),)
        return self._compile(key,
                             self._make_sparse_pipeline(norm_seeded=seeded)
                             if seeded else self._make_sparse_pipeline(),
                             args, donate)

    def _executable_ell(self, mb: int, nb: int, wf: int, wa: int, B: int,
                        dtype, *, donate: bool = False,
                        seeded: bool = False):
        sig = ("ell", mb, nb, wf, wa) + (("normseed",) if seeded else ())
        key = self._cache_key(sig, B, dtype, donate)
        k0 = self._key_template()
        args = (self._sds((B, mb, wf), dtype),
                self._sds((B, mb, wf), jnp.int32),
                self._sds((B, nb, wa), dtype),
                self._sds((B, nb, wa), jnp.int32),
                self._sds((B, mb), dtype), self._sds((B, nb), dtype),
                self._sds((B, nb), dtype), self._sds((B, nb), dtype),
                self._sds((B, *k0.shape), k0.dtype))
        if seeded:
            args = args + (self._sds((B,), dtype),)
        return self._compile(key, self._make_ell_pipeline(norm_seeded=seeded)
                             if seeded else self._make_ell_pipeline(),
                             args, donate)

    def cache_info(self) -> dict:
        return {"hits": self.cache_hits, "misses": self.cache_misses,
                "entries": len(self._cache)}

    def hlo_texts(self) -> List[str]:
        """Compiled HLO text of every cached executable (shows, e.g.,
        whether a Pallas kernel compiled in as ``tpu_custom_call``)."""
        return [c.as_text() for c in self._cache.values()]

    # -- cross-instance norm cache ------------------------------------

    def _norm_fingerprint(self, lp: StandardLP):
        """Norm-cache key: shape bucket + exact shape + sparsity pattern.

        Sparse instances hash their COO index arrays (blake2b-64), so an
        estimate is only ever reused across instances with the SAME
        nonzero pattern — the paper's repeated-structure setting (one
        constraint template, many coefficient draws).  Index order is
        hashed as given: a reordered but equal pattern just misses the
        cache (conservative, never wrong).  Dense instances share one
        entry per exact shape.
        """
        bucket = self._bucket(*lp.K.shape)
        if isinstance(lp.K, SparseCOO):
            h = hashlib.blake2b(digest_size=8)
            h.update(np.ascontiguousarray(
                np.asarray(lp.K.row, np.int64)).tobytes())
            h.update(np.ascontiguousarray(
                np.asarray(lp.K.col, np.int64)).tobytes())
            return (bucket, tuple(lp.K.shape), int(lp.K.nnz),
                    h.hexdigest())
        return (bucket, tuple(lp.K.shape))

    # -- solving ------------------------------------------------------

    def _instance_keys(self, idxs: Sequence[int], n_total: int,
                       B: int) -> jnp.ndarray:
        """One PRNG key per batch slot: fold the instance's position in
        the stream into ``opts.seed`` (filler slots get out-of-range
        positions, so even dropped work is decorrelated)."""
        base = jax.random.PRNGKey(self.opts.seed)
        positions = list(idxs) + [n_total + j for j in range(B - len(idxs))]
        return jax.vmap(lambda p: jax.random.fold_in(base, p))(
            jnp.asarray(positions, jnp.uint32))

    def _collect(self, out, bucket: Tuple[int, int], idxs: Sequence[int],
                 lps: Sequence[StandardLP], results: list) -> None:
        xs, ys, its, merits = out[:4]
        xs, ys = np.asarray(xs), np.asarray(ys)
        its, merits = np.asarray(its), np.asarray(merits)
        # trailing rhos (raw norm estimates) arrived with the 5-tuple
        # pipelines; tolerate legacy 4-tuples (e.g. checkpoints gathered
        # from pods running an older serialization)
        rhos = np.asarray(out[4]) if len(out) > 4 else None
        record_norms = (rhos is not None and self.norm_reuse
                        and self.opts.norm_override is None)
        for k, i in enumerate(idxs):
            lp = lps[i]
            m, n = lp.K.shape
            x = xs[k, :n]
            it = int(its[k])
            if self.opts.norm_override is not None:
                lanczos = 0
            elif i in self._seeded_idxs:
                lanczos = NORM_REFINE_ITERS
            else:
                lanczos = self.opts.lanczos_iters
            results[i] = BatchItemResult(
                name=lp.name, x=x, y=ys[k, :m],
                obj=float(lp.c @ x), iterations=it,
                merit=float(merits[k]),
                converged=bool(merits[k] <= self.opts.tol),
                bucket=bucket,
                mvm_calls=engine.mvm_accounting(
                    it, self.opts.check_every, lanczos,
                    restart=self.opts.restart),
                sparse=bool(getattr(lp, "is_sparse", False)),
            )
            if record_norms and np.isfinite(rhos[k]):
                fp = self._norm_fingerprint(lp)
                prev = self._norm_cache.get(fp)
                val = float(rhos[k])
                self._norm_cache[fp] = (val if prev is None
                                        else max(prev, val))

    def _donate(self, nbytes: int) -> bool:
        return nbytes >= self.donate_min_bytes and _donation_supported()

    def _dispatch_bucket(self, group, idxs, n_total: int,
                         mb: int, nb: int, sig, dtype,
                         stats):
        """Stack one bucket and submit it to its compiled executable.

        ``sig`` is the group's sparse signature: None for dense serving,
        a bare int nnz bucket for the COO/BCOO backend, or
        ``("ell", wf, wa)`` width buckets for the ELL backend.  Returns
        the (asynchronously dispatched) device outputs — the call never
        blocks on the solve itself.
        """
        B = self._padded_batch(len(group))
        # norm-reuse serving: a bucket is seeded only when EVERY member's
        # fingerprint already has a cached estimate (filler slots reuse
        # the first member's seed — their results are dropped anyway)
        rho_seeds = None
        if self.norm_reuse and self.opts.norm_override is None:
            cached = [self._norm_cache.get(self._norm_fingerprint(lp))
                      for lp in group]
            if all(v is not None for v in cached):
                # dtype-convert on host: jnp.asarray of a ready numpy
                # array is a pure transfer, so a first seeded pass never
                # triggers an eager convert compile (warm streams must
                # stay at zero)
                rho_seeds = jnp.asarray(np.asarray(
                    cached + [cached[0]] * (B - len(group)),
                    jax.dtypes.canonicalize_dtype(dtype)))
        seeded = rho_seeds is not None
        # batch padding repeats the first instance; extras are dropped
        filler = [group[0]] * (B - len(group))
        keys = self._instance_keys(idxs, n_total, B)
        if isinstance(sig, tuple):                       # ("ell", wf, wa)
            _, wf, wa = sig
            stacked = stack_problems_ell(group + filler, m=mb, n=nb,
                                         wf=wf, wa=wa)
            stats["sparse_stack_bytes"] += sum(a.nbytes for a in stacked)
            arrays = [jnp.asarray(a, jnp.int32) if i in (1, 3)
                      else jnp.asarray(a, dtype)
                      for i, a in enumerate(stacked)]
            donate = self._donate(arrays[0].nbytes)
            exe_fn = functools.partial(self._executable_ell, mb, nb, wf,
                                       wa, B, dtype, donate=donate)
        elif sig is not None:                            # bare int nnz
            stacked = stack_problems_sparse(group + filler, m=mb, n=nb,
                                            nnz=sig)
            stats["sparse_stack_bytes"] += sum(a.nbytes for a in stacked)
            arrays = ([jnp.asarray(stacked[0], dtype),
                       jnp.asarray(stacked[1], jnp.int32)]
                      + [jnp.asarray(a, dtype) for a in stacked[2:]])
            donate = self._donate(arrays[0].nbytes)
            exe_fn = functools.partial(self._executable_sparse, mb, nb,
                                       sig, B, dtype, donate=donate)
        else:
            group = [lp.densified() for lp in group]
            filler = [group[0]] * (B - len(group))
            stacked = stack_problems(group + filler, m=mb, n=nb)
            stats["dense_stack_bytes"] += sum(a.nbytes for a in stacked)
            arrays = [jnp.asarray(a, dtype) for a in stacked]
            donate = self._donate(arrays[0].nbytes)
            exe_fn = functools.partial(self._executable, mb, nb, B, dtype,
                                       donate=donate)
        exe = exe_fn(seeded=seeded)
        if self.norm_reuse and self.opts.norm_override is None \
                and not seeded:
            # cold pass over a new fingerprint set: compile the seeded
            # twin NOW so the warm stream that will hit the cache later
            # reports zero compiles (bench_guard --max-warm-compiles 0)
            exe_fn(seeded=True)
        if seeded:
            self._seeded_idxs.update(idxs)
            stats["norm_seeded_buckets"] += 1
        stats["donated_buckets"] += int(donate)
        sh = self._sharding()
        if sh is not None:
            arrays = [jax.device_put(a, sh) for a in arrays]
            keys = jax.device_put(keys, sh)
            if seeded:
                rho_seeds = jax.device_put(rho_seeds, sh)
        call_args = ((*arrays, keys, rho_seeds) if seeded
                     else (*arrays, keys))
        if self.transfer_sanitize:
            # inputs are on device by now (the jnp.asarray stacking above
            # is the one sanctioned upload); anything implicit past this
            # point is a serving bug
            with sanitize.no_implicit_transfers():
                return exe(*call_args)
        return exe(*call_args)

    def _sparse_signature(self, lp: StandardLP):
        """Sparse component of an instance's bucket key: the nnz bucket
        (bare int — the COO/BCOO stacking axis) or the pair of ELL width
        buckets.  Either way, one occupancy outlier never inflates (and
        never recompiles) the whole shape bucket's stack."""
        if self.opts.sparse_kernel == "ell":
            wf, wa = coo_row_widths(lp.K.row, lp.K.col, lp.K.data,
                                    lp.K.shape)
            return ("ell", ell_width_bucket(wf), ell_width_bucket(wa))
        return nnz_bucket(lp.K.nnz)

    def _group_buckets(self, lps: Sequence[StandardLP]) -> dict:
        """Group stream positions by ((m_bucket, n_bucket), sparse sig).

        Pure function of the stream (and solver config): every process
        of a multi-pod deployment derives the identical grouping, which
        is what makes coordination-free bucket routing possible."""
        buckets: dict = {}
        for i, lp in enumerate(lps):
            sp = bool(getattr(lp, "is_sparse", False)) and \
                self.supports_sparse
            sig = self._sparse_signature(lp) if sp else None
            buckets.setdefault((self._bucket(*lp.K.shape), sig),
                               []).append(i)
        return buckets

    # -- multi-pod routing hooks (runtime.cluster overrides these) ----

    def _route(self, buckets: dict) -> Tuple[dict, dict]:
        """Split buckets into (served here, served by other pods).

        The base scheduler is single-pod: everything is local."""
        return buckets, {}

    def _bucket_served(self, key, idxs: Sequence[int], out) -> None:
        """Called once per locally served bucket with its device outputs
        (after collection) — the cluster solver publishes here."""

    def _gather_remote(self, remote: dict, lps, results, stats) -> None:
        """Collect buckets served by other pods.  Single-pod: none."""
        if remote:      # pragma: no cover - _route never yields any here
            raise RuntimeError("base BatchSolver cannot gather remote "
                               f"buckets: {sorted(remote)}")

    def solve_stream(self, lps: Sequence[StandardLP]) -> List[BatchItemResult]:
        """Solve a heterogeneous stream; results come back in input order.

        Dispatch-then-collect: every locally routed bucket is stacked
        and submitted to its compiled executable before ANY result is
        pulled back (JAX dispatch is asynchronous, so device work
        overlaps host stacking of later buckets), then results are
        collected preferring buckets whose buffers are already ready.
        ``async_dispatch=False`` restores blocking per-bucket serving.
        Buckets routed to OTHER pods (``runtime.cluster``) are gathered
        after the local work completes.
        """
        lps = list(lps)
        dtype = jnp.dtype(self.opts.dtype)
        buckets = self._group_buckets(lps)
        mine, remote = self._route(buckets)

        results: List[Optional[object]] = [None] * len(lps)
        self._seeded_idxs = set()
        stats = {"n_buckets": len(buckets), "n_local_buckets": len(mine),
                 "dense_stack_bytes": 0,
                 "sparse_stack_bytes": 0, "donated_buckets": 0,
                 "norm_seeded_buckets": 0,
                 "dispatch_s": 0.0, "collect_s": 0.0, "compiles": 0}
        compiles0 = sanitize.compile_counts()["compiles"]
        t0 = time.perf_counter()
        pending = []
        for ((mb, nb), sig), idxs in mine.items():
            group = [lps[i] for i in idxs]
            out = self._dispatch_bucket(group, idxs, len(lps), mb, nb, sig,
                                        dtype, stats)
            if self.async_dispatch:
                pending.append((out, ((mb, nb), sig), idxs))
            else:
                jax.block_until_ready(out)
                self._collect(out, (mb, nb), idxs, lps, results)
                self._bucket_served(((mb, nb), sig), idxs, out)
        stats["dispatch_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        while pending:
            # completion order: prefer a bucket whose buffers are ready;
            # fall back to the oldest submission (blocking on it).
            nxt = next((p for p in pending if _outputs_ready(p[0])),
                       pending[0])
            pending.remove(nxt)
            out, key, idxs = nxt
            self._collect(out, key[0], idxs, lps, results)
            self._bucket_served(key, idxs, out)
        stats["collect_s"] = time.perf_counter() - t0
        self._gather_remote(remote, lps, results, stats)
        stats["compiles"] = (sanitize.compile_counts()["compiles"]
                             - compiles0)
        self.last_stream_stats = stats
        return results  # type: ignore[return-value]


def solve_stream(lps: Sequence[StandardLP],
                 opts: PDHGOptions = PDHGOptions(), *,
                 mesh=None, solver: Optional[BatchSolver] = None,
                 ) -> List[BatchItemResult]:
    """One-shot entry point; pass ``solver`` to keep the executable cache
    warm across calls."""
    if solver is None:
        solver = BatchSolver(opts, mesh=mesh)
    return solver.solve_stream(lps)

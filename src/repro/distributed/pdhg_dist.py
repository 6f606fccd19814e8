"""Distributed in-memory PDHG via shard_map (DESIGN.md §4).

The device mesh is the crossbar grid: each device owns a static tile of
the Ruiz-scaled constraint matrix K (equivalently, of the symmetric block
M — K row/col tiles and their transposes are the SAME buffers read both
ways, so the encode-once property survives sharding).  Per iteration:

  dual step   K @ x_bar : local (m_loc, n_loc) @ (n_loc,) then
              psum over the COLUMN axis ("model")     — "sum the currents"
  primal step K^T @ y   : local transpose-read then
              psum over the ROW axes ("pod","data")

Vectors are the only thing that ever moves (two small psums per
iteration); K is written once at setup.  This is the paper's
communication pattern mapped onto jax.lax collectives.

The iteration math itself is ``core.engine``'s (shared with the jit /
batch / crossbar paths); this module contributes the psum-tiled operator
backend's data layout and the psum-reduced KKT merit.

Exposes:
  * ``make_dist_step``  — jitted k-iteration step (dry-run / roofline unit)
  * ``solve_dist``      — full solver: pad, shard, engine loop with KKT
                          checks + adaptive restarts, unscale.
  * ``solve_dist_auto`` — ``solve_dist`` over the cluster-global mesh
                          (``runtime.cluster`` + ``make_cluster_mesh``):
                          multi-process deployments shard_map over ALL
                          pods' devices; single-process falls back to
                          the local mesh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import engine
from ..core import pdhg as pdhg_mod
from ..core.pdhg import PDHGOptions, PDHGResult
from ..core.residuals import kkt_residuals
from ..core.symblock import build_sym_block, dot, mv
from ..lp.problem import StandardLP
from .sharding import axis_size, col_axes, pad_to_multiple, row_axes


def _l2sq(v):
    return jnp.sum(v * v)


def _dist_kkt_max(x, x_prev, y, c, b, Kx, KTy, lb, ub, Rax, Cax):
    """max KKT residual, computed from local blocks + scalar psums.

    x-like vectors are sharded over Cax, y-like over Rax.  Every sum is a
    local reduction followed by a psum over the owning axis, so the result
    is identical on all devices (drives collective-free control flow).
    """
    sum_c = lambda v: jax.lax.psum(v, Cax)      # noqa: E731
    sum_r = lambda v: jax.lax.psum(v, Rax)      # noqa: E731
    reduced = c - KTy
    has_lb = jnp.isfinite(lb)
    has_ub = jnp.isfinite(ub)
    lam_lo = jnp.where(has_lb, jnp.maximum(reduced, 0.0), 0.0)
    lam_hi = jnp.where(has_ub, jnp.maximum(-reduced, 0.0), 0.0)
    lam = lam_lo - lam_hi
    nrm_b = jnp.sqrt(sum_r(_l2sq(b)))
    nrm_c = jnp.sqrt(sum_c(_l2sq(c)))
    r_pri = jnp.sqrt(sum_r(_l2sq(Kx - b))) / (1.0 + nrm_b)
    r_dual = jnp.sqrt(sum_c(_l2sq(reduced - lam))) / (1.0 + nrm_c)
    r_iter = jnp.sqrt(sum_c(_l2sq(jnp.maximum(x_prev - x, 0.0)))) / (
        1.0 + jnp.sqrt(sum_c(_l2sq(x))))
    pobj = sum_c(dot(c, x))
    dobj = sum_r(dot(b, y)) + sum_c(
        dot(jnp.where(has_lb, lb, 0.0), lam_lo)
        - dot(jnp.where(has_ub, ub, 0.0), lam_hi))
    r_gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
    return jnp.maximum(jnp.maximum(r_pri, r_dual), jnp.maximum(r_iter, r_gap))


@dataclasses.dataclass
class DistProblem:
    """Padded + device-laid-out problem data (the 'encoded' state)."""

    K: jax.Array         # (m_pad, n_pad) sharded P(Rax, Cax)
    b: jax.Array         # (m_pad,)  P(Rax)
    c: jax.Array         # (n_pad,)  P(Cax)
    lb: jax.Array
    ub: jax.Array
    T: jax.Array
    Sigma: jax.Array
    m: int               # original dims
    n: int
    mesh: Mesh


def shard_problem(scaled, T, Sigma, mesh: Mesh,
                  tile_dtype=None) -> DistProblem:
    """Pad to mesh multiples and place blocks (the encode-once step).

    Padding semantics: extra primal coordinates are pinned (lb=ub=0) and
    extra rows have b=0 with zero K rows, so padding never changes the
    optimum.  ``tile_dtype`` downcasts the device-resident K tiles
    (hillclimb 1: bf16 "conductances"); vectors keep the solve dtype.
    """
    Rax, Cax = row_axes(mesh), col_axes(mesh)
    R, C = axis_size(mesh, Rax), axis_size(mesh, Cax)
    m, n = scaled.K.shape
    Kp = pad_to_multiple(pad_to_multiple(scaled.K, R, 0), C, 1)
    if tile_dtype is not None:
        Kp = Kp.astype(tile_dtype)
    bp = pad_to_multiple(scaled.b, R, 0)
    cp = pad_to_multiple(scaled.c, C, 0)
    lbp = pad_to_multiple(scaled.lb, C, 0)
    ubp = pad_to_multiple(scaled.ub, C, 0)   # pad ub with 0 => pinned vars
    Tp = pad_to_multiple(T, C, 0, value=1.0)
    Sigp = pad_to_multiple(Sigma, R, 0, value=1.0)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))  # noqa: E731
    return DistProblem(
        K=put(Kp, P(Rax, Cax)),
        b=put(bp, P(Rax)),
        c=put(cp, P(Cax)),
        lb=put(lbp, P(Cax)),
        ub=put(ubp, P(Cax)),
        T=put(Tp, P(Cax)),
        Sigma=put(Sigp, P(Rax)),
        m=m, n=n, mesh=mesh,
    )


def make_dist_step(mesh: Mesh, n_inner: int = 1, gamma: float = 0.0):
    """k-iteration distributed PDHG step (the dry-run/roofline unit).

    Returns a function  (K, b, c, lb, ub, T, Sigma, x, x_bar, y, tau,
    sigma) -> (x, x_bar, y, tau, sigma)  running ``n_inner`` engine
    iterations over the psum-tiled operator backend.  State is the
    engine's carried form: ``x_bar`` is the next iteration's extrapolated
    point and ``tau``/``sigma`` already include its theta factor (with
    ``gamma=0`` — the dry-run default — these coincide with the raw step
    sizes).
    """
    Rax, Cax = row_axes(mesh), col_axes(mesh)

    def local_fn(K, b, c, lb, ub, T, Sig, x, x_bar, y, tau, sigma):
        op = engine.sharded_operator(K, Rax, Cax)
        state = engine.PDHGState(x=x, x_prev=x, x_bar=x_bar, y=y,
                                 tau=tau, sigma=sigma)
        state = jax.lax.fori_loop(
            0, n_inner,
            lambda i, s: engine.pdhg_step(op, engine.JNP_UPDATES, b, c,
                                          lb, ub, T, Sig, gamma, s),
            state)
        return state.x, state.x_bar, state.y, state.tau, state.sigma

    vec_r, vec_c = P(Rax), P(Cax)
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(Rax, Cax), vec_r, vec_c, vec_c, vec_c, vec_c, vec_r,
                  vec_c, vec_c, vec_r, P(), P()),
        out_specs=(vec_c, vec_c, vec_r, P(), P()),
        check_vma=False,
    )


def solve_dist(
    lp: StandardLP,
    mesh: Mesh,
    opts: PDHGOptions = PDHGOptions(),
    tile_dtype=None,
) -> PDHGResult:
    """Full distributed solve (host prep -> shard -> jitted while_loop)."""
    scaled, T, Sigma = pdhg_mod.prepare(lp, opts)
    if opts.norm_override is not None:
        rho = float(opts.norm_override)
    else:
        from ..core.lanczos import (
            NORM_BACKENDS, lanczos_svd_jit, power_iteration_mv)
        if opts.norm_backend not in NORM_BACKENDS:
            raise ValueError(f"unknown norm_backend {opts.norm_backend!r}; "
                             f"expected one of {NORM_BACKENDS}")
        Keff = jnp.sqrt(Sigma)[:, None] * scaled.K * jnp.sqrt(T)[None, :]
        M = build_sym_block(Keff)
        if opts.norm_backend == "power":
            rho = float(power_iteration_mv(lambda v: mv(M, v), M.shape[0],
                                           M.dtype,
                                           iters=opts.lanczos_iters))
        else:
            rho = float(lanczos_svd_jit(M, k_max=opts.lanczos_iters))
        if tile_dtype is not None:
            rho = rho / (1.0 - 0.05)   # Lemma-2 margin for tile rounding
    prob = shard_problem(scaled, T, Sigma, mesh, tile_dtype=tile_dtype)
    Rax, Cax = row_axes(mesh), col_axes(mesh)
    n_pad = prob.c.shape[0]
    m_pad = prob.b.shape[0]
    dt = prob.b.dtype   # vector dtype (tiles may be bf16)

    def local_solve(K, b, c, lb, ub, T, Sig):
        # deterministic init: every device draws the FULL vector then
        # slices its block => identical draws to the single-device solver
        # (same PRNGKey(seed+1) threading as ``solve_jit``; on an
        # unpadded 1-device mesh the iterates coincide bit-for-bit).
        key, kx, ky = jax.random.split(jax.random.PRNGKey(opts.seed + 1), 3)
        ci = jax.lax.axis_index(Cax)
        ri = jax.lax.axis_index(Rax)
        nloc, mloc = c.shape[0], b.shape[0]
        x0f = jax.random.normal(kx, (n_pad,), dt)
        y0f = jax.random.normal(ky, (m_pad,), dt)
        x0 = jnp.clip(jax.lax.dynamic_slice(x0f, (ci * nloc,), (nloc,)),
                      lb, ub)
        y0 = jax.lax.dynamic_slice(y0f, (ri * mloc,), (mloc,))
        op = engine.sharded_operator(K, Rax, Cax)

        def residual_fn(x, x_prev, y, Kx, KTy):
            return _dist_kkt_max(x, x_prev, y, c, b, Kx, KTy, lb, ub,
                                 Rax, Cax)

        # the adaptive rebalance reduces x-like vectors over the column
        # axis and y-like over the rows, exactly like the merit's norms;
        # padded coordinates are pinned (dx = dy = 0) so they never bias
        # the movement ratios
        xsum_fn = lambda v: jax.lax.psum(jnp.sum(v), Cax)   # noqa: E731
        ysum_fn = lambda v: jax.lax.psum(jnp.sum(v), Rax)   # noqa: E731

        return engine.pdhg_loop(
            op, engine.JNP_UPDATES, b, c, lb, ub, T, Sig,
            x0, y0, opts.eta / (opts.omega * rho),
            opts.eta * opts.omega / rho, key,
            max_iters=opts.max_iters, tol=opts.tol, gamma=opts.gamma,
            check_every=opts.check_every,
            restart_beta=opts.restart_beta, restart=opts.restart,
            step_rule=opts.step_rule, eta=opts.eta,
            xsum_fn=xsum_fn, ysum_fn=ysum_fn,
            residual_fn=residual_fn,
        )

    vec_r, vec_c = P(Rax), P(Cax)
    solve_fn = jax.jit(jax.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=(P(Rax, Cax), vec_r, vec_c, vec_c, vec_c, vec_c, vec_r),
        out_specs=(vec_c, vec_r, P(), P()),
        check_vma=False,
    ))
    x, y, it, merit = solve_fn(prob.K, prob.b, prob.c, prob.lb, prob.ub,
                               prob.T, prob.Sigma)
    x = np.asarray(x)[: prob.n]
    y = np.asarray(y)[: prob.m]
    x_orig = np.asarray(scaled.D2) * x
    y_orig = np.asarray(scaled.D1) * y
    # Post-hoc noiseless KKT residuals on the UNSCALED solution, one per
    # component (as every other path reports them) — the in-loop scalar
    # merit only drives the status and ``result.merit``; stuffing it into
    # all four fields made ``residuals.as_dict()`` claim
    # r_pri == r_dual == r_iter == r_gap.
    res_obj = kkt_residuals(
        jnp.asarray(x_orig), jnp.asarray(x_orig), jnp.asarray(y_orig),
        jnp.asarray(lp.c), jnp.asarray(lp.b),
        jnp.asarray(lp.K @ x_orig), jnp.asarray(lp.K.T @ y_orig),
        lb=jnp.asarray(lp.lb), ub=jnp.asarray(lp.ub))
    it_i = int(it)
    lanczos_mvms = 0 if opts.norm_override is not None else opts.lanczos_iters
    merit_f = float(merit)
    if not np.isfinite(merit_f):
        status = "diverged"          # NaN exits the loop; report it truly
    elif merit_f <= opts.tol:
        status = "optimal"
    else:
        status = "iteration_limit"
    return PDHGResult(
        status=status,
        x=x_orig, y=y_orig, obj=float(lp.c @ x_orig),
        iterations=it_i, residuals=res_obj, sigma_max=rho,
        lanczos_iters=lanczos_mvms,
        mvm_calls=engine.mvm_accounting(it_i, opts.check_every,
                                        lanczos_mvms,
                                        restart=opts.restart),
        merit=merit_f,
    )


def solve_dist_auto(
    lp: StandardLP,
    opts: PDHGOptions = PDHGOptions(),
    cluster: str = "auto",
    tile_dtype=None,
) -> PDHGResult:
    """``solve_dist`` over the process-spanning global mesh.

    Brings the cluster up through ``runtime.cluster.init_cluster``
    (env-driven, idempotent, single-process fallback) and shard_maps
    over ``make_cluster_mesh()`` — in a multi-process deployment the
    pod axis is one process per pod and every psum crosses the
    interconnect; single-process this degrades to the local-devices
    mesh, so every existing entry point keeps working unchanged.
    """
    from ..runtime import cluster as cluster_mod
    from ..runtime.mesh import make_cluster_mesh, make_local_mesh

    info = cluster_mod.init_cluster(cluster)
    mesh = make_cluster_mesh() if info.is_multiprocess else make_local_mesh()
    return solve_dist(lp, mesh, opts, tile_dtype=tile_dtype)

"""Mixed-precision iterative refinement for the crossbar PDHG solve.

After Le Gallo et al., "Mixed-Precision In-Memory Computing"
(arXiv 1701.04279): the analog crossbar solves fast but only down to its
read-noise floor; a digital outer loop recovers full-precision answers by
repeatedly solving the RESIDUAL-CORRECTION problem on the *same
programmed conductances*.  For the LP saddle point

    min_x max_y  c'x + y'(b - Kx),   lb <= x <= ub,

substituting x = x_bar + dx, y = y_bar + dy gives (up to a constant) the
correction saddle

    min_dx max_dy  (c - K'y_bar)'dx + dy'((b - K x_bar) - K dx),
    lb - x_bar <= dx <= ub - x_bar,

i.e. the SAME operator K with shifted b/c and a shifted box — nothing is
ever reprogrammed, which is the paper's core constraint (writes are the
expensive phase; the ledger across refinement rounds shows zero
additional write cycles).  Each round:

  1. DIGITAL: compute exact residuals r_b = b - Kx, r_c = c - K'y against
     the full-precision operator (the digital co-processor's job — these
     MVMs are counted via ``engine.refine_digital_mvms`` but never
     charged to the crossbar read ledger).
  2. Scale the correction problem to unit size (s = max residual norm).
     Analog read noise is RELATIVE, so re-solving the residual system at
     its own scale is what gains digits: the absolute noise floor
     shrinks proportionally to the residual each round.
  3. ANALOG: solve the correction LP through ``engine.solve_core`` on
     the programmed operator, warm-started at dx = dy = 0 (the previous
     outer iterate IS the origin in shifted coordinates).  Every inner
     MVM is an analog read, charged to the ledger like any other solve.
  4. DIGITAL: evaluate the candidate x + s*dx, y + s*dy exactly and
     adopt it only if it improves the exact KKT merit (safeguarded
     refinement — a noisy correction can regress, and once the merit is
     at ``refine_tol`` further corrections would only pump read noise
     back in).

``refined_core`` is the traced shell (vmappable — the batched crossbar
pipeline runs it per lane); ``solve_crossbar_refined`` is the eager
single-instance driver with the energy ledger, dispatched from
``solve_crossbar_jit`` when ``opts.refine_rounds > 0``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import engine
from ..core import pdhg as pdhg_mod
from ..core.lanczos import lanczos_svd_jit, power_iteration_mv
from ..core.residuals import kkt_residuals
from ..core.symblock import build_sym_block, mv
from ..lp.problem import StandardLP
from .device import DeviceModel, EPIRAM
from .encode import encode_matrix
from .energy import Ledger

#: guard for an exactly-zero residual (already converged): the correction
#: problem degenerates and the scale must not divide by zero
_TINY = 1e-300


def digital_merit(x, y, b, c, lb, ub, Kx, KTy):
    """Exact KKT merit from full-precision operator images."""
    return kkt_residuals(x, x, y, c, b, Kx, KTy, lb=lb, ub=ub).max


def refined_core(K_dig_fwd, K_dig_adj, K_fwd, K_adj, b, c, lb, ub, T,
                 Sigma, rho, key, static, *,
                 operator: Optional[engine.Operator] = None):
    """Digital-outer / analog-inner refinement shell (traced, vmappable).

    ``K_dig_fwd``/``K_dig_adj`` are the EXACT (full-precision) scaled
    operator blocks used only for the digital residual/merit MVMs;
    ``K_fwd``/``K_adj`` (or ``operator``) is the programmed analog
    operator every inner solve runs on — identical in every round, never
    rewritten.  ``static`` is the ``engine.SolveStatic`` from
    ``pdhg.opts_static``: its ``refine_rounds`` and ``refine_tol`` drive
    the shell, and it is passed whole into ``engine.solve_core``.

    Returns ``(x, y, its, merit)`` where ``its`` is the per-round
    iteration-count vector (length ``refine_rounds + 1``; callers charge
    each round's analog windows to the read ledger) and ``merit`` is the
    exact digital KKT merit after refinement (``refine_rounds == 0``
    degenerates to ``solve_core`` with its in-loop analog merit).
    """
    rounds, refine_tol = static.refine_rounds, static.refine_tol

    x, y, it0, merit0 = engine.solve_core(
        K_fwd, K_adj, b, c, lb, ub, T, Sigma, rho, key, static,
        operator=operator)
    if rounds == 0:
        return x, y, jnp.reshape(it0, (1,)), merit0

    its = [it0]
    with jax.named_scope(engine.REFINE_SCOPE):
        Kx = mv(K_dig_fwd, x)
        KTy = mv(K_dig_adj, y)
        merit = digital_merit(x, y, b, c, lb, ub, Kx, KTy)
    for _ in range(rounds):
        with jax.named_scope(engine.REFINE_SCOPE):
            key, kr = jax.random.split(key)
            rb = b - Kx
            rc = c - KTy
            # unit-scale the correction problem: relative analog noise
            # means the absolute error floor of the inner solve tracks s
            # downward
            s = jnp.maximum(jnp.maximum(jnp.max(jnp.abs(rb)),
                                        jnp.max(jnp.abs(rc))),
                            jnp.asarray(_TINY, b.dtype))
            b_r, c_r, lb_r, ub_r = rb / s, rc / s, (lb - x) / s, (ub - x) / s
        dx, dy, it_r, _ = engine.solve_core(
            K_fwd, K_adj, b_r, c_r, lb_r, ub_r, T, Sigma, rho, kr, static,
            operator=operator, x0=jnp.zeros_like(x), y0=jnp.zeros_like(y))
        its.append(it_r)
        with jax.named_scope(engine.REFINE_SCOPE):
            x_c = jnp.clip(x + s * dx, lb, ub)
            y_c = y + s * dy
            Kx_c = mv(K_dig_fwd, x_c)
            KTy_c = mv(K_dig_adj, y_c)
            merit_c = digital_merit(x_c, y_c, b, c, lb, ub, Kx_c, KTy_c)
            # safeguarded adoption: only keep an exact improvement, and
            # stop moving once the target tolerance is met
            adopt = jnp.logical_and(merit_c < merit, merit > refine_tol)
            x, y = jnp.where(adopt, x_c, x), jnp.where(adopt, y_c, y)
            Kx = jnp.where(adopt, Kx_c, Kx)
            KTy = jnp.where(adopt, KTy_c, KTy)
            merit = jnp.where(adopt, merit_c, merit)
    return x, y, jnp.stack(its), merit


# module-level jit so repeated eager-driver calls share the executable
# cache (a per-call jax.jit wrapper would recompile every solve)
_refined_core_jit = jax.jit(refined_core, static_argnums=(12,))


def solve_crossbar_refined(
    lp: StandardLP,
    opts: pdhg_mod.PDHGOptions,
    device: DeviceModel = EPIRAM,
    key: Optional[jax.Array] = None,
    ledger: Optional[Ledger] = None,
):
    """Eager driver: encode once, then the refined solve with the ledger.

    Mirrors ``solve_crossbar_jit`` (one encode of the symmetric block M,
    charged as WRITE) but runs ``refined_core`` instead of a single
    solve: the write ledger is touched exactly once — refinement rounds
    add only READ windows (plus uncharged digital residual MVMs, counted
    on the report as ``digital_mvms``).  Returns a
    ``CrossbarSolveReport``.
    """
    from .solver import (  # deferred cycle
        CrossbarSolveReport,
        _charge_reads,
        answer_status,
    )

    if key is None:
        key = jax.random.PRNGKey(opts.seed)
    ledger = ledger if ledger is not None else Ledger()

    scaled, T, Sigma = pdhg_mod.prepare(lp, opts)
    m, n = scaled.K.shape
    M = build_sym_block(scaled.K)
    enc = encode_matrix(M, device, key, ledger=ledger)
    M_prog = enc.decode()
    K_fwd = M_prog[:m, m:]
    K_adj = M_prog[m:, :m]

    if opts.norm_override is not None:
        rho = jnp.asarray(opts.norm_override, scaled.K.dtype)
        lanczos_mvms = 0
    else:
        Keff = (jnp.sqrt(Sigma)[:, None] * K_fwd * jnp.sqrt(T)[None, :])
        Msym = build_sym_block(Keff)
        if opts.norm_backend == "power":
            est = power_iteration_mv(lambda v: mv(Msym, v), Msym.shape[0],
                                     Msym.dtype, iters=opts.lanczos_iters)
        else:
            est = lanczos_svd_jit(Msym, k_max=opts.lanczos_iters)
        rho = engine.lemma2_margin(est, device.sigma_read)
        lanczos_mvms = opts.lanczos_iters

    static = pdhg_mod.opts_static(opts, device.sigma_read)
    x, y, its, merit = _refined_core_jit(
        scaled.K, scaled.K.T, K_fwd, K_adj, scaled.b, scaled.c, scaled.lb,
        scaled.ub, T, Sigma, rho, jax.random.PRNGKey(opts.seed + 1),
        static)

    its_np = np.asarray(its)
    pdhg_mvms = int(sum(
        engine.mvm_accounting(int(i), opts.check_every, 0,
                              restart=opts.restart)
        for i in its_np))
    _charge_reads(ledger, device, lanczos_mvms + pdhg_mvms,
                  enc.active_cells)

    x_orig = np.asarray(scaled.unscale_x(x))
    y_orig = np.asarray(scaled.unscale_y(y))
    res = kkt_residuals(
        x, x, y, scaled.c, scaled.b, mv(scaled.K, x), mv(scaled.K.T, y),
        lb=scaled.lb, ub=scaled.ub)
    merit_f = float(merit)
    it_total = int(its_np.sum())
    result = pdhg_mod.PDHGResult(
        status=answer_status(merit_f, opts), x=x_orig, y=y_orig,
        obj=float(lp.c @ x_orig), iterations=it_total, residuals=res,
        sigma_max=float(rho), lanczos_iters=lanczos_mvms,
        mvm_calls=lanczos_mvms + pdhg_mvms, merit=merit_f,
    )
    return CrossbarSolveReport(
        result=result, ledger=ledger, device=device,
        lanczos_mvms=lanczos_mvms, pdhg_mvms=pdhg_mvms,
        executed_iterations=it_total,
        digital_mvms=engine.refine_digital_mvms(opts.refine_rounds),
    )

"""One PDHG iteration engine with pluggable operator / update backends.

The paper's core claim is that an enhanced-PDHG iteration reduces to two
device MVMs plus cheap vector algebra.  This module is the SINGLE home of
that half-iteration — extrapolation, dual MVM+update, primal MVM+update,
and the check-interval residual/restart block — shared verbatim by every
solver path in the repo:

    core.pdhg.solve        host loop      accel_operator   (Accel handles)
    core.pdhg.solve_jit    while_loop     dense_operator
    runtime.batch          vmapped        dense_operator
    crossbar.solver        vmapped        dense_operator | crossbar_operator
    distributed.pdhg_dist  shard_map      sharded_operator

Two orthogonal backend axes parameterize the engine:

  * **operator backend** (``Operator``): where the two device MVMs run —
    dense ``jnp`` matmuls with optional multiplicative read noise, sparse
    BCOO/BCSR contractions over the stored nonzeros (same noise hooks;
    the paper-scale sparse workload class), the differential-pair Pallas
    crossbar kernel (``kernels.ops.crossbar_mvm`` against the single
    programmed symmetric block M), a shard_map psum-tiled operator over
    a device mesh, or a host-side ``Accel`` handle (crossbar simulation
    with an energy ledger).
  * **update backend** (``Updates``): how the proximal vector algebra
    runs — reference ``jnp`` (one expression per update) or the fused
    Pallas kernels (``kernels.ops.primal_update`` / ``dual_update``, one
    VMEM pass per vector), selected by ``PDHGOptions.kernel`` with
    interpret-mode auto-detection from ``kernels.interpret``.

Iteration state is carried in the *pre-extrapolated* form: ``x_bar`` for
iteration k is computed at the END of iteration k-1 (fused into the
primal update — exactly what the Pallas kernel emits), and ``tau/sigma``
already include iteration k's deterministic-adaptation factor theta_k.
This is algebraically identical to Algorithm 4's ordering: theta_{k}
depends only on tau_{k-1}, which is known when iteration k-1 retires.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .residuals import kkt_residuals
from .symblock import MODE_AX, MODE_ATY, MVM_PRECISION, matmul_accel, mv

KERNELS = ("jnp", "pallas")
SPARSE_KERNELS = ("ell", "bcoo")
STEP_RULES = ("fixed", "adaptive", "strongly_convex")

# Adaptive step-rule tuning (``step_rule="adaptive"``): log-space
# smoothing weight for the PDLP primal-weight updates, and the trust
# region confining the weight around its data-driven initial value.
# Rebalancing happens ONLY at check boundaries (weight moves at restart
# events, the down-only scale safeguard at every boundary), so within a
# ``check_every`` window tau/sigma are constants and the fused
# megakernel window stays a single launch.  The step-scale product
# sqrt(tau*sigma) is never grown past the global-norm value: for the
# bilinear saddle dynamics tau*sigma*rho^2 <= eta^2 is NECESSARY (the
# dual is unconstrained, so overshoot diverges along the top singular
# pair) — adaptivity lives entirely in the primal/dual SPLIT of the
# budget plus the downside safeguard.
ADAPT_SMOOTH = 0.5         # exp(s*log(target) + (1-s)*log(old))
ADAPT_OMEGA_CLIP = 1024.0  # omega confined to [omega0/1024, omega0*1024]
_ADAPT_TINY = 1e-30        # degenerate-movement / div-by-zero guard


# ---------------------------------------------------------------- state ---

class PDHGState(NamedTuple):
    """Carried PDHG iterate (a pytree; safe in lax loops and shard_map).

    ``tau``/``sigma`` are the CURRENT iteration's step sizes (theta_k
    already applied); ``x_bar`` is the current iteration's extrapolated
    point; ``x_prev`` feeds the r_iter residual at check time.
    """

    x: jax.Array
    x_prev: jax.Array
    x_bar: jax.Array
    y: jax.Array
    tau: jax.Array
    sigma: jax.Array


class Operator(NamedTuple):
    """The two device MVMs of one iteration.  ``fwd(v, key) ~ K v`` (dual
    step), ``adj(v, key) ~ K^T v`` (primal step); ``key`` seeds per-MVM
    read noise and may be ``None`` on noiseless backends.

    ``fuse(state, n_steps) -> (state', x_sum, y_sum)`` is the optional
    megakernel hook: one launch running ``n_steps`` full PDHG half-steps
    (the check-interval fusion window) and returning the new state plus
    the window's ergodic sums.  ``pdhg_loop`` uses it in place of the
    per-step ``fori_loop`` when present; only noiseless backends mount
    it (no per-MVM keys can be split inside the kernel)."""

    fwd: Callable
    adj: Callable
    name: str = "dense"
    fuse: Optional[Callable] = None


class Updates(NamedTuple):
    """The proximal vector algebra of one iteration.

    primal(x, kty, c, T, lb, ub, tau, theta) -> (x_new, x_bar_next)
    dual(y, kxbar, b, Sigma, sigma)          -> y_new
    """

    primal: Callable
    dual: Callable
    name: str = "jnp"


# ---------------------------------------------------- operator backends ---

def _read_noise(w, key, sigma_read):
    """Multiplicative cycle-to-cycle read noise, truncated at 4 sigma so
    Assumption 3 (bounded perturbation) holds exactly."""
    g = jnp.clip(jax.random.normal(key, w.shape, w.dtype), -4.0, 4.0)
    return w * (1.0 + sigma_read * g)


def dense_operator(K_fwd, K_adj, sigma_read: float = 0.0) -> Operator:
    """Dense jnp backend.  On an ideal device ``K_adj == K_fwd.T``; on a
    programmed crossbar the two blocks of M are physically distinct cells
    and carry independent programming error."""

    def fwd(v, key=None):
        w = mv(K_fwd, v)
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    def adj(v, key=None):
        w = mv(K_adj, v)
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    return Operator(fwd, adj, "dense")


def sparse_operator(K_sp, sigma_read: float = 0.0) -> Operator:
    """Sparse jnp backend over a ``jax.experimental.sparse`` matrix
    (BCOO or BCSR): the two MVMs contract only the stored nonzeros, so
    paper-scale sparse LPs never materialize a dense K on device.  The
    read-noise hook matches ``dense_operator`` exactly — a crossbar only
    programs the nonzero conductances, and cycle-to-cycle noise rides on
    the accumulated currents either way.

    The adjoint is a transpose VIEW taken once at trace time (BCSR drops
    to BCOO for it — BCSR has no native transpose); no index shuffling
    happens inside the iteration.
    """
    from jax.experimental import sparse as jsparse  # deferred

    K_adj = (K_sp.to_bcoo() if isinstance(K_sp, jsparse.BCSR) else K_sp).T

    def fwd(v, key=None):
        w = K_sp @ v
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    def adj(v, key=None):
        w = K_adj @ v
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    return Operator(fwd, adj, "sparse")


def sparse_ell_operator(data_f, cols_f, data_a, cols_a,
                        sigma_read: float = 0.0) -> Operator:
    """ELL backend (``kernels.sparse_mvm``): the forward MVM contracts
    the ELL form of K (data_f/cols_f, (m, Wf)), the adjoint a separately
    stored ELL of K^T (data_a/cols_a, (n, Wa)) — both are XLA gathers +
    axis-1 reductions, no scatter anywhere in the iteration.  The
    read-noise hook matches ``dense_operator`` exactly."""
    from ..kernels import sparse_mvm as _ell  # deferred: keep core light

    def fwd(v, key=None):
        w = _ell.ell_matvec(data_f, cols_f, v)
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    def adj(v, key=None):
        w = _ell.ell_matvec(data_a, cols_a, v)
        if sigma_read > 0.0:
            w = _read_noise(w, key, sigma_read)
        return w

    return Operator(fwd, adj, "sparse_ell")


def accel_operator(accel) -> Operator:
    """Host-loop backend over an encoded ``symblock.Accel`` handle (MVM
    stats feed the energy ledger; the backend brings its own physics)."""

    def fwd(v, key=None):
        return matmul_accel(accel, v, MODE_AX, key=key)

    def adj(v, key=None):
        return matmul_accel(accel, v, MODE_ATY, key=key)

    return Operator(fwd, adj, f"accel({accel.name})")


def crossbar_operator(g_pos, g_neg, scale, m: int, n: int,
                      sigma_read: float = 0.0, interpret=None) -> Operator:
    """Differential-pair Pallas backend against the SINGLE programmed
    symmetric block M (Algorithm 2): both MVM modes are zero-padded reads
    of the same (R, C) conductance array, exactly the paper's access
    pattern.  Read noise is a per-row multiplicative sample folded into
    the kernel's output gain.  A compiled kernel refuses f64 conductances
    here, at mount time."""
    from ..kernels import ops  # deferred: keep core import-light
    from ..kernels.interpret import check_kernel_dtype

    check_kernel_dtype(g_pos.dtype, interpret, "the crossbar MVM kernel")
    R, C = g_pos.shape

    def _mvm(v_full, key):
        if sigma_read > 0.0:
            noise = sigma_read * jnp.clip(
                jax.random.normal(key, (R,), v_full.dtype), -4.0, 4.0)
        else:
            noise = jnp.zeros((R,), v_full.dtype)
        return ops.crossbar_mvm(g_pos, g_neg, v_full, scale, noise,
                                interpret=interpret)

    def fwd(x, key=None):
        v = jnp.zeros((C,), x.dtype).at[m:m + n].set(x)
        return _mvm(v, key)[:m]

    def adj(y, key=None):
        v = jnp.zeros((C,), y.dtype).at[:m].set(y)
        return _mvm(v, key)[m:m + n]

    return Operator(fwd, adj, "crossbar")


def sharded_operator(K_loc, row_axis, col_axis) -> Operator:
    """shard_map psum-tiled backend: each device owns a static (m_loc,
    n_loc) tile of K; ``fwd`` psums partial products over the column
    axis ("sum the currents along a crossbar grid row"), ``adj`` over the
    row axes.  Tiles may be a narrower dtype than the vectors (bf16
    "conductances"); accumulation is at least f32 and never *below* the
    tile dtype (f64 tiles accumulate in f64)."""
    acc_dt = jnp.promote_types(K_loc.dtype, jnp.float32)

    def fwd(v, key=None):
        w = jax.lax.dot_general(
            K_loc, v.astype(K_loc.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            precision=MVM_PRECISION, preferred_element_type=acc_dt,
        )
        return jax.lax.psum(w.astype(v.dtype), col_axis)

    def adj(v, key=None):
        w = jax.lax.dot_general(
            K_loc, v.astype(K_loc.dtype),
            dimension_numbers=(((0,), (0,)), ((), ())),
            precision=MVM_PRECISION, preferred_element_type=acc_dt,
        )
        return jax.lax.psum(w.astype(v.dtype), row_axis)

    return Operator(fwd, adj, "sharded")


# ------------------------------------------------- megakernel (fused) ---

def make_fused_dense(K_fwd, K_adj, b, c, lb, ub, T, Sigma, gamma,
                     interpret=None) -> Callable:
    """``Operator.fuse`` hook for the dense backend: one
    ``kernels.pdhg_megakernel`` launch per check-interval window.
    Noiseless only — the caller guarantees ``sigma_read == 0``."""
    from ..kernels import pdhg_megakernel as _mega  # deferred

    def fuse(state: PDHGState, n_steps: int):
        (x, x_prev, x_bar, y, tau, sigma, xs, ys) = _mega.fused_dense_steps(
            K_fwd, K_adj, b, c, lb, ub, T, Sigma,
            state.x, state.x_prev, state.x_bar, state.y,
            state.tau, state.sigma,
            n_steps=int(n_steps), gamma=float(gamma), interpret=interpret)
        return (PDHGState(x=x, x_prev=x_prev, x_bar=x_bar, y=y,
                          tau=tau, sigma=sigma), xs, ys)

    return fuse


# ------------------------------------------------------ update backends ---

def _primal_jnp(x, kty, c, T, lb, ub, tau, theta):
    x_new = jnp.clip(x - tau * T * (c - kty), lb, ub)
    return x_new, x_new + theta * (x_new - x)


def _dual_jnp(y, kxbar, b, Sigma, sigma):
    return y + sigma * Sigma * (b - kxbar)


JNP_UPDATES = Updates(_primal_jnp, _dual_jnp, "jnp")


def make_updates(kernel: str, dtype, interpret=None) -> Updates:
    """Update-backend factory keyed by ``PDHGOptions.kernel``.

    ``interpret=None`` auto-detects per ``kernels.interpret``
    (interpreted on CPU, compiled Mosaic on real TPU).  ``dtype`` is the
    solve dtype: compiled kernels refuse a 64-bit one here."""
    if kernel == "jnp":
        return JNP_UPDATES
    if kernel == "pallas":
        from ..kernels import ops  # deferred: keep core import-light
        from ..kernels.interpret import check_kernel_dtype

        check_kernel_dtype(dtype, interpret, "the fused update kernel")

        def primal(x, kty, c, T, lb, ub, tau, theta):
            return ops.primal_update(x, kty, c, T, lb, ub, tau, theta,
                                     interpret=interpret)

        def dual(y, kxbar, b, Sigma, sigma):
            return ops.dual_update(y, kxbar, b, Sigma, sigma,
                                   interpret=interpret)

        return Updates(primal, dual, "pallas")
    raise ValueError(f"unknown update kernel {kernel!r}; expected "
                     f"{KERNELS}")


# ------------------------------------------------------------ iteration ---

def init_state(x0, y0, tau0, sigma0, gamma) -> PDHGState:
    """Enter engine state: apply iteration 1's theta to (tau0, sigma0)
    and seed the extrapolation at x_bar_1 = x0 (x_prev = x0)."""
    tau0 = jnp.asarray(tau0, x0.dtype)
    sigma0 = jnp.asarray(sigma0, x0.dtype)
    theta1 = 1.0 / jnp.sqrt(1.0 + 2.0 * gamma * tau0)
    return PDHGState(x=x0, x_prev=x0, x_bar=x0, y=y0,
                     tau=theta1 * tau0, sigma=sigma0 / theta1)


def pdhg_step(op: Operator, upd: Updates, b, c, lb, ub, T, Sigma, gamma,
              state: PDHGState, k1=None, k2=None) -> PDHGState:
    """ONE enhanced-PDHG iteration (paper Algorithm 4, eq. 7 signs).

        y_{k+1} = y_k + sigma_k Sigma (b - K x_bar_k)        # device MVM 1
        x_{k+1} = proj(x_k - tau_k T (c - K^T y_{k+1}))      # device MVM 2
        theta_{k+1} = 1/sqrt(1 + 2 gamma tau_k)
        x_bar_{k+1} = x_{k+1} + theta_{k+1} (x_{k+1} - x_k)  # fused above
        tau_{k+1} = theta_{k+1} tau_k; sigma_{k+1} = sigma_k / theta_{k+1}

    ``k1``/``k2`` seed the two MVMs' read noise (``None`` on noiseless
    backends).  All step math lives HERE — no caller re-implements it.
    """
    Kxbar = op.fwd(state.x_bar, k1)
    y_n = upd.dual(state.y, Kxbar, b, Sigma, state.sigma)
    KTy = op.adj(y_n, k2)
    theta_n = 1.0 / jnp.sqrt(1.0 + 2.0 * gamma * state.tau)
    x_n, x_bar_n = upd.primal(state.x, KTy, c, T, lb, ub, state.tau, theta_n)
    return PDHGState(x=x_n, x_prev=state.x, x_bar=x_bar_n, y=y_n,
                     tau=theta_n * state.tau, sigma=state.sigma / theta_n)


def restart_state(state: PDHGState, x_new, y_new) -> PDHGState:
    """Adopt a restart point: x = x_prev = x_bar = x_new (momentum reset),
    keeping the tau/sigma schedule running."""
    return state._replace(x=x_new, x_prev=x_new, x_bar=x_new, y=y_new)


def adaptive_omega_init(tau0, sigma0, b, c, T, Sigma,
                        xsum=jnp.sum, ysum=jnp.sum):
    """Data-driven primal-weight initialization (the PDLP heuristic in
    the preconditioned metric): scale the primal weight
    ``omega = sqrt(sigma/tau)`` by ``sqrt(|T^1/2 c| / |Sigma^1/2 b|)``,
    the expected dual/primal movement ratio of the very first iterations
    (the dual residual is driven by ``Sigma^1/2 b``, the primal one by
    ``T^1/2 c``).  On scale-imbalanced instances — objective and rhs in
    mismatched units, which Ruiz equilibration of K cannot see — this
    alone recovers most of the adaptive win.  Composes with the user's
    ``opts.omega`` (multiplies it).  ``xsum``/``ysum`` reduce
    primal/dual vectors (the distributed path passes psum wrappers, so
    every shard derives the same global weight)."""
    dt = b.dtype
    tiny = jnp.asarray(_ADAPT_TINY, dt)
    nc2 = xsum(T * c * c)
    nb2 = ysum(Sigma * b * b)
    w = (jnp.maximum(nc2, tiny) / jnp.maximum(nb2, tiny)) ** 0.25
    w = jnp.clip(w, 1.0 / ADAPT_OMEGA_CLIP, ADAPT_OMEGA_CLIP)
    ok = jnp.logical_and(nc2 > tiny, nb2 > tiny)
    w = jnp.where(jnp.logical_and(ok, jnp.isfinite(w)), w, 1.0)
    return tau0 / w, sigma0 * w


def adaptive_shrink(tau, sigma, eta, dx, dy, Kdx, KTdy, T, Sigma, ok,
                    xsum=jnp.sum, ysum=jnp.sum):
    """Down-only local step-scale safeguard for ``step_rule="adaptive"``
    (Malitsky–Pock-flavored, backtracking free), applied at every check
    boundary with zero extra MVMs (``Kdx``/``KTdy`` come from the check
    MVMs by linearity: ``K dx = K x_new - K x_old``).

    The Rayleigh quotient along the window's movement,
    ``rho_loc^2 = (|S^1/2 K dx|^2 + |T^1/2 K^T dy|^2)
                  / (|T^-1/2 dx|^2 + |S^-1/2 dy|^2)``,
    is a LOWER bound on the true preconditioned operator norm — so
    whenever ``sqrt(tau*sigma) * rho_loc > eta`` the Lemma 2 coupling is
    provably violated (the Lanczos/power estimate was too small, e.g.
    few iterations or heavy read noise) and the scale is shrunk to
    ``eta / rho_loc``.  The product is NEVER grown: for the bilinear
    saddle dynamics ``tau*sigma*rho^2 <= 1`` is necessary, not just
    sufficient — any sustained overshoot diverges along the top singular
    pair, so there is no safe upside, only this downside protection.
    Identity when the estimate was sound.  Gated by ``ok`` (a valid
    previous boundary exists) and finiteness.
    """
    dt = dx.dtype
    tiny = jnp.asarray(_ADAPT_TINY, dt)
    ndx2 = xsum(dx * dx / T)
    ndy2 = ysum(dy * dy / Sigma)
    nK2 = ysum(Sigma * Kdx * Kdx) + xsum(T * KTdy * KTdy)
    mv2 = ndx2 + ndy2
    rho_loc = jnp.sqrt(nK2 / jnp.maximum(mv2, tiny))
    g = jnp.sqrt(tau * sigma)
    s = jnp.minimum(jnp.asarray(1.0, dt),
                    jnp.asarray(eta, dt) / jnp.maximum(rho_loc * g, tiny))
    ok = jnp.logical_and(ok, jnp.logical_and(mv2 > tiny, jnp.isfinite(s)))
    s = jnp.where(ok, s, 1.0)
    return tau * s, sigma * s


def adaptive_omega_update(tau, sigma, dx, dy, T, Sigma, w_lo, w_hi, ok,
                          xsum=jnp.sum, ysum=jnp.sum):
    """PDLP primal-weight rebalancing, applied at RESTART events only
    (restarts land on check boundaries, so the fused window stays one
    launch).  ``dx``/``dy`` are the movement since the previous restart
    anchor; the weight ``omega = sqrt(sigma/tau)`` is pulled toward the
    dual/primal movement ratio ``|dy|_S^-1/2 / |dx|_T^-1/2`` with
    PDLP's log-space smoothing (``ADAPT_SMOOTH``) and clipped to
    ``[w_lo, w_hi]`` (a trust region around the initial weight).
    Restart cadence matters: at raw window cadence the ratio chases its
    own effect (a bigger sigma moves the dual more, which asks for a
    bigger sigma — positive feedback); between restarts the movement
    reflects genuine progress scale.  The product tau*sigma (the Lemma 2
    budget) is preserved exactly."""
    dt = dx.dtype
    tiny = jnp.asarray(_ADAPT_TINY, dt)
    ndx2 = xsum(dx * dx / T)
    ndy2 = ysum(dy * dy / Sigma)
    ok = jnp.logical_and(ok, jnp.logical_and(ndx2 > tiny, ndy2 > tiny))
    w_old = jnp.sqrt(sigma / tau)
    ratio = jnp.sqrt(ndy2 / jnp.maximum(ndx2, tiny))
    w_new = jnp.exp(ADAPT_SMOOTH * jnp.log(jnp.maximum(ratio, tiny))
                    + (1.0 - ADAPT_SMOOTH) * jnp.log(
                        jnp.maximum(w_old, tiny)))
    w_new = jnp.clip(w_new, w_lo, w_hi)
    g = jnp.sqrt(tau * sigma)
    ok = jnp.logical_and(ok, jnp.isfinite(w_new))
    return (jnp.where(ok, g / w_new, tau),
            jnp.where(ok, g * w_new, sigma))


# ----------------------------------------------------------------- loop ---

def draw_init(key, m: int, n: int, lb, ub, dtype):
    """Paper's projected-Gaussian start; returns (key', x0, y0).  Every
    jitted path draws through here so backends share inits bit-for-bit."""
    key, kx, ky = jax.random.split(key, 3)
    x0 = jnp.clip(jax.random.normal(kx, (n,), dtype), lb, ub)
    y0 = jax.random.normal(ky, (m,), dtype)
    return key, x0, y0


def pdhg_loop(op: Operator, upd: Updates, b, c, lb, ub, T, Sigma,
              x0, y0, tau0, sigma0, key, *,
              max_iters: int, tol: float, gamma: float, check_every: int,
              restart_beta: float, restart: bool = True,
              step_rule: str = "fixed", eta: float = 0.95,
              xsum_fn: Optional[Callable] = None,
              ysum_fn: Optional[Callable] = None,
              residual_fn: Optional[Callable] = None):
    """The jitted solve loop every non-host path runs: ``check_every``
    fused iterations per ``lax.while_loop`` body, then one residual check
    on the current AND ergodic-average iterates with a PDLP-style
    adaptive restart.

    Check MVMs go through the SAME (possibly noisy) operator backend as
    the solve — 4 device MVMs per check with fresh keys (k3/k4 current,
    k5/k6 averaged; reusing them would correlate read noise between the
    two residual evaluations), matching the host driver and the energy
    ledger's 4-MVMs-per-check charge.  ``restart=False`` (a STATIC
    Python bool) removes the entire averaged-iterate block from the
    trace: no ergodic-average residual MVMs (checks drop to 2 MVMs —
    ``mvm_accounting`` mirrors this) and the averaged iterate is never
    adopted.  With noiseless operators the surviving iterates are
    bit-for-bit those of ``restart_beta = 0.0`` with restarts on, minus
    that trick's reliance on ``0.0 * inf == NaN`` comparing false.

    When ``op.fuse`` is mounted (megakernel mode), each check-interval
    window runs as ONE fused launch instead of ``check_every`` stepped
    launches; the check itself stays out here, so fused and unfused
    loops visit the same check points on the same iterates.

    ``step_rule`` is a STATIC Python string (one of ``STEP_RULES``):

      * ``"fixed"`` (default) and ``"strongly_convex"`` trace the exact
        loop this function has always traced — ``"strongly_convex"`` is
        just the explicit, validated opt-in for ``gamma > 0``'s
        accelerated ``theta_k`` schedule (the theta math lives in
        ``pdhg_step`` and is carried in tau/sigma either way; with
        ``gamma == 0`` every theta is exactly 1.0 and "fixed" is
        bitwise-identical to the historical behavior).
      * ``"adaptive"`` = PDLP-style primal-weight adaptation on top of
        the same loop: (a) ``adaptive_omega_init`` rescales
        (tau0, sigma0) from the problem data before the first iterate;
        (b) ``adaptive_omega_update`` rebalances the primal weight at
        RESTART events from the movement since the previous restart
        anchor (carried in the loop state); (c) ``adaptive_shrink``
        applies a down-only step-scale safeguard at every boundary from
        the window's Rayleigh quotient (reusing the check MVMs by
        linearity — zero extra MVMs).  tau/sigma move ONLY at check
        boundaries, so the fused megakernel window is untouched and
        stays one launch.  ``eta`` is the Lemma 2 safety factor the
        safeguard enforces; ``xsum_fn``/``ysum_fn`` let the distributed
        path psum every rebalance reduction.  With ``restart=False``
        only (a) and (c) are active.

    ``residual_fn(x, x_prev, y, Kx, KTy) -> scalar merit`` defaults to
    the dense KKT residual max; the distributed path passes its
    psum-reduced variant.  Returns ``(x, y, iterations, merit)``.
    """
    if step_rule not in STEP_RULES:
        raise ValueError(f"unknown step_rule {step_rule!r}; expected one "
                         f"of {STEP_RULES}")
    adaptive = step_rule == "adaptive"
    xsum = jnp.sum if xsum_fn is None else xsum_fn
    ysum = jnp.sum if ysum_fn is None else ysum_fn
    if residual_fn is None:
        def residual_fn(x, x_prev, y, Kx, KTy):
            return kkt_residuals(x, x_prev, y, c, b, Kx, KTy,
                                 lb=lb, ub=ub).max

    dt = x0.dtype
    if adaptive:
        tau0, sigma0 = adaptive_omega_init(
            jnp.asarray(tau0, dt), jnp.asarray(sigma0, dt),
            b, c, T, Sigma, xsum, ysum)
        w0 = jnp.sqrt(sigma0 / tau0)
        w_lo = w0 / jnp.asarray(ADAPT_OMEGA_CLIP, dt)
        w_hi = w0 * jnp.asarray(ADAPT_OMEGA_CLIP, dt)
    state0 = init_state(x0, y0, tau0, sigma0, gamma)

    def half_iter(_, carry):
        state, xs, ys, cnt, rk = carry
        rk, k1, k2 = jax.random.split(rk, 3)
        state = pdhg_step(op, upd, b, c, lb, ub, T, Sigma, gamma,
                          state, k1, k2)
        return (state, xs + state.x, ys + state.y, cnt + 1.0, rk)

    def body(loop):
        if adaptive:
            (state, it, merit, xs, ys, cnt, m_restart, rk,
             ax, ay, aKx, aKTy, aok, rx, ry) = loop
        else:
            state, it, merit, xs, ys, cnt, m_restart, rk = loop
        if op.fuse is not None:
            # megakernel window: one fused launch, no per-step keys
            # (fused backends are noiseless, so none are consumed)
            state, dxs, dys = op.fuse(state, check_every)
            xs, ys = xs + dxs, ys + dys
            cnt = cnt + jnp.asarray(check_every, cnt.dtype)
        else:
            state, xs, ys, cnt, rk = jax.lax.fori_loop(
                0, check_every, half_iter, (state, xs, ys, cnt, rk))
        rk, k3, k4 = jax.random.split(rk, 3)
        Kx = op.fwd(state.x, k3)
        KTy = op.adj(state.y, k4)
        merit = residual_fn(state.x, state.x_prev, state.y, Kx, KTy)
        Kx_c, KTy_c = Kx, KTy
        if restart:
            x_avg = xs / jnp.maximum(cnt, 1.0)
            y_avg = ys / jnp.maximum(cnt, 1.0)
            rk, k5, k6 = jax.random.split(rk, 3)
            Kxa = op.fwd(x_avg, k5)
            KTya = op.adj(y_avg, k6)
            merit_avg = residual_fn(x_avg, x_avg, y_avg, Kxa, KTya)
            do_restart = merit_avg < restart_beta * m_restart
            use_avg = jnp.logical_or(
                jnp.logical_and(do_restart, merit_avg < merit),
                merit_avg <= tol,  # adopt the average if it satisfies tol
            )
            pick = lambda a, cur: jnp.where(use_avg, a, cur)  # noqa: E731
            state = state._replace(
                x=pick(x_avg, state.x), x_prev=pick(x_avg, state.x_prev),
                x_bar=pick(x_avg, state.x_bar), y=pick(y_avg, state.y))
            m_restart = jnp.where(do_restart,
                                  jnp.minimum(merit_avg, merit), m_restart)
            xs = jnp.where(do_restart, jnp.zeros_like(xs), xs)
            ys = jnp.where(do_restart, jnp.zeros_like(ys), ys)
            cnt = jnp.where(do_restart, 0.0, cnt)
            # the carried merit must be the merit of the iterate actually
            # CARRIED: min(merit, merit_avg) used to adopt the averaged
            # iterate's (lower) merit even when the state kept the
            # current iterate, so exits reported a residual the returned
            # solution does not satisfy.
            merit = jnp.where(use_avg, merit_avg, merit)
            if adaptive:
                # operator images of the iterate actually carried — by
                # linearity, no extra MVMs beyond the check's
                Kx_c, KTy_c = pick(Kxa, Kx), pick(KTya, KTy)
                tau_n, sigma_n = adaptive_omega_update(
                    state.tau, state.sigma, state.x - rx, state.y - ry,
                    T, Sigma, w_lo, w_hi, do_restart, xsum, ysum)
                state = state._replace(tau=tau_n, sigma=sigma_n)
                rx = jnp.where(do_restart, state.x, rx)
                ry = jnp.where(do_restart, state.y, ry)
        if adaptive:
            tau_n, sigma_n = adaptive_shrink(
                state.tau, state.sigma, eta,
                state.x - ax, state.y - ay, Kx_c - aKx, KTy_c - aKTy,
                T, Sigma, aok, xsum, ysum)
            state = state._replace(tau=tau_n, sigma=sigma_n)
            return (state, it + check_every, merit, xs, ys, cnt,
                    m_restart, rk, state.x, state.y, Kx_c, KTy_c,
                    jnp.asarray(True), rx, ry)
        return (state, it + check_every, merit, xs, ys, cnt, m_restart, rk)

    def cond(loop):
        it, merit = loop[1], loop[2]
        return jnp.logical_and(it < max_iters, merit > tol)

    init = (state0, jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, dt),
            jnp.zeros_like(x0), jnp.zeros_like(y0), jnp.asarray(0.0, dt),
            jnp.asarray(jnp.inf, dt), key)
    if adaptive:
        # window baselines for the first boundary are placeholders
        # (aok=False masks them until a boundary has been recorded);
        # the restart anchors (rx, ry) start at the true initial iterate.
        init = init + (x0, y0, jnp.zeros_like(y0), jnp.zeros_like(x0),
                       jnp.asarray(False), x0, y0)
    state, it, merit = jax.lax.while_loop(cond, body, init)[:3]
    return state.x, state.y, it, merit


# ----------------------------------------------------- jit core + ledger ---

def solve_core(K_fwd, K_adj, b, c, lb, ub, T, Sigma, rho, key, static, *,
               operator: Optional[Operator] = None, x0=None, y0=None):
    """The jitted solve core (formerly ``pdhg._solve_jit_core``).

    ``static`` is the hashable tuple from ``pdhg.opts_static``:
    (max_iters, tol, eta, omega, gamma, check_every, restart_beta,
    sigma_read, kernel).  ``sigma_read`` > 0 adds multiplicative
    cycle-to-cycle read noise per MVM — residual checks included —
    and ``kernel`` selects the update backend (jnp | pallas).

    ``operator`` swaps the MVM backend (e.g. the differential-pair
    crossbar kernel or the ELL operator) in place of the
    default dense one — ``K_fwd``/``K_adj`` may then be ``None``; the
    step-size initialization, init draws, and option plumbing stay HERE
    either way (problem dims come from ``b``/``c``).  ``K_fwd`` may be
    a ``jax.experimental.sparse`` matrix (BCOO/BCSR): the default
    operator is then ``sparse_operator`` and ``K_adj`` is ignored (the
    adjoint is a transpose view of the same nonzeros).

    Trailing static entries past the original 9 are optional (older
    9-tuples keep their exact semantics): ``restart`` (explicit restart
    gate, default True), ``sparse_kernel`` (executable-cache
    discriminator for the sparse backend — the stacking layer picks the
    operator), ``megakernel`` (fuse each check window into one launch;
    mounted on the noiseless dense backend and refused on every other),
    ``step_rule`` (one of ``STEP_RULES``, default ``"fixed"`` — see
    ``pdhg_loop``).  Entries 13/14 (``refine_rounds``/``refine_tol``)
    belong to the digital refinement shell around this core
    (``crossbar.refine``) and are ignored here.

    ``x0``/``y0`` warm-start the loop (both or neither); by default the
    paper's projected-Gaussian init is drawn from ``key``.  The
    refinement shell passes zeros — the correction LP's origin IS the
    previous outer iterate in shifted coordinates.
    """
    (max_iters, tol, eta, omega, gamma, check_every, restart_beta,
     sigma_read, kernel) = static[:9]
    # ``static`` is the jit static-arg tuple — plain Python values at
    # trace time, so these bool() calls never touch the device
    restart = bool(static[9]) if len(static) > 9 else True  # jaxlint: disable=R5
    megakernel = bool(static[11]) if len(static) > 11 else False  # jaxlint: disable=R5
    step_rule = str(static[12]) if len(static) > 12 else "fixed"
    m, n = b.shape[0], c.shape[0]
    # an all-zero operator (degenerate but legal: the optimum is just the
    # box projection of -c's direction) has rho = 0; unguarded it makes
    # tau0 = inf and NaNs the very first update
    rho = jnp.maximum(rho, jnp.asarray(1e-12, b.dtype))
    tau0 = eta / (omega * rho)
    sigma0 = eta * omega / rho
    if x0 is None:
        key, x0, y0 = draw_init(key, m, n, lb, ub, b.dtype)
    if operator is None:
        if hasattr(K_fwd, "todense"):   # JAXSparse (BCOO/BCSR), not ndarray
            operator = sparse_operator(K_fwd, sigma_read)
        else:
            operator = dense_operator(K_fwd, K_adj, sigma_read)
    if megakernel and operator.fuse is None:
        if operator.name != "dense" or sigma_read > 0.0:
            raise ValueError(
                f"megakernel=True fuses the noiseless dense operator only; "
                f"this solve mounts the {operator.name!r} operator"
                f"{' with read noise' if sigma_read > 0.0 else ''} — set "
                "PDHGOptions.megakernel=False")
        operator = operator._replace(fuse=make_fused_dense(
            K_fwd, K_adj, b, c, lb, ub, T, Sigma, gamma))
    return pdhg_loop(
        operator, make_updates(kernel, b.dtype),
        b, c, lb, ub, T, Sigma, x0, y0, tau0, sigma0, key,
        max_iters=max_iters, tol=tol, gamma=gamma, check_every=check_every,
        restart_beta=restart_beta, restart=restart,
        step_rule=step_rule, eta=eta,
    )


def lemma2_margin(rho, sigma_read: float):
    """Widen a NOISY operator-norm estimate so the step-size coupling
    tau*sigma*rho^2 < 1 (Lemma 2) holds for the TRUE norm despite the
    read noise in the Lanczos MVMs.  Identity when noiseless; callers
    skip it entirely under ``opts.norm_override`` (a trusted norm)."""
    if sigma_read <= 0.0:
        return rho
    return rho / (1.0 - min(4.0 * sigma_read, 0.5))


# Per-window accounting pieces.  These three are the GROUND TRUTH the
# trace-level audit (tools/traceaudit) independently reproduces by
# counting MVM-bearing primitives in the jaxpr of every solver path —
# change any of them and the audit fails until the traced computation
# (or TRACE_BASELINE.json) agrees again.

#: MVMs per PDHG half-iteration pair: one forward (K @ x_bar) for the
#: dual update + one adjoint (K^T @ y) for the primal update.
MVMS_PER_ITERATION = 2


def mvms_per_check(restart: bool = True) -> int:
    """MVMs charged per residual check: an x/y pair for the current
    iterate, plus a second pair for the averaged iterate when restarts
    are enabled (with ``restart=False`` the averaged pair is never
    evaluated)."""
    return 4 if restart else 2


def mvm_window_budget(check_every: int, restart: bool = True) -> int:
    """MVMs per while_loop body execution (one check window): the
    ``check_every`` fused/stepped PDHG iterations plus the residual
    check.  ``step_rule="adaptive"`` rebalances from already-computed
    quantities and adds exactly zero — the traceaudit budget checker
    asserts this per path."""
    return MVMS_PER_ITERATION * check_every + mvms_per_check(restart)


def mvm_accounting(iterations: int, check_every: int,
                   lanczos_iters: int, restart: bool = True) -> int:
    """Device-MVM total for the energy ledger, shared by every jitted
    path: norm estimation (1 MVM per Lanczos/power iteration; 0 under
    ``norm_override``) + PDHG (``MVMS_PER_ITERATION``/iter) + residual
    checks (``mvms_per_check(restart)`` each).

    ``iterations`` on EVERY jitted path — stepped fori_loop and fused
    megakernel alike — advances by ``check_every`` per while_loop body,
    so reported iteration counts (and therefore this charge) quantize to
    ``check_every`` multiples: convergence mid-window is only observed
    at the next boundary, and the work (and energy) for the full window
    was genuinely spent.  Megakernel and stepped paths agree exactly —
    a test pins this (``tests/test_step_rules.py``)."""
    n_checks = max(1, iterations // max(1, check_every))
    return (lanczos_iters + MVMS_PER_ITERATION * iterations
            + mvms_per_check(restart) * n_checks)


def refine_digital_mvms(refine_rounds: int) -> int:
    """Exact (digital, full-precision) MVMs the iterative-refinement
    shell (``crossbar.refine``) issues OUTSIDE the analog while loops:
    one (Kx, K^Ty) baseline pair before the first round plus one
    candidate-evaluation pair per round.  These run on the digital
    co-processor against the exact operator — they are NOT analog reads
    and are never charged to the crossbar read ledger; the traceaudit
    budget analyzer uses this count to tell sanctioned digital residual
    MVMs apart from unledgered analog reads leaking out of the loop."""
    return 0 if refine_rounds <= 0 else 2 + 2 * refine_rounds


def refine_window_factor(refine_rounds: int) -> int:
    """Number of analog while-loop solves a refined path runs (the
    original solve plus one correction solve per round) — each is a full
    ``pdhg_loop`` whose windows charge ``mvm_window_budget`` MVMs.  The
    traceaudit budget analyzer multiplies the per-window budget by this
    when auditing refined paths."""
    return 1 + max(0, refine_rounds)

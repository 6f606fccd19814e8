"""Enhanced PDHG for LPs on in-memory accelerators (paper Algorithm 4).

Iteration (sign convention of eq. 7; Algorithm 4 lists the equivalent
negated-dual form — we keep eq. 7's so the KKT conditions (9)-(11) read
canonically: K^T y <= c etc.):

    theta_k = 1 / sqrt(1 + 2*gamma*tau)        # deterministic adaptation
    tau    <- theta_k * tau;   sigma <- sigma / theta_k    # tau*sigma const
    x_bar  = x_k + theta_k (x_k - x_{k-1})     # momentum extrapolation
    y_{k+1} = y_k + sigma * Sigma ⊙ (b - K x_bar)          # 1 device MVM
    x_{k+1} = proj_[lb,ub]( x_k - tau * T ⊙ (c - K^T y_{k+1}) )  # 1 device MVM

Exactly two device MVMs per iteration, both against the SAME encoded
symmetric block M (Algorithm 2 modes A@x and AT@y); all proximal and
vector algebra stays on the host.  No K / K^T reprogramming ever happens
after the single encode (Algorithm 1).

Two drivers:
  * ``solve``      — host loop over an arbitrary ``Accel`` (crossbar sim
                     with energy ledger, noise keys, restart logic,
                     infeasibility detection, residual history).
  * ``solve_jit``  — jax.lax.while_loop, fully jitted on a dense K
                     (the performance/distributed path).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..lp.problem import StandardLP
from . import engine
from . import precondition as precond_mod
from .lanczos import (
    NORM_BACKENDS,
    lanczos_svd,
    lanczos_svd_jit,
    power_iteration_mv,
)
from .noise import NOISELESS, NoiseModel
from .residuals import KKTResiduals, kkt_residuals
from .symblock import (
    build_sym_block,
    encode_exact,
    encode_noisy,
    mv,
    scaled_accel,
)


@dataclasses.dataclass
class PDHGOptions:
    max_iters: int = 20000
    tol: float = 1e-6
    eta: float = 0.95              # safety margin (paper: eta ~ 0.95)
    omega: float = 1.0             # primal weight (tau = eta/(omega L), sigma = eta omega/L)
    gamma: float = 0.0             # Nesterov acceleration parameter (>=0)
    ruiz_iters: int = 10
    use_diag_precond: bool = True
    lanczos_iters: int = 64
    lanczos_tol: float = 1e-8
    check_every: int = 64
    restart: bool = True
    restart_beta: float = 0.5      # restart when merit(avg) < beta * merit at last restart
    infeasibility_detection: bool = True
    seed: int = 0
    dtype: np.dtype = np.float64
    track_history: bool = False
    norm_override: Optional[float] = None  # skip Lanczos (reuse across runs)
    kernel: str = "jnp"            # update backend: "jnp" | "pallas" (fused)
    sparse_kernel: str = "ell"     # sparse operator backend: "ell"
    #                                (ELL XLA gather; the
    #                                wall-clock path) | "bcoo" (COO/BCOO
    #                                scatter; the memory-optimal path)
    megakernel: bool = False       # fuse each check_every window into ONE
    #                                kernel launch (noiseless paths only)
    step_rule: str = "fixed"       # step-size schedule: "fixed" (constant
    #                                tau/sigma; requires gamma == 0) |
    #                                "adaptive" (data-driven primal-weight
    #                                init, PDLP-style rebalancing at
    #                                restart events, and a down-only
    #                                Malitsky-Pock-flavored step-scale
    #                                safeguard — all at check boundaries
    #                                only, so fused windows stay one
    #                                launch; requires gamma == 0) |
    #                                "strongly_convex" (the accelerated
    #                                theta_k = 1/sqrt(1+2*gamma*tau)
    #                                schedule; requires gamma > 0)
    norm_backend: str = "lanczos"  # operator-norm estimator on the jitted
    #                                prep paths: "lanczos" (Algorithm 3) |
    #                                "power" (symmetric-block power
    #                                iteration; same MVM count/charge)
    refine_rounds: int = 0         # digital iterative-refinement rounds
    #                                around the crossbar solve
    #                                (crossbar.refine): each round
    #                                re-solves the residual-correction LP
    #                                on the SAME programmed conductances
    #                                (shifted b/c only — zero extra write
    #                                cycles), recovering digital-grade
    #                                accuracy from noisy analog reads
    refine_tol: float = 0.0        # stop adopting corrections once the
    #                                exact (digital) KKT merit is at or
    #                                below this — avoids pumping read
    #                                noise back into a converged iterate
    #                                (0.0 = refine for all rounds)


@dataclasses.dataclass
class PDHGResult:
    status: str                 # "optimal" | "iteration_limit" |
    #                             "diverged" | "primal_infeasible"
    x: np.ndarray               # solution in ORIGINAL (unscaled) coordinates
    y: np.ndarray
    obj: float
    iterations: int
    residuals: KKTResiduals
    sigma_max: float            # operator-norm estimate used
    lanczos_iters: int
    mvm_calls: int              # total device MVMs issued (energy ledger)
    history: Optional[list] = None
    restarts: int = 0
    certificate: Optional[object] = None   # Farkas cert when diverged
    merit: Optional[float] = None  # in-loop merit at exit (jitted paths:
    #                                computed with the same noisy device
    #                                MVMs the solve used; ``residuals`` is
    #                                the noiseless post-hoc evaluation)


def _project(x, lb, ub):
    return jnp.clip(x, lb, ub)


def prepare(lp: StandardLP, opts: PDHGOptions):
    """Step 0 of Algorithm 4: scaling, preconditioning (host).

    Densifies a sparse K — the single-instance paths are dense; sparse
    problems stream through ``runtime.batch``'s sparse pipeline instead.
    """
    dt = opts.dtype
    scaled = precond_mod.apply_ruiz(
        jnp.asarray(lp.K_dense, dt), jnp.asarray(lp.b, dt), jnp.asarray(lp.c, dt),
        jnp.asarray(lp.lb, dt), jnp.asarray(lp.ub, dt),
        iters=opts.ruiz_iters,
    )
    if opts.use_diag_precond:
        T, Sigma = precond_mod.diagonal_precondition(scaled.K)
    else:
        m, n = scaled.K.shape
        T = jnp.ones(n, dt)
        Sigma = jnp.ones(m, dt)
    return scaled, T, Sigma


def solve(
    lp: StandardLP,
    opts: PDHGOptions = PDHGOptions(),
    accel_factory: Optional[Callable] = None,
    noise: NoiseModel = NOISELESS,
    on_iteration: Optional[Callable] = None,
) -> PDHGResult:
    """Algorithm 4 host driver over an arbitrary accelerator backend.

    accel_factory(K_scaled) -> Accel.  Default: exact dense backend.
    ``noise`` only applies to the default backends; a crossbar backend
    brings its own device physics.
    """
    opts_static(opts)    # shared option validation (step_rule/kernel/...)
    scaled, T, Sigma = prepare(lp, opts)
    m, n = scaled.K.shape
    key = jax.random.PRNGKey(opts.seed)

    if accel_factory is None:
        if noise.kind == "none":
            accel = encode_exact(scaled.K)
        else:
            accel = encode_noisy(scaled.K, noise.apply)
    else:
        accel = accel_factory(scaled.K)
    use_keys = noise.kind != "none" or accel.name.startswith("crossbar")

    # ---- Step 1: operator-norm estimation on the PRECONDITIONED operator.
    # M' = D M D with D = diag(sqrt(Sigma), sqrt(T)) is the symmetric block
    # of Sigma^{1/2} K T^{1/2}; Lanczos on M' (host-side scaling wrap, no
    # device rewrite) yields rho = ||Sigma^{1/2} K T^{1/2}||_2, and the
    # convergence condition for diagonal steps (tau T, sigma Sigma) is
    # tau*sigma*rho^2 < 1 (Lemma 2 with L := rho).
    if opts.norm_override is not None:
        rho = float(opts.norm_override)
        lanczos_iters = 0
    else:
        wrapped = scaled_accel(accel, jnp.sqrt(Sigma), jnp.sqrt(T))
        key, sub = jax.random.split(key)
        lres = lanczos_svd(
            wrapped, k_max=opts.lanczos_iters, tol=opts.lanczos_tol,
            key=sub, reorthogonalize=True, noise_keys=use_keys,
        )
        rho = lres.sigma_max
        lanczos_iters = lres.iterations

    tau = opts.eta / (opts.omega * rho)
    sigma = opts.eta * opts.omega / rho
    adaptive = opts.step_rule == "adaptive"
    w_lo = w_hi = None
    adapt_prev = None               # previous boundary (x, y, Kx, KTy)
    if adaptive:
        # data-driven primal-weight init + trust region (engine math)
        tau, sigma = engine.adaptive_omega_init(
            jnp.asarray(tau, scaled.K.dtype),
            jnp.asarray(sigma, scaled.K.dtype),
            scaled.b, scaled.c, T, Sigma)
        w0 = jnp.sqrt(sigma / tau)
        w_lo = w0 / engine.ADAPT_OMEGA_CLIP
        w_hi = w0 * engine.ADAPT_OMEGA_CLIP

    # ---- Step 2: initialization (paper: projected Gaussian start).
    key, kx, ky = jax.random.split(key, 3)
    x = _project(jax.random.normal(kx, (n,), dtype=scaled.K.dtype),
                 scaled.lb, scaled.ub)
    y = jax.random.normal(ky, (m,), dtype=scaled.K.dtype)
    # running ergodic sums for restarts / averaged iterate
    x_sum = jnp.zeros_like(x)
    y_sum = jnp.zeros_like(y)
    avg_len = 0
    merit_at_restart = np.inf
    n_restarts = 0

    history = [] if opts.track_history else None
    status = "iteration_limit"
    res = None
    it = 0

    # The per-iteration math is the engine's — this driver only owns the
    # Python-level control flow (history, callbacks, infeasibility exit).
    op = engine.accel_operator(accel)
    upd = engine.make_updates(opts.kernel, scaled.K.dtype)
    state = engine.init_state(x, y, tau, sigma, opts.gamma)
    adapt_anchor = (state.x, state.y)   # restart anchor for omega updates
    del x, y, tau, sigma

    for it in range(opts.max_iters):
        if use_keys:
            key, k1, k2 = jax.random.split(key, 3)
        else:
            k1 = k2 = None
        state = engine.pdhg_step(op, upd, scaled.b, scaled.c, scaled.lb,
                                 scaled.ub, T, Sigma, opts.gamma, state,
                                 k1, k2)

        x_sum = x_sum + state.x
        y_sum = y_sum + state.y
        avg_len += 1

        if (it + 1) % opts.check_every == 0 or it == opts.max_iters - 1:
            if use_keys:
                key, k3, k4 = jax.random.split(key, 3)
            else:
                k3 = k4 = None
            Kx = op.fwd(state.x, k3)
            KTy_c = op.adj(state.y, k4)
            res = kkt_residuals(
                state.x, state.x_prev, state.y, scaled.c, scaled.b, Kx,
                KTy_c, lb=scaled.lb, ub=scaled.ub,
            )
            merit = float(res.max)
            if history is not None:
                history.append(
                    {"iter": it + 1, "merit": merit, **res.as_dict(),
                     "obj": float(jnp.vdot(scaled.c, state.x))}
                )
            if on_iteration is not None:
                on_iteration(it + 1, merit, accel)
            if not np.isfinite(merit):
                # NaN/inf merit: the iterate blew up.  NaN fails every
                # comparison below, so without this check the loop would
                # run to the iteration limit and report it as such.
                status = "diverged"
                break
            if merit <= opts.tol:
                status = "optimal"
                break
            if opts.infeasibility_detection and merit > 1e8:
                status = "diverged"
                break
            Kx_b, KTy_b = Kx, KTy_c   # images of the iterate carried on
            if opts.restart and avg_len > 0:
                # fresh keys: reusing k3/k4 here would correlate the read
                # noise between the current- and averaged-iterate checks
                if use_keys:
                    key, k5, k6 = jax.random.split(key, 3)
                else:
                    k5 = k6 = None
                x_avg = x_sum / avg_len
                y_avg = y_sum / avg_len
                Kxa = op.fwd(x_avg, k5)
                KTya = op.adj(y_avg, k6)
                res_avg = kkt_residuals(
                    x_avg, x_avg, y_avg, scaled.c, scaled.b, Kxa, KTya,
                    lb=scaled.lb, ub=scaled.ub,
                )
                merit_avg = float(res_avg.max)
                if merit_avg < opts.restart_beta * merit_at_restart:
                    # restart from the (better) averaged iterate
                    if merit_avg < merit:
                        state = engine.restart_state(state, x_avg, y_avg)
                        Kx_b, KTy_b = Kxa, KTya
                    merit_at_restart = min(merit_avg, merit)
                    x_sum = jnp.zeros_like(state.x)
                    y_sum = jnp.zeros_like(state.y)
                    avg_len = 0
                    n_restarts += 1
                    if adaptive:
                        # primal-weight rebalance rides restart events
                        rx, ry = adapt_anchor
                        tau_n, sigma_n = engine.adaptive_omega_update(
                            state.tau, state.sigma,
                            state.x - rx, state.y - ry, T, Sigma,
                            w_lo, w_hi, jnp.asarray(True))
                        state = state._replace(tau=tau_n, sigma=sigma_n)
                        adapt_anchor = (state.x, state.y)
            if adaptive:
                # boundary-only down-only scale safeguard; the math lives
                # in the engine, and K(dx)/K^T(dy) come from the check
                # MVMs by linearity
                if adapt_prev is not None:
                    px, py, pKx, pKTy = adapt_prev
                    tau_n, sigma_n = engine.adaptive_shrink(
                        state.tau, state.sigma, opts.eta,
                        state.x - px, state.y - py,
                        Kx_b - pKx, KTy_b - pKTy,
                        T, Sigma, jnp.asarray(True))
                    state = state._replace(tau=tau_n, sigma=sigma_n)
                adapt_prev = (state.x, state.y, Kx_b, KTy_b)

    x_orig = np.asarray(scaled.unscale_x(state.x))
    y_orig = np.asarray(scaled.unscale_y(state.y))
    if res is None:
        Kx = op.fwd(state.x)
        KTy_c = op.adj(state.y)
        res = kkt_residuals(state.x, state.x, state.y, scaled.c, scaled.b,
                            Kx, KTy_c, lb=scaled.lb, ub=scaled.ub)
    certificate = None
    if status == "diverged" and opts.infeasibility_detection:
        # PDHG's dual iterate diverges along a Farkas ray on primal-
        # infeasible instances [51]; the diagonal rescaling preserves
        # certificates (K~^T y~ <= 0 <=> K^T (D1 y~) <= 0 for D2 > 0).
        from .infeasibility import check_farkas

        cert = check_farkas(np.asarray(lp.K), np.asarray(lp.b), y_orig,
                            tol=1e-5)
        if cert.kind != "none":
            status = "primal_infeasible"
            certificate = cert
    return PDHGResult(
        status=status,
        x=x_orig,
        y=y_orig,
        obj=float(lp.c @ x_orig),
        iterations=it + 1,
        residuals=res,
        sigma_max=rho,
        lanczos_iters=lanczos_iters,
        mvm_calls=accel.stats["mvm_calls"],
        history=history,
        restarts=n_restarts,
        certificate=certificate,
        merit=float(res.max),
    )


# --------------------------------------------------------------------------
# Fully-jitted dense solver (performance path; the iteration core itself
# lives in ``core.engine`` — this is the option plumbing around it).
# --------------------------------------------------------------------------

# PDHGOptions fields that deliberately stay OUT of the compiled-executable
# cache key (``tools.jaxlint`` rule R1 cross-checks this allowlist against
# the dataclass fields and the ``opts_static`` tuple below — adding an
# option without deciding its cache-key fate is a lint error).
# ``ruiz_iters``/``lanczos_iters``/``norm_override``/``norm_backend``
# ride in ``runtime.batch``'s separate prep-signature tuple (the norm
# estimate is a prep-stage input to the solve executable, not part of
# its trace); ``lanczos_tol``/
# ``use_diag_precond``/``infeasibility_detection`` only steer the host
# solve path; ``seed``/``track_history`` are runtime data; ``dtype`` is
# already encoded by every traced array shape.
DYNAMIC_FIELDS = (
    "ruiz_iters", "use_diag_precond", "lanczos_iters", "lanczos_tol",
    "infeasibility_detection", "seed", "dtype", "track_history",
    "norm_override", "norm_backend",
)


def opts_static(opts: PDHGOptions, sigma_read: float = 0.0) -> tuple:
    """The hashable option tuple ``engine.solve_core`` consumes
    (positional unpack — keep in sync with the head of that function, and
    nowhere else: ``solve_jit``, ``runtime.batch`` and
    ``crossbar.solver`` all build it through here; fields that
    deliberately stay out of the tuple are declared in
    ``DYNAMIC_FIELDS`` and the pairing is machine-checked by jaxlint
    rule R1).  ``opts.kernel``,
    ``opts.restart``, ``opts.sparse_kernel``, ``opts.megakernel`` and
    ``opts.step_rule`` are
    part of the tuple, so compiled-executable caches keyed on it never
    serve one backend's executable to another (a step-rule change is a
    different trace and must never reuse an executable compiled for
    another rule).  ``opts.restart`` rides
    as an explicit static boolean — the old encoding (restart off ==
    ``restart_beta 0.0``) only worked because ``0.0 * inf`` is NaN and
    NaN comparisons are false inside the jitted body."""
    if opts.kernel not in engine.KERNELS:
        raise ValueError(f"unknown update kernel {opts.kernel!r}; "
                         f"expected one of {engine.KERNELS}")
    if opts.sparse_kernel not in engine.SPARSE_KERNELS:
        raise ValueError(f"unknown sparse kernel {opts.sparse_kernel!r}; "
                         f"expected one of {engine.SPARSE_KERNELS}")
    if opts.megakernel and float(sigma_read) > 0.0:
        raise ValueError("megakernel mode is noiseless-only: per-MVM "
                         "read-noise keys cannot be split inside a fused "
                         "launch (sigma_read must be 0)")
    if opts.step_rule not in engine.STEP_RULES:
        raise ValueError(f"unknown step_rule {opts.step_rule!r}; expected "
                         f"one of {engine.STEP_RULES}")
    if opts.step_rule == "strongly_convex" and not opts.gamma > 0.0:
        raise ValueError("step_rule='strongly_convex' is the accelerated "
                         "theta_k schedule and requires gamma > 0")
    if opts.step_rule != "strongly_convex" and opts.gamma != 0.0:
        raise ValueError(f"gamma > 0 drives the strongly-convex schedule; "
                         f"set step_rule='strongly_convex' explicitly "
                         f"(got gamma={opts.gamma} with "
                         f"step_rule={opts.step_rule!r})")
    if opts.refine_rounds < 0:
        raise ValueError(f"refine_rounds must be >= 0 "
                         f"(got {opts.refine_rounds})")
    # refine_rounds/refine_tol ride in the static tuple (entries 13/14):
    # the refinement shell unrolls one analog solve per round, so a
    # different round count is a different trace and must never reuse an
    # executable compiled for another.  solve_core itself ignores them.
    return (opts.max_iters, opts.tol, opts.eta, opts.omega, opts.gamma,
            opts.check_every, opts.restart_beta, float(sigma_read),
            opts.kernel, bool(opts.restart), opts.sparse_kernel,
            bool(opts.megakernel), opts.step_rule,
            int(opts.refine_rounds), float(opts.refine_tol))


# Backwards-compatible alias: the dense jit core now lives in the engine.
_solve_jit_core = engine.solve_core


def solve_jit(
    lp: StandardLP,
    opts: PDHGOptions = PDHGOptions(),
    K_fwd=None,
    K_adj=None,
    sigma_read: float = 0.0,
    transfer_sanitize: bool = False,
) -> PDHGResult:
    """Jitted dense-K solver: Ruiz + PC precond + Lanczos + while_loop.

    ``K_fwd``/``K_adj`` override the operator actually *executed* (e.g. the
    decoded programmed crossbar blocks, already in the Ruiz-scaled frame);
    preconditioning and residual scaling still derive from the nominal K.
    ``sigma_read`` adds multiplicative per-MVM read noise inside the loop.
    ``transfer_sanitize`` runs the jitted iteration core under
    ``runtime.sanitize.no_implicit_transfers()`` — every input is device
    resident by then, so any implicit transfer the solve triggers is a
    bug and raises (host-side prep/result extraction stay unguarded:
    those transfers are the sanctioned ones).
    """
    scaled, T, Sigma = prepare(lp, opts)
    Kf = scaled.K if K_fwd is None else jnp.asarray(K_fwd, scaled.K.dtype)
    Ka = Kf.T if K_adj is None else jnp.asarray(K_adj, scaled.K.dtype)
    if opts.norm_backend not in NORM_BACKENDS:
        raise ValueError(f"unknown norm_backend {opts.norm_backend!r}; "
                         f"expected one of {NORM_BACKENDS}")
    if opts.norm_override is not None:
        rho = jnp.asarray(opts.norm_override, scaled.K.dtype)
    else:
        Keff = jnp.sqrt(Sigma)[:, None] * Kf * jnp.sqrt(T)[None, :]
        M = build_sym_block(Keff)
        if opts.norm_backend == "power":
            rho = power_iteration_mv(lambda v: mv(M, v), M.shape[0], M.dtype,
                                     iters=opts.lanczos_iters)
        else:
            rho = lanczos_svd_jit(M, k_max=opts.lanczos_iters)
        rho = engine.lemma2_margin(rho, sigma_read)
    static = opts_static(opts, sigma_read)
    core = jax.jit(engine.solve_core, static_argnums=(10,))
    core_args = (
        Kf, Ka, scaled.b, scaled.c, scaled.lb, scaled.ub, T, Sigma, rho,
        jax.random.PRNGKey(opts.seed + 1), static,
    )
    if transfer_sanitize:
        from ..runtime import sanitize
        with sanitize.no_implicit_transfers():
            x, y, it, merit = core(*core_args)
    else:
        x, y, it, merit = core(*core_args)
    x_orig = np.asarray(scaled.unscale_x(x))
    y_orig = np.asarray(scaled.unscale_y(y))
    res = kkt_residuals(
        x, x, y, scaled.c, scaled.b, mv(scaled.K, x), mv(scaled.K.T, y),
        lb=scaled.lb, ub=scaled.ub,
    )
    it_i = int(it)
    lanczos_mvms = 0 if opts.norm_override is not None else opts.lanczos_iters
    merit_f = float(merit)
    # a non-finite merit exits the while_loop (NaN > tol is false) —
    # report it as divergence, not as a clean iteration limit
    if not np.isfinite(merit_f):
        status = "diverged"
    elif merit_f <= opts.tol:
        status = "optimal"
    else:
        status = "iteration_limit"
    return PDHGResult(
        status=status,
        x=x_orig, y=y_orig, obj=float(lp.c @ x_orig),
        iterations=it_i, residuals=res, sigma_max=float(rho),
        lanczos_iters=lanczos_mvms,
        mvm_calls=engine.mvm_accounting(it_i, opts.check_every,
                                        lanczos_mvms,
                                        restart=opts.restart),
        merit=merit_f,
    )

"""Operator-norm estimation (paper §3.2, Algorithm 3).

Power iteration (eq. 8) is the classical choice; the paper adopts the
Lanczos iteration on the symmetric block M because it is markedly more
robust to analog MVM noise (Theorem 1: the ergodic Ritz estimate obeys
O(1/K) + O(K * eps_max)).  Proposition 1: lambda_max(M) == sigma_max(K),
so a Lanczos run on M estimates ||K||_2 directly with ONE device MVM per
iteration.

Two implementations:
  * ``lanczos_svd``      — host loop over an arbitrary Accel backend
                           (crossbar sim, energy ledger, noise keys).
  * ``lanczos_svd_jit``  — fixed-iteration lax.scan, fully jittable
                           (used by the distributed/perf paths).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .symblock import MODE_FULL, Accel, dot, matmul_accel, mv

# norm estimators selectable by ``PDHGOptions.norm_backend`` on the
# jitted prep paths; both cost ONE symmetric-block MVM per iteration, so
# the energy ledger charges them identically
NORM_BACKENDS = ("lanczos", "power")


@dataclasses.dataclass
class LanczosResult:
    sigma_max: float          # estimated dominant singular value of K
    iterations: int
    alphas: np.ndarray
    betas: np.ndarray
    ritz_history: np.ndarray  # largest Ritz value after each iteration
    ergodic_estimate: float   # mean of ritz_history (Theorem 1 estimator)


def lanczos_svd(
    accel: Accel,
    k_max: int = 64,
    tol: float = 1e-8,
    key: Optional[jax.Array] = None,
    reorthogonalize: bool = True,
    noise_keys: bool = False,
) -> LanczosResult:
    """Algorithm 3 (LanczosSVD) on the encoded symmetric block M.

    One full-vector device MVM per iteration.  ``reorthogonalize`` applies
    full re-orthogonalization against all previous basis vectors (the
    paper's Lemma 1 setting, essential under device noise).
    """
    dim = accel.m + accel.n
    if key is None:
        # deliberate: the default start vector must be reproducible so
        # norm estimates (and thus step sizes) are stable run-to-run
        key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
    key, sub = jax.random.split(key)
    v = jax.random.normal(sub, (dim,))
    v = v / jnp.linalg.norm(v)
    v_prev = jnp.zeros_like(v)
    beta = 0.0
    alphas, betas, ritz_hist = [], [], []
    basis = [v]
    for j in range(k_max):
        if noise_keys:
            key, sub = jax.random.split(key)
            w = matmul_accel(accel, v, MODE_FULL, key=sub)
        else:
            w = matmul_accel(accel, v, MODE_FULL)
        w = w - beta * v_prev
        alpha = float(dot(v, w))
        w = w - alpha * v
        if reorthogonalize:
            for q in basis:
                w = w - dot(q, w) * q
        beta_next = float(jnp.linalg.norm(w))
        alphas.append(alpha)
        betas.append(beta_next)
        T = _tridiag(alphas, betas[:-1])
        ritz = float(np.max(np.abs(np.linalg.eigvalsh(T))))
        ritz_hist.append(ritz)
        # breakdown when beta hits the requested tol OR the fp-roundoff
        # floor of the working dtype (an absolute 1e-10 can never trigger
        # in f32, where residual norms bottom out around eps * ||M||)
        eps_floor = 8.0 * float(jnp.finfo(w.dtype).eps) * max(ritz, 1.0)
        if beta_next < max(tol, eps_floor):
            break
        v_prev = v
        v = w / beta_next
        basis.append(v)
        beta = beta_next
    ritz_hist = np.asarray(ritz_hist)
    return LanczosResult(
        sigma_max=float(ritz_hist[-1]),
        iterations=len(alphas),
        alphas=np.asarray(alphas),
        betas=np.asarray(betas),
        ritz_history=ritz_hist,
        ergodic_estimate=float(ritz_hist.mean()),
    )


def _tridiag(alphas, betas) -> np.ndarray:
    k = len(alphas)
    T = np.zeros((k, k))
    T[np.arange(k), np.arange(k)] = alphas
    if k > 1:
        T[np.arange(k - 1), np.arange(1, k)] = betas
        T[np.arange(1, k), np.arange(k - 1)] = betas
    return T


def lanczos_svd_jit_mv(matvec, dim: int, dtype, k_max: int = 32,
                       key=None) -> jnp.ndarray:
    """Jitted fixed-iteration Lanczos on an arbitrary symmetric matvec.

    The operator enters only through ``matvec(v) -> M v`` — sparse
    pipelines pass a BCOO/COO contraction over the symmetric block M
    here and never build M densely.  Returns the largest |Ritz value| of
    the k_max-step tridiagonalization; no early exit (fixed cost).
    """
    if key is None:
        # deliberate: reproducible default start vector (see lanczos_svd)
        key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
    v0 = jax.random.normal(key, (dim,), dtype=dtype)
    v0 = v0 / jnp.linalg.norm(v0)

    def step(carry, _):
        v_prev, v, beta = carry
        w = matvec(v)
        w = w - beta * v_prev
        alpha = dot(v, w)
        w = w - alpha * v
        beta_next = jnp.linalg.norm(w)
        v_next = jnp.where(beta_next > 1e-30, w / beta_next, w)
        return (v, v_next, beta_next), (alpha, beta_next)

    (_, _, _), (alphas, betas) = jax.lax.scan(
        step, (jnp.zeros_like(v0), v0, jnp.asarray(0.0, dtype)),
        None, length=k_max,
    )
    T = jnp.diag(alphas) + jnp.diag(betas[:-1], 1) + jnp.diag(betas[:-1], -1)
    return jnp.max(jnp.abs(jnp.linalg.eigvalsh(T)))


def lanczos_svd_jit(M: jnp.ndarray, k_max: int = 32, key=None) -> jnp.ndarray:
    """Jitted fixed-iteration Lanczos on a dense symmetric M.

    Returns the largest |Ritz value| of the k_max-step tridiagonalization.
    No early exit (fixed cost) — used inside jitted solver pipelines and
    the distributed dry-run.
    """
    return lanczos_svd_jit_mv(lambda v: mv(M, v), M.shape[0], M.dtype,
                              k_max=k_max, key=key)


def power_iteration_mv(matvec, dim: int, dtype, iters: int = 64,
                       key=None, v0=None) -> jnp.ndarray:
    """Jitted fixed-iteration power method on an arbitrary symmetric
    matvec — the ``norm_backend="power"`` twin of ``lanczos_svd_jit_mv``
    (same call shape, same one-MVM-per-iteration ledger charge).

    On the symmetric block M of K the spectrum comes in +/-sigma pairs
    (Proposition 1), so the iterate itself may oscillate between the two
    dominant eigenvector signs — but the Rayleigh growth factor
    ``||M v_k||`` still converges to sigma_max(K), which is what is
    returned.  ``v0`` optionally overrides the start vector; by default a
    fresh reproducible draw is used (the norm-reuse refinement path keeps
    the default — only the scalar estimate is cached, not the direction).
    """
    if key is None:
        # deliberate: reproducible default start vector (see lanczos_svd)
        key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
    if v0 is None:
        v0 = jax.random.normal(key, (dim,), dtype=dtype)
    v0 = v0 / jnp.maximum(jnp.linalg.norm(v0), 1e-30)

    def body(v, _):
        w = matvec(v)
        nw = jnp.linalg.norm(w)
        return w / jnp.maximum(nw, 1e-30), nw

    _, norms = jax.lax.scan(body, v0, None, length=iters)
    return norms[-1]


def power_iteration(
    K: jnp.ndarray, iters: int = 100, key=None
) -> jnp.ndarray:
    """Two-sided power iteration baseline (eq. 8): ||K||_2 estimate."""
    m, n = K.shape
    if key is None:
        # deliberate: reproducible default start vector (see lanczos_svd)
        key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
    v = jax.random.normal(key, (n,), dtype=K.dtype)
    v = v / jnp.linalg.norm(v)

    def body(v, _):
        w = mv(K.T, mv(K, v))
        nw = jnp.linalg.norm(w)
        return w / nw, nw

    v, norms = jax.lax.scan(body, v, None, length=iters)
    return jnp.sqrt(norms[-1])

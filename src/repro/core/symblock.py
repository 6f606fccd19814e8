"""Symmetric block-matrix formulation (paper Algorithms 1 & 2).

    M = [[0_{m x m}, K       ],
         [K^T,       0_{n x n}]]

is encoded to the accelerator ONCE; every MVM the solver needs is a single
device MVM against M with mode-dependent zero padding / slicing:

    full : w = M @ u                       (Lanczos)
    A@x  : t = K @ x    = (M @ [0; x])[:m]  (dual step)
    AT@y : s = K^T @ y  = (M @ [y; 0])[m:]  (primal step)

``Accel`` abstracts *where* the single MVM runs: exact jnp, noisy-model,
Pallas crossbar kernel, MELISO+ crossbar simulation, or the shard_map
distributed backend.  Each backend only has to provide ``mvm_full``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

MODE_FULL = "full"
MODE_AX = "A@x"
MODE_ATY = "AT@y"

#: Precision of every solver MVM and dot, the Pallas kernels' included.
#: A TPU multiplies f32 operands in one bf16 pass by default, which
#: floors an f32 solve's KKT merit near 1e-3; HIGHEST multiplies at the
#: operand dtype (CPUs and f64 always do).
MVM_PRECISION = jax.lax.Precision.HIGHEST


def kernel_precision():
    """``MVM_PRECISION`` as a Mosaic kernel can lower it, read when the
    kernel is traced (so a change of ``MVM_PRECISION`` reaches compiled
    kernels too): Mosaic contracts at float32 (``HIGHEST``) or in one
    bfloat16 pass (``DEFAULT``), so anything below ``HIGHEST`` is the
    one pass."""
    if MVM_PRECISION == jax.lax.Precision.HIGHEST:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def mv(A, v) -> jnp.ndarray:
    """``A @ v`` at ``MVM_PRECISION``."""
    return jnp.matmul(A, v, precision=MVM_PRECISION)


def dot(u, v) -> jnp.ndarray:
    """``u . v`` at ``MVM_PRECISION``."""
    return jnp.vdot(u, v, precision=MVM_PRECISION)


def build_sym_block(K) -> jnp.ndarray:
    """Algorithm 1 (BUILDSYMBLOCK), host step: M from K (m x n)."""
    K = jnp.asarray(K)
    m, n = K.shape
    top = jnp.concatenate([jnp.zeros((m, m), K.dtype), K], axis=1)
    bot = jnp.concatenate([K.T, jnp.zeros((n, n), K.dtype)], axis=1)
    return jnp.concatenate([top, bot], axis=0)


@dataclasses.dataclass
class Accel:
    """Encoded accelerator handle (result of Algorithm 1 step 2).

    mvm_full: v (m+n,) -> M @ v.  May be stochastic (device noise); the
    caller threads an explicit PRNG key when the backend needs one.
    """

    mvm_full: Callable[..., jnp.ndarray]
    m: int
    n: int
    name: str = "exact"
    # Number of device MVMs issued (host-side bookkeeping for the energy
    # ledger; incremented by matmul_accel).
    stats: Optional[dict] = None

    def __post_init__(self):
        if self.stats is None:
            self.stats = {"mvm_calls": 0}


def encode_exact(K, dtype=None) -> Accel:
    """Reference backend: encode M as a dense jnp array, exact arithmetic."""
    K = jnp.asarray(K, dtype=dtype)
    m, n = K.shape
    M = build_sym_block(K)

    def mvm(v, key=None):
        return mv(M, v)

    return Accel(mvm_full=mvm, m=m, n=n, name="exact")


def encode_noisy(K, noise_apply, dtype=None) -> Accel:
    """Backend with an explicit MVM perturbation model (Assumptions 1-4).

    noise_apply(key, w) -> w_noisy, applied to the exact product. Models
    \\tilde{M} v = M v + zeta with E[zeta] = 0.
    """
    K = jnp.asarray(K, dtype=dtype)
    m, n = K.shape
    M = build_sym_block(K)

    def mvm(v, key=None):
        w = mv(M, v)
        if key is None:
            return w
        return noise_apply(key, w)

    return Accel(mvm_full=mvm, m=m, n=n, name="noisy")


def matmul_accel(accel: Accel, u, mode: str, key=None) -> jnp.ndarray:
    """Algorithm 2 (MATMULACCEL): pad -> single device MVM -> slice."""
    m, n = accel.m, accel.n
    u = jnp.asarray(u)
    if mode == MODE_FULL:
        v = u
    elif mode == MODE_AX:
        v = jnp.concatenate([jnp.zeros((m,), u.dtype), u])
    elif mode == MODE_ATY:
        v = jnp.concatenate([u, jnp.zeros((n,), u.dtype)])
    else:
        raise ValueError(f"unknown mode {mode!r}")
    w = accel.mvm_full(v, key) if key is not None else accel.mvm_full(v)
    accel.stats["mvm_calls"] += 1
    if mode == MODE_FULL:
        return w
    if mode == MODE_AX:
        return w[:m]          # t = K x
    return w[m:]              # s = K^T y


def scaled_accel(accel: Accel, row_scale, col_scale, name=None) -> Accel:
    """Diagonal similarity wrap: M' = D M D with D = diag(row_scale, col_scale).

    Used to evaluate the *preconditioned* operator norm
    ||Sigma^{1/2} K T^{1/2}||_2 without reprogramming the device:
    diag(Sigma^{1/2}, T^{1/2}) M diag(Sigma^{1/2}, T^{1/2}) is exactly the
    symmetric block of Sigma^{1/2} K T^{1/2}.  Host-side vector scaling only
    — consistent with the encode-once constraint.
    """
    d = jnp.concatenate([jnp.asarray(row_scale), jnp.asarray(col_scale)])

    def mvm(v, key=None):
        w = accel.mvm_full(d * v, key) if key is not None else accel.mvm_full(d * v)
        return d * w

    return Accel(
        mvm_full=mvm, m=accel.m, n=accel.n,
        name=name or f"scaled({accel.name})", stats=accel.stats,
    )


def as_dense(accel: Accel) -> np.ndarray:
    """Materialize M by probing (test helper; O(m+n) MVMs)."""
    dim = accel.m + accel.n
    eye = jnp.eye(dim)
    cols = [np.asarray(accel.mvm_full(eye[:, i])) for i in range(dim)]
    return np.stack(cols, axis=1)

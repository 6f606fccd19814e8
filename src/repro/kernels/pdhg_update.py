"""Pallas TPU kernels: fused PDHG vector updates.

Each PDHG half-iteration performs several elementwise passes over the
primal/dual vectors (extrapolation, preconditioned gradient step, box
projection).  Unfused, every pass is an HBM read+write of the full vector;
fused, each vector streams through VMEM exactly once per half-iteration —
a pure memory-roofline win (the vectors are the ONLY per-iteration HBM
traffic once M is device-resident, mirroring the paper's encode-once
design where only vectors move).

primal:  x_new = clip(x − τ·T⊙(c − KTy), lb, ub)
         x_bar = x_new + θ·(x_new − x)           (extrapolation for k+1)
dual:    y_new = y + σ·Σ⊙(b − Kxbar)

Scalars (τ, θ, σ) ride in as (1,1) blocks pinned to block (0,0) — they
are runtime OPERANDS, not compile-time constants, so the carried
``PDHGState.tau``/``sigma`` may change between iterations (the
``strongly_convex`` θ-schedule every step, ``adaptive`` rebalancing at
check boundaries) without retracing or recompiling these kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve_interpret
from .spec import block_spec

BLOCK = 256


def _primal_kernel(x_ref, kty_ref, c_ref, t_ref, lb_ref, ub_ref,
                   tau_ref, theta_ref, xn_ref, xb_ref):
    tau = tau_ref[0, 0]
    theta = theta_ref[0, 0]
    x = x_ref[...]
    step = x - tau * t_ref[...] * (c_ref[...] - kty_ref[...])
    x_new = jnp.clip(step, lb_ref[...], ub_ref[...])
    xn_ref[...] = x_new
    xb_ref[...] = x_new + theta * (x_new - x)


def _dual_kernel(y_ref, kxbar_ref, b_ref, sig_ref, sigma_ref, yn_ref):
    sigma = sigma_ref[0, 0]
    yn_ref[...] = y_ref[...] + sigma * sig_ref[...] * (b_ref[...] - kxbar_ref[...])


def _col(a):
    return a.reshape(-1, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def primal_update_padded(x, kty, c, T, lb, ub, tau, theta, *,
                         interpret: bool | None = None):
    """Inputs are (N, 1) with N % BLOCK == 0; tau/theta are (1, 1).

    ``interpret=None`` auto-detects the backend (interpreted on CPU,
    compiled Mosaic on real TPU) via ``kernels.interpret``."""
    N = x.shape[0]
    assert N % BLOCK == 0
    grid = (N // BLOCK,)
    vec = block_spec((BLOCK, 1), lambda i: (i, 0))
    scl = block_spec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _primal_kernel,
        grid=grid,
        in_specs=[vec, vec, vec, vec, vec, vec, scl, scl],
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((N, 1), x.dtype)] * 2,
        interpret=resolve_interpret(interpret),
    )(x, kty, c, T, lb, ub, tau, theta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dual_update_padded(y, kxbar, b, Sigma, sigma, *,
                       interpret: bool | None = None):
    """Inputs are (M, 1) with M % BLOCK == 0; sigma is (1, 1).

    ``interpret=None`` auto-detects the backend like
    ``primal_update_padded``."""
    M = y.shape[0]
    assert M % BLOCK == 0
    grid = (M // BLOCK,)
    vec = block_spec((BLOCK, 1), lambda i: (i, 0))
    scl = block_spec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _dual_kernel,
        grid=grid,
        in_specs=[vec, vec, vec, vec, scl],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((M, 1), y.dtype),
        interpret=resolve_interpret(interpret),
    )(y, kxbar, b, Sigma, sigma)

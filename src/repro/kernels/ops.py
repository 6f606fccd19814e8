"""Jit'd public wrappers around the Pallas kernels.

Handle padding to tile boundaries and column-vector reshapes.
``interpret=None`` passes through to the kernels, which resolve it in
``kernels.interpret`` (interpreted on CPU, compiled Mosaic on TPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import crossbar_mvm as _xbar
from . import pdhg_update as _upd


def _pad_to(a, mult, axis):
    size = a.shape[axis]
    target = ((size + mult - 1) // mult) * mult
    if target == size:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(a, pad)


def crossbar_mvm(g_pos, g_neg, v, scale, noise, interpret=None):
    """w = scale * (1 + noise) ⊙ ((G+ − G−) @ v)  with arbitrary (R, C).

    noise: per-row multiplicative read-noise sample, shape (R,).
    """
    R, C = g_pos.shape
    gp = _pad_to(_pad_to(g_pos, _xbar.TILE_R, 0), _xbar.TILE_C, 1)
    gn = _pad_to(_pad_to(g_neg, _xbar.TILE_R, 0), _xbar.TILE_C, 1)
    vp = _pad_to(v.reshape(-1, 1), _xbar.TILE_C, 0)
    gain = scale * (1.0 + noise)
    gainp = _pad_to(gain.reshape(-1, 1), _xbar.TILE_R, 0)
    out = _xbar.crossbar_mvm_padded(gp, gn, vp, gainp, interpret=interpret)
    return out[:R, 0]


def primal_update(x, kty, c, T, lb, ub, tau, theta, interpret=None):
    n = x.shape[0]
    cols = [_pad_to(a.reshape(-1, 1), _upd.BLOCK, 0)
            for a in (x, kty, c, T, lb, ub)]
    tau2 = jnp.asarray(tau, x.dtype).reshape(1, 1)
    theta2 = jnp.asarray(theta, x.dtype).reshape(1, 1)
    x_new, x_bar = _upd.primal_update_padded(
        *cols, tau2, theta2, interpret=interpret
    )
    return x_new[:n, 0], x_bar[:n, 0]


def dual_update(y, kxbar, b, Sigma, sigma, interpret=None):
    m = y.shape[0]
    cols = [_pad_to(a.reshape(-1, 1), _upd.BLOCK, 0)
            for a in (y, kxbar, b, Sigma)]
    sig2 = jnp.asarray(sigma, y.dtype).reshape(1, 1)
    out = _upd.dual_update_padded(*cols, sig2, interpret=interpret)
    return out[:m, 0]

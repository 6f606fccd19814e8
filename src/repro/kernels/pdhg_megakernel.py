"""Pallas megakernel: k fused PDHG half-iterations per launch.

The engine's while_loop body runs ``check_every`` half-iterations per
residual check; on small buckets the per-iteration launch/dispatch cost
dominates the two tiny MVMs.  This kernel hoists the whole
check-interval window into ONE ``pallas_call``: operator and iterate
state stay resident in VMEM while a ``fori_loop`` replays the exact
``engine.pdhg_step`` algebra ``n_steps`` times, emitting the final
state plus the ergodic sums the restart block needs.  The residual /
restart check stays OUTSIDE the kernel — ``check_every`` already
delimits the fusion window, so fused and unfused loops visit the same
check points on the same iterates.

Noiseless only (``sigma_read == 0``): per-MVM read-noise keys can't be
split inside the kernel, and the engine only mounts the fused path when
no noise is configured.  Dense operands only: K (m, n) and K^T (n, m)
are whole VMEM blocks feeding MXU matmuls.  With no grid, everything
the window touches lives in VMEM at once, so ``check_vmem`` refuses any
operand set past ``VMEM_BUDGET`` before Mosaic is asked to compile it
(interpreted kernels have no VMEM and take any size).

Vectors travel as (d, 1) columns and scalars as (1, 1) blocks, the
kernel-package convention.  tau/sigma enter as (1, 1) runtime operands
and are RETURNED with the state: the ``strongly_convex`` θ-schedule
updates them inside the window (the in-kernel ``fori_loop`` replays the
same recurrence as ``pdhg_step``), while ``step_rule="adaptive"``
changes them only BETWEEN windows (at check boundaries, in the engine) —
either way the window stays one launch and nothing retraces.

Because the loop advances ``check_every`` half-iterations per launch,
``PDHGResult.iterations`` from any fused or stepped jit path is
quantized to multiples of ``check_every`` — exits are only observed at
check boundaries (see ``engine.mvm_accounting``).  On CPU this runs
interpreted (slow, validation only); the win is compiled Mosaic on a
real accelerator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import symblock
from .interpret import check_kernel_dtype, resolve_interpret

#: VMEM the fused window may plan for.  Calibrated by compiling the
#: kernel for v5e at f32: every shape whose ``vmem_estimate`` is within
#: this compiled (512x1024, 1024x512), while 768x1024, 1024x1024,
#: 512x2048 and 128x4096 ran out of VMEM.
VMEM_BUDGET = 16 << 20
#: Each (d, 1) vector column is lane-padded to (d, 128) in VMEM, and the
#: fused loop keeps about this many of them live per vector length.
_LIVE_COLUMNS = 16
_LANES = 128


def vmem_estimate(m: int, n: int, dtype) -> int:
    """Bytes of VMEM the fused dense window needs for an (m, n) K: both
    operator blocks plus the lane-padded vector columns."""
    isz = jnp.dtype(dtype).itemsize
    return 2 * m * n * isz + _LIVE_COLUMNS * _LANES * (m + n) * isz


def check_vmem(m: int, n: int, dtype) -> None:
    """Refuse an operand set the fused window cannot hold in VMEM."""
    need = vmem_estimate(m, n, dtype)
    if need > VMEM_BUDGET:
        raise ValueError(
            f"megakernel=True cannot fuse a {m}x{n} {jnp.dtype(dtype).name} "
            f"bucket: its window needs ~{need / 2**20:.1f} MiB of VMEM, "
            f"over the kernel's {VMEM_BUDGET / 2**20:.0f} MiB budget; "
            "set PDHGOptions.megakernel=False for buckets this large")


def _run_steps(fwd, adj, b, c, lb, ub, T, Sigma, gamma, n_steps,
               x, x_prev, x_bar, y, tau, sigma):
    """``n_steps`` of engine.pdhg_step on (d, 1) columns, accumulating
    the ergodic sums.  The algebra (order included) mirrors
    ``core.engine.pdhg_step`` exactly — keep the two in sync."""
    init = (x, x_prev, x_bar, y, tau, sigma,
            jnp.zeros_like(x), jnp.zeros_like(y))

    def step(_, carry):
        x, x_prev, x_bar, y, tau, sigma, xs, ys = carry
        Kxbar = fwd(x_bar)
        y_n = y + sigma * Sigma * (b - Kxbar)
        KTy = adj(y_n)
        theta_n = 1.0 / jnp.sqrt(1.0 + 2.0 * gamma * tau)
        x_n = jnp.clip(x - tau * T * (c - KTy), lb, ub)
        x_bar_n = x_n + theta_n * (x_n - x)
        return (x_n, x, x_bar_n, y_n, theta_n * tau, sigma / theta_n,
                xs + x_n, ys + y_n)

    return jax.lax.fori_loop(0, n_steps, step, init)


def _write(outs, results):
    for ref, val in zip(outs, results):
        ref[...] = val.astype(ref.dtype)


def _dense_kernel(K_ref, Ka_ref, b_ref, c_ref, lb_ref, ub_ref, T_ref,
                  S_ref, x_ref, xp_ref, xb_ref, y_ref, tau_ref, sig_ref,
                  *outs, n_steps, gamma):
    K = K_ref[...]
    Ka = Ka_ref[...]
    acc_dt = jnp.promote_types(K.dtype, jnp.float32)

    def mv(M, v):
        return jax.lax.dot_general(
            M, v, dimension_numbers=(((1,), (0,)), ((), ())),
            precision=symblock.kernel_precision(),
            preferred_element_type=acc_dt).astype(v.dtype)

    results = _run_steps(
        lambda v: mv(K, v), lambda v: mv(Ka, v),
        b_ref[...], c_ref[...], lb_ref[...], ub_ref[...],
        T_ref[...], S_ref[...], gamma, n_steps,
        x_ref[...], xp_ref[...], xb_ref[...], y_ref[...],
        tau_ref[...], sig_ref[...])
    _write(outs, results)


def _fused_call(kernel, operands, state_cols, m, n, dt, interpret):
    """Single-program pallas_call: every operand is one whole-array
    block (the megakernel's point is no grid, no HBM round-trips)."""
    out_shape = [
        jax.ShapeDtypeStruct((n, 1), dt),    # x
        jax.ShapeDtypeStruct((n, 1), dt),    # x_prev
        jax.ShapeDtypeStruct((n, 1), dt),    # x_bar
        jax.ShapeDtypeStruct((m, 1), dt),    # y
        jax.ShapeDtypeStruct((1, 1), dt),    # tau
        jax.ShapeDtypeStruct((1, 1), dt),    # sigma
        jax.ShapeDtypeStruct((n, 1), dt),    # x ergodic sum
        jax.ShapeDtypeStruct((m, 1), dt),    # y ergodic sum
    ]
    return pl.pallas_call(
        kernel, out_shape=out_shape, interpret=interpret,
    )(*operands, *state_cols)


def _cols(b, c, lb, ub, T, Sigma, x, x_prev, x_bar, y, tau, sigma, dt):
    col = lambda a: jnp.asarray(a, dt).reshape(-1, 1)  # noqa: E731
    return ([col(a) for a in (b, c, lb, ub, T, Sigma)],
            [col(a) for a in (x, x_prev, x_bar, y)]
            + [jnp.asarray(a, dt).reshape(1, 1) for a in (tau, sigma)])


def _unpack(out, m, n):
    x, x_prev, x_bar, y, tau, sigma, xs, ys = out
    return (x[:, 0], x_prev[:, 0], x_bar[:, 0], y[:, 0],
            tau[0, 0], sigma[0, 0], xs[:, 0], ys[:, 0])


def fused_dense_steps(K, K_adj, b, c, lb, ub, T, Sigma,
                      x, x_prev, x_bar, y, tau, sigma, *,
                      n_steps: int, gamma: float, interpret=None):
    """k fused dense PDHG half-steps; K (m, n), K_adj (n, m).  Returns
    ``(x, x_prev, x_bar, y, tau, sigma, x_sum, y_sum)`` as 1-D/scalars.
    """
    m, n = K.shape
    dt = K.dtype
    check_kernel_dtype(dt, interpret, "the dense megakernel")
    interpret = resolve_interpret(interpret)
    if not interpret:           # interpreted kernels have no VMEM
        check_vmem(m, n, dt)
    vecs, state = _cols(b, c, lb, ub, T, Sigma, x, x_prev, x_bar, y,
                        tau, sigma, dt)
    kernel = functools.partial(_dense_kernel, n_steps=int(n_steps),
                               gamma=float(gamma))
    out = _fused_call(kernel, [K, K_adj] + vecs, state, m, n, dt,
                      interpret)
    return _unpack(out, m, n)

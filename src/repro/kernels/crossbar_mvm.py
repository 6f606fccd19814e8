"""Pallas TPU kernel: tiled differential-pair crossbar MVM.

TPU-native adaptation of the paper's analog MVM (DESIGN.md §2): the
64x64 analog crossbar tile becomes a VMEM block feeding the MXU.  MXU
matmul tiles are 128x128, so we pack a 2x2 grid of logical 64x64
crossbars per block — the BlockSpec index maps are the digital analogue of
the paper's "broadcast input voltages to every tile, sum currents along
grid rows".

    w = gain ⊙ ((G+ − G−) @ v)

  G+/G− : (R, C) normalized conductances, VMEM-tiled (TR, TC) blocks
  v     : (C, 1) input "voltages", tiled (TC, 1), broadcast down each
          block row of the grid (the crossbar input broadcast)
  gain  : (R, 1) per-row output scaling — encodes BOTH the conductance
          scale s and the multiplicative cycle-to-cycle read noise
          (1 + sigma*xi), applied once at the final accumulation step
  accumulation over the column-tile grid dimension = the analog current
  summation along a crossbar grid row.

Grid iteration order on TPU is row-major with the LAST axis innermost, so
for grid (i, j) all column tiles j of a row-block i run back-to-back and
the output block stays resident in VMEM across the accumulation — no
HBM round-trips for partial sums.

A PDHG product needs one off-diagonal block of the programmed
M = [[0, K], [K^T, 0]]: ``read_windows`` names the row and column tiles
each product reads, and ``crossbar_mvm_padded`` walks only those.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import symblock
from .interpret import resolve_interpret
from .spec import block_spec

# 128 = MXU tile edge; each block packs a 2x2 grid of 64x64 crossbars.
TILE_R = 128
TILE_C = 128


def _mvm_kernel(gp_ref, gn_ref, v_ref, gain_ref, out_ref, *, n_col_tiles):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = gp_ref[...] - gn_ref[...]                     # (TR, TC) in VMEM
    # accumulate at least f32 (MXU native), never BELOW the tile dtype —
    # x64 interpret-mode validation must not round through f32 — and
    # multiply at the tile dtype, not one bf16 pass
    part = jax.lax.dot_general(
        g, v_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=symblock.kernel_precision(),
        preferred_element_type=jnp.promote_types(g.dtype, jnp.float32),
    )                                                  # (TR, 1)
    out_ref[...] += part.astype(out_ref.dtype)

    @pl.when(j == n_col_tiles - 1)
    def _finish():
        out_ref[...] *= gain_ref[...]


def tiles(lo: int, hi: int, tile: int) -> Tuple[int, int]:
    """``(first, count)`` of the ``tile``-wide tiles that cover
    ``[lo, hi)``."""
    first = lo // tile
    return first, -(-hi // tile) - first


def read_windows(m: int, n: int):
    """Tile windows ``(rows, cols)`` of the two products against the
    programmed M = [[0, K], [K^T, 0]] of an (m, n) K: the forward read
    returns rows ``[0, m)`` from an input supported on columns
    ``[m, m+n)``, the adjoint rows ``[m, m+n)`` from columns ``[0, m)``.
    Every other tile adds an exact zero or lands in rows nobody keeps."""
    fwd = (tiles(0, m, TILE_R), tiles(m, m + n, TILE_C))
    adj = (tiles(m, m + n, TILE_R), tiles(0, m, TILE_C))
    return fwd, adj


@functools.partial(jax.jit, static_argnames=("rows", "cols", "interpret"))
def crossbar_mvm_padded(g_pos, g_neg, v, gain, *, rows=None, cols=None,
                        interpret: bool | None = None):
    """MVM on tile-aligned inputs: R, C multiples of (TILE_R, TILE_C).

    v: (C, 1); gain: (R, 1).  ``rows`` and ``cols`` are static
    ``(first, count)`` tile windows (``None``: all of them): the grid
    visits only those tiles of the full arrays, through offsets in the
    index maps, so nothing outside the window is copied or read.
    Returns the window's rows, (rows count * TILE_R, 1).
    ``interpret=None`` auto-detects the backend via
    ``kernels.interpret``.
    """
    R, C = g_pos.shape
    assert R % TILE_R == 0 and C % TILE_C == 0, (R, C)
    r0, n_row_tiles = rows or (0, R // TILE_R)
    c0, n_col_tiles = cols or (0, C // TILE_C)
    kernel = functools.partial(_mvm_kernel, n_col_tiles=n_col_tiles)
    return pl.pallas_call(
        kernel,
        grid=(n_row_tiles, n_col_tiles),
        in_specs=[
            block_spec((TILE_R, TILE_C), lambda i, j: (r0 + i, c0 + j)),
            block_spec((TILE_R, TILE_C), lambda i, j: (r0 + i, c0 + j)),
            block_spec((TILE_C, 1), lambda i, j: (c0 + j, 0)),     # v
            block_spec((TILE_R, 1), lambda i, j: (r0 + i, 0)),     # gain
        ],
        out_specs=block_spec((TILE_R, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_row_tiles * TILE_R, 1),
                                       g_pos.dtype),
        interpret=resolve_interpret(interpret),
    )(g_pos, g_neg, v, gain)

"""ELL sparse MVM (an XLA gather), host-side COO->ELL conversion, and
the rule that turns a small ELL bucket into a dense operator.

A COO product is a scatter-add over (nnz,) gathers, which XLA CPU
serializes, and so is every Ruiz/Pock-Chambolle reduction on COO.  ELL
(ELLPACK) trades a bounded amount of padding for fully vectorized
row-major access:

    data (m, W) float   row i's nonzero values, zero-padded to width W
    cols (m, W) int32   matching column indices (padding points at 0)

so one MVM is a dense gather + axis-1 reduction,

    w[i] = sum_j data[i, j] * v[cols[i, j]]

with no scatter anywhere.  Padding entries carry data == 0, so whatever
``cols`` says for them (index 0 by convention) contributes nothing.

``ell_matvec`` is a plain XLA gather and axis-1 sum on every backend.
(A row-blocked Pallas form of the same gather does not compile for TPU:
Mosaic accepts only 2-D gathers.)  On a TPU that gather moves about
0.1 G slots/s, three orders of magnitude below a dense matvec, so the
ELL bucket program (``runtime.batch.make_ell_bucket_pipeline``) keeps
the gather only where the dense form is too large: ``ell_goes_dense``
decides from the bucket's static shapes, and a bucket it sends dense is
scattered once per solve into its (m, n) K (``ell_to_dense``) and
multiplied on the MXU from then on.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

# Smallest ELL width bucket (widths bucket to powers of two).
MIN_ELL_WIDTH = 4
#: Dense elements one ELL slot is worth at equal time, by operand
#: itemsize: a bucket's dense form wins while m*n <= R * (m*wf + n*wa).
#: Below the break-even ``tools/ell_crossover.py`` measured on a TPU v5e
#: from 256x512 up (float32 191-739, float64 11.5-24.8; PERF.md).  Below
#: that size both forms cost a launch's fixed time, and dense is ahead.
DENSE_ELEMENTS_PER_SLOT = {4: 64, 8: 8}
#: Largest dense bucket operator, B*m*n*itemsize bytes: 1/32 of a v5e's
#: 16 GiB of HBM.  The compiled bucket program holds about 3x that in
#: float32 temporaries and 17x in float64, whose products are emulated.
DENSE_OPERATOR_MAX_BYTES = 512 << 20


# ------------------------------------------------------ host conversion ---

def ell_width_bucket(width: int, min_size: int = MIN_ELL_WIDTH) -> int:
    """Round an ELL width up to its power-of-two bucket so repeat sparse
    traffic with drifting row occupancy reuses compiled executables."""
    return max(min_size, 1 << (max(int(width), 1) - 1).bit_length())


def coo_row_widths(row, col, data, shape: Tuple[int, int]) -> Tuple[int, int]:
    """(max nonzeros per row, max nonzeros per column) of a COO triplet,
    counting only true nonzeros — explicit zeros (nnz padding at (0, 0)
    included) never widen the ELL form."""
    data = np.asarray(data).reshape(-1)
    keep = data != 0
    row = np.asarray(row).reshape(-1)[keep]
    col = np.asarray(col).reshape(-1)[keep]
    m, n = shape
    wf = int(np.bincount(row, minlength=max(m, 1)).max()) if m else 0
    wa = int(np.bincount(col, minlength=max(n, 1)).max()) if n else 0
    return wf, wa


def ell_from_coo(data, row, col, shape: Tuple[int, int],
                 width: Optional[int] = None):
    """Host-side COO -> ELL conversion (numpy).

    Drops explicit zero entries first (they carry no information and
    would only widen rows), then packs each row's nonzeros
    left-justified in column-sorted order.  Returns ``(ell_data (m, W),
    ell_cols (m, W) int32)`` with ``W = width`` (must cover the widest
    row) or the exact max row width when ``width`` is None.  Rows with
    no nonzeros — including every row of an all-zero K — come back fully
    padded (data 0, cols 0), which the matvec treats as inert.
    """
    m, n = int(shape[0]), int(shape[1])
    data = np.asarray(data).reshape(-1)
    keep = data != 0
    data = data[keep]
    row = np.asarray(row, np.int64).reshape(-1)[keep]
    col = np.asarray(col, np.int64).reshape(-1)[keep]
    order = np.lexsort((col, row))
    data, row, col = data[order], row[order], col[order]
    counts = np.bincount(row, minlength=max(m, 1))[:max(m, 1)]
    w_need = int(counts.max()) if m else 0
    W = w_need if width is None else int(width)
    assert W >= w_need, (W, w_need)
    ell_data = np.zeros((m, W), data.dtype)
    ell_cols = np.zeros((m, W), np.int32)
    if data.size:
        # position of each entry within its row (entries are row-sorted)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
        pos = np.arange(data.size) - np.repeat(starts, counts)
        ell_data[row, pos] = data
        ell_cols[row, pos] = col
    return ell_data, ell_cols


# ------------------------------------------------------- operator choice ---

def ell_goes_dense(B: int, m: int, n: int, wf: int, wa: int,
                   itemsize: int) -> bool:
    """Whether an ELL bucket of ``B`` lanes, shape (m, n) and width
    buckets (wf, wa) multiplies by a dense K instead of gathering: its
    dense form is cheaper per iteration than its slots and fits under
    ``DENSE_OPERATOR_MAX_BYTES``.  A pure function of static shapes, so
    every process decides alike and the executable cache key holds it."""
    r = DENSE_ELEMENTS_PER_SLOT.get(int(itemsize), 0)
    return (m * n <= r * (m * wf + n * wa)
            and B * m * n * itemsize <= DENSE_OPERATOR_MAX_BYTES)


def ell_to_dense(data, cols, n: int):
    """The dense (m, n) matrix of one ELL layout: one scatter-add, in
    which padding slots (data 0) add nothing."""
    rows = jnp.arange(data.shape[0])[:, None]
    return jnp.zeros((data.shape[0], n), data.dtype).at[rows, cols].add(data)


# ----------------------------------------------------------------- matvec ---

def ell_matvec(data, cols, v):
    """``w = ELL(data, cols) @ v``: one (m, W) gather + one axis-1
    reduction; no scatter."""
    if data.shape[1] == 0:
        return jnp.zeros(data.shape[0], v.dtype)
    return jnp.sum(data * jnp.take(v, cols, axis=0), axis=1)

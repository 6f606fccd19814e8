"""The plain reference of the simulated crossbar: what one analog read
returns, what programming targets, and what the energy ledger charges.

Nothing here imports the program.  The rules are the ones its
docstrings state (``kernels/ops.py`` ``crossbar_mvm``,
``crossbar/encode.py`` ``encode_core`` and ``charge_write``,
``crossbar/solver.py`` ``_charge_reads``), written again in numpy:

* a read of the differential pair ``(G+, G-)`` with conductance scale
  ``s`` and per-row read-noise sample ``xi`` returns
  ``s * (1 + xi) * ((G+ - G-) @ v)``;
* programming targets ``G+ = round(max(W, 0) / s * (L - 1)) / (L - 1)``
  and ``G- = round(max(-W, 0) / s * (L - 1)) / (L - 1)`` with
  ``s = max |W|`` (1 for an all-zero W) and ``L`` conductance levels;
* the ledger: a nonzero-target pair takes the write-verify train of
  ``avg_write_pulses`` on each of its two cells, a zero-target one a
  single RESET pulse per cell, a padding pair (outside the logical
  operator) is zero-target and is ledgered apart as well; tiles program
  in parallel, cells within a tile one after another; every read draws
  read energy from each active cell (two per nonzero pair) and takes one
  read latency.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: rows per block of ``read`` (bounds the float64 temporaries)
READ_BLOCK = 512


def read(g_pos, g_neg, scale, noise, v) -> np.ndarray:
    """One analog read in float64: ``scale * (1 + noise) * ((G+ - G-) @ v)``.

    ``g_pos``/``g_neg`` are (..., R, C), ``v`` (..., C), ``noise``
    (..., R) and ``scale`` (...); leading axes are lanes.  Rows are taken
    ``READ_BLOCK`` at a time."""
    gp = np.asarray(g_pos, np.float64)
    gn = np.asarray(g_neg, np.float64)
    v = np.asarray(v, np.float64)
    gain = (np.asarray(scale, np.float64)[..., None]
            * (1.0 + np.asarray(noise, np.float64)))
    out = np.empty(gp.shape[:-1])
    for r0 in range(0, gp.shape[-2], READ_BLOCK):
        r1 = r0 + READ_BLOCK
        out[..., r0:r1] = np.einsum("...rc,...c->...r",
                                    gp[..., r0:r1, :] - gn[..., r0:r1, :], v)
    return gain * out


def quantize(W, levels: int):
    """Programming targets ``(G+, G-, scale)`` of ``W`` before any
    programming error.  Computed in ``W``'s own dtype, as the program
    computes them: which of two levels a cell lands on when ``|W| / s``
    sits within a rounding of a half step depends on the last bit."""
    W = np.asarray(W)
    raw = np.max(np.abs(W))
    scale = raw if raw > 0 else W.dtype.type(1.0)
    top = W.dtype.type(levels - 1)

    def q(g):
        return np.round(g * top) / top

    return (q(np.maximum(W, 0) / scale), q(np.maximum(-W, 0) / scale),
            scale)


def programmed_pairs(W, levels: int) -> int:
    """Differential pairs of ``W`` whose quantized target is nonzero."""
    gp, gn, _ = quantize(W, levels)
    return int(np.count_nonzero((gp > 0) | (gn > 0)))


def reads(executed_iterations: int, check_every: int,
          lanczos_iters: int) -> int:
    """Analog reads charged for ``executed_iterations`` PDHG iterations
    summed over a lane's analog solves: the norm estimate's reads, two
    a iteration, and four at each check.  Every solve runs whole windows
    of ``check_every`` iterations, so the checks over all solves are
    ``executed_iterations // check_every``."""
    return (lanczos_iters + 2 * executed_iterations
            + 4 * (executed_iterations // check_every))


def ledger(device: Dict[str, float], nz: int, pairs_logical: int,
           pairs_total: int, n_reads: int) -> Dict[str, float]:
    """The ledger of one programmed array read ``n_reads`` times.

    ``device`` holds the configuration's ``device_model``; ``nz`` the
    nonzero-target pairs, ``pairs_logical`` the pairs of the operator
    itself and ``pairs_total`` those of the tile-padded array."""
    replicas = max(1, int(device["ecc"]))
    pulses = float(device["avg_write_pulses"])
    e_pulse = float(device["write_pulse_energy_j"])
    fill = nz / pairs_total
    pulses_logical = nz * 2 * pulses + (2 * pairs_logical - 2 * nz)
    pulses_padding = 2.0 * (pairs_total - pairs_logical)
    cells_per_tile = 2 * int(device["crossbar_rows"]) * int(
        device["crossbar_cols"])
    active_cells = 2.0 * pairs_total * fill * replicas
    return {
        "write_energy_j": replicas * (pulses_logical + pulses_padding)
        * e_pulse,
        "write_energy_padding_j": pulses_padding * e_pulse,
        "write_latency_s": cells_per_tile * (fill * pulses + (1.0 - fill))
        * float(device["write_pulse_latency_s"]),
        "read_energy_j": n_reads * active_cells
        * float(device["read_energy_per_cell_j"]),
        "read_latency_s": n_reads * float(device["read_latency_s"]),
        "mvm_count": n_reads,
        "cells_written": replicas * 2 * pairs_total,
        "cells_written_padding": 2 * (pairs_total - pairs_logical),
    }

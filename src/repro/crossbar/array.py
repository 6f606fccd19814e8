"""Distributed crossbar array: the logical analog accelerator (paper §6).

A grid of crossbar tiles holds the encoded symmetric block M.  One logical
MVM = broadcast input slices to every tile, each tile's analog MVM runs in
parallel, partial currents are summed along grid rows — no matrix movement,
no reprogramming.  This module provides:

  * ``CrossbarArray``    — the device-physics simulation (quantization,
                           programming error, cycle-to-cycle read noise,
                           energy/latency ledger).
  * ``crossbar_accel``   — an ``Accel`` factory so Algorithm 2-4 run on it
                           unchanged.
  * ``analog_linear``    — drop-in noisy/quantized linear op for arbitrary
                           dense layers (ties the paper's substrate to the
                           assigned LM architectures for inference demos).

The analog math itself is delegated to the Pallas crossbar kernel
(`repro.kernels.ops.crossbar_mvm`) when ``use_kernel=True``, or to the
pure-jnp reference implementation otherwise — both are validated against
each other in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.symblock import Accel, build_sym_block, mv
from .device import DeviceModel, EPIRAM
from .encode import EncodedMatrix, encode_matrix
from .energy import Ledger


@dataclasses.dataclass
class CrossbarArray:
    enc: EncodedMatrix
    ledger: Ledger
    device: DeviceModel
    use_kernel: bool = False
    # base key for keyless ``mvm`` read noise, derived from the
    # programming key so the whole device history is one seed
    read_key: Optional[jax.Array] = None

    @classmethod
    def program(
        cls,
        W,
        device: DeviceModel = EPIRAM,
        key: Optional[jax.Array] = None,
        ledger: Optional[Ledger] = None,
        use_kernel: bool = False,
    ) -> "CrossbarArray":
        if key is None:
            # reproducible default: programming a crossbar with no key
            # must yield the same conductances every run
            key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
        ledger = ledger if ledger is not None else Ledger()
        enc = encode_matrix(W, device, key, ledger=ledger)
        return cls(enc=enc, ledger=ledger, device=device,
                   use_kernel=use_kernel,
                   read_key=jax.random.fold_in(key, 0x52454144))

    def mvm(self, v, key: Optional[jax.Array] = None) -> jnp.ndarray:
        """One logical analog MVM: w = W @ v with device non-idealities."""
        dev = self.device
        enc = self.enc
        R, C = enc.g_pos.shape
        vp = jnp.zeros((C,), enc.g_pos.dtype).at[: enc.cols].set(
            jnp.asarray(v, enc.g_pos.dtype))
        if key is None:
            # fold the MVM count into the programming-derived read key:
            # cycle-to-cycle read noise must differ per call (a fixed
            # fallback key used to replay the SAME noise sample on every
            # keyless MVM, silently correlating whole solves)
            base = self.read_key
            if base is None:
                base = jax.random.PRNGKey(0)  # jaxlint: disable=R2
            key = jax.random.fold_in(base, self.ledger.mvm_count)
        if self.use_kernel:
            from ..kernels import ops as kops
            noise = dev.sigma_read * jax.random.normal(key, (R,), vp.dtype)
            w = kops.crossbar_mvm(enc.g_pos, enc.g_neg, vp, enc.scale, noise)
        else:
            w = mv(enc.g_pos - enc.g_neg, vp) * enc.scale
            w = w * (1.0 + dev.sigma_read
                     * jax.random.normal(key, w.shape, w.dtype))
        # ledger: all tiles fire in parallel -> one read latency quantum;
        # energy scales with ACTIVE cells (zero conductances draw ~none).
        self.ledger.read_latency_s += dev.read_latency_s
        self.ledger.read_energy_j += (dev.read_energy_per_cell_j
                                      * enc.active_cells)
        self.ledger.mvm_count += 1
        return w[: enc.rows]


def crossbar_accel_factory(
    device: DeviceModel = EPIRAM,
    key: Optional[jax.Array] = None,
    ledger: Optional[Ledger] = None,
    use_kernel: bool = False,
):
    """Returns an ``accel_factory`` for ``core.pdhg.solve``: K -> Accel.

    Encodes the symmetric block M = [[0, K], [K^T, 0]] ONCE (Algorithm 1);
    every subsequent Algorithm-2 call is a read-only analog MVM.
    """
    led = ledger if ledger is not None else Ledger()

    def factory(K) -> Accel:
        M = build_sym_block(K)
        arr = CrossbarArray.program(
            M, device=device, key=key, ledger=led, use_kernel=use_kernel
        )
        m, n = K.shape

        def mvm(v, key=None):
            return arr.mvm(v, key=key)

        acc = Accel(mvm_full=mvm, m=m, n=n, name=f"crossbar:{device.name}")
        acc.ledger = led          # exposed for the benchmark harness
        acc.array = arr
        return acc

    factory.ledger = led
    return factory


def analog_linear(x, W, device: DeviceModel = EPIRAM, key=None):
    """Noisy/quantized linear op  x @ W^T  through the crossbar model.

    A convenience for running *inference* of the assigned LM architectures
    through the paper's device substrate (weights encoded once; activations
    stream).  Not used in training (the technique is inapplicable there;
    see DESIGN.md §Arch-applicability).
    """
    if key is None:
        # reproducible inference-demo default (weights + activations
        # share one seed; pass a key to decorrelate runs)
        key = jax.random.PRNGKey(0)  # jaxlint: disable=R2
    arr = CrossbarArray.program(jnp.asarray(W), device=device, key=key)
    xs = jnp.atleast_2d(x)
    k = jax.random.split(key, xs.shape[0])
    out = jnp.stack([arr.mvm(xi, key=ki) for xi, ki in zip(xs, k)])
    return out.reshape((*x.shape[:-1], W.shape[0]))

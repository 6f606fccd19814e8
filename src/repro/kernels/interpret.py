"""Shared interpret-mode auto-detection for every Pallas entry point.

One rule for the whole kernel package: interpreted on CPU (the
container's validation mode), compiled Mosaic on a real TPU backend.
Both the high-level ``ops`` wrappers and the low-level ``*_padded``
kernels default through here, so a real-TPU caller of either API never
silently runs interpreted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret) -> bool:
    """``None`` -> backend auto-detection; anything else passes through."""
    return interpret_default() if interpret is None else bool(interpret)


def check_kernel_dtype(dtype, interpret, kernel: str) -> None:
    """Refuse 64-bit operands for a compiled kernel.

    Mosaic rejects f64 inside a TPU kernel (``X64 element types`` in
    ``tpu_custom_call``); interpreted kernels take any dtype.  The solve
    dtype is ``PDHGOptions.dtype``, so that is the option to change.
    """
    dt = jnp.dtype(dtype)
    if dt.itemsize > 4 and not resolve_interpret(interpret):
        raise ValueError(
            f"{kernel} is a compiled Pallas kernel and TPUs take no "
            f"{dt.name} inside one; set PDHGOptions.dtype=np.float32 or "
            "use kernel='jnp'")

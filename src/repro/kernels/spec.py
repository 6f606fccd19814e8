"""BlockSpec construction shared by every gridded Pallas kernel.

Mosaic compiles index maps whose results are 32-bit.  A map that returns
Python ints yields 64-bit indices once ``jax_enable_x64`` is on (the CLI
and the benchmarks turn it on), and Mosaic then refuses the kernel with
``failed to legalize operation 'func.return'``.  ``block_spec`` casts
every index the map returns to int32, so the kernels compile the same
with x64 on or off.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl


def block_spec(block_shape, index_map) -> pl.BlockSpec:
    """``pl.BlockSpec`` whose index map returns int32 block indices."""

    def index_map_i32(*grid_idx):
        return tuple(jnp.asarray(i, jnp.int32) for i in index_map(*grid_idx))

    return pl.BlockSpec(block_shape, index_map_i32)

"""Least bytes the crossbar reads of one instance must move, from the
instance alone.

Each PDHG product reads the conductance pair (G+ and G-) of the block
it needs, K for the forward product and K^T for the adjoint: 2 * m * n
cells of the instance's logical m x n, whatever the tiles, buckets or
lanes it rides in.  The products per iteration are
``bench.kernels.products_per_iteration``.  This count is the yardstick
of ``xbar_hbm_share.stream`` and does not follow the program's own
accounting.
"""
from bench.kernels import products_per_iteration


def read_bytes(m: int, n: int, iterations: int, check_every: int,
               itemsize: int) -> float:
    """Bytes of conductances read over ``iterations`` PDHG iterations of
    an m x n instance."""
    return (iterations * products_per_iteration(check_every)
            * 2.0 * m * n * itemsize)

"""Least work of one PDHG iteration, from the instance alone.

One iteration multiplies by K and by K^T; every ``check_every``
iterations the restart check multiplies four more times (K and K^T at
the current and at the averaged iterate).  These counts are the
yardstick of the kernel metrics and do not follow the program's own
accounting.
"""


def products_per_iteration(check_every: int) -> float:
    return 2.0 + 4.0 / check_every


def sparse_iteration_nnz(nnz: float, check_every: int) -> float:
    """Logical nonzeros multiplied in one sparse iteration."""
    return products_per_iteration(check_every) * nnz

"""What the benchmark hands the program: its options and its inputs.

The program sees only ``StandardLP``s in the configuration's dtype; the
known optimum stays with the benchmark.
"""
from __future__ import annotations

import numpy as np

from bench.gen.lp import Instance


def options(config: dict):
    """``PDHGOptions`` as the configuration states them.  The solver's
    own seed (its random starting point) is the configuration's
    ``pdhg_seed``, the same for every run seed, so that each run does the
    same work."""
    from repro.core.pdhg import PDHGOptions

    return PDHGOptions(max_iters=int(config["max_iters"]),
                       tol=float(config["tol"]),
                       check_every=int(config["check_every"]),
                       dtype=np.dtype(config["dtype"]),
                       seed=int(config["pdhg_seed"]))


def to_program(inst: Instance, dtype) -> object:
    """The instance as a host ``StandardLP`` in ``dtype``: dense K, or a
    ``SparseCOO`` of its nonzeros."""
    from repro.lp.problem import SparseCOO, StandardLP

    m, n = inst.shape
    if inst.K is not None:
        K = inst.K.astype(dtype)
    else:
        data, row, col = inst.coo
        K = SparseCOO(data.astype(dtype), row, col, (m, n))
    return StandardLP(c=inst.c.astype(dtype), K=K, b=inst.b.astype(dtype),
                      lb=inst.lb.astype(dtype), ub=inst.ub.astype(dtype),
                      name=inst.name)

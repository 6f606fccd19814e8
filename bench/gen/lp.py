"""Standard-form LPs ``min c@x  s.t.  K x = b, lb <= x <= ub`` built
around a known optimum, in the shapes of arXiv:2509.21137's Table 1.

Copied from the program's ``repro.lp.random_inequality_lp_known``,
``LPProblem.to_standard`` and ``table1_instance``, with two changes:
they draw from a ``numpy.random.Generator`` handed in, so one pool seed
yields many independent instances, and G is rounded to the dtype the
program is given before h and c are built from it, so the known optimum
is exact for the matrix the program sees.

The construction (KKT by hand) for ``min c@x  s.t.  G x >= h,
0 <= x <= box``: put each coordinate of ``x*`` at its lower bound, at
its upper bound or inside; make ``min(m, n // 2)`` rows tight at ``x*``
with multipliers ``y > 0`` and the others slack with ``y = 0``; give the
coordinates at a bound positive bound multipliers; then
``c = G^T y + lam_l - lam_u`` makes ``x*`` optimal.  Standard form adds
one slack per row: ``K = [G, -I]``, ``b = h``, ``c = [c, 0]``,
``ub = [box, ..., inf, ...]``; the optimal objective carries over.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Instance:
    """One standard-form LP and its known optimum, in float64 on the host.

    ``K`` is the dense (m, n) matrix; ``coo`` is ``(data, row, col)`` of
    its nonzeros, for payloads that hand the program a sparse K.
    """

    name: str
    shape: Tuple[int, int]
    c: np.ndarray
    b: np.ndarray
    lb: np.ndarray
    ub: np.ndarray
    x_opt: np.ndarray
    y_opt: np.ndarray
    obj_opt: float
    K: Optional[np.ndarray] = None
    coo: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None

    def matvec(self, x):
        """``K @ x`` in float64."""
        x = np.asarray(x, np.float64)
        if self.K is not None:
            return self.K @ x
        data, row, col = self.coo
        return np.bincount(row, weights=data * x[col],
                           minlength=self.shape[0])

    def rmatvec(self, y):
        """``K.T @ y`` in float64."""
        y = np.asarray(y, np.float64)
        if self.K is not None:
            return self.K.T @ y
        data, row, col = self.coo
        return np.bincount(col, weights=data * y[row],
                           minlength=self.shape[1])

    @property
    def nnz(self) -> int:
        if self.K is not None:
            return int(np.count_nonzero(self.K))
        return int(self.coo[0].size)


def table1_lp(name: str, m: int, n: int, density: float, box: float,
              rng: np.random.Generator, dtype=np.float32) -> Instance:
    """An inequality-form LP with ``m`` rows over ``n`` boxed variables
    and a known optimum, in standard form (shape ``m x (n + m)``).
    Below ``density`` 1 each row keeps a random share of its entries, at
    least two."""
    G = rng.normal(size=(m, n))
    if density < 1.0:
        mask = rng.random((m, n)) < density
        mask[np.arange(m), rng.integers(0, n, m)] = True
        mask[np.arange(m), rng.integers(0, n, m)] = True
        G = G * mask
    G = G.astype(dtype).astype(np.float64)
    kind = rng.choice(3, size=n, p=[0.3, 0.3, 0.4])   # lb, ub, interior
    x_opt = np.where(kind == 0, 0.0,
                     np.where(kind == 1, box,
                              rng.uniform(0.2 * box, 0.8 * box, n)))
    n_active = min(m, max(1, n // 2))
    active = rng.choice(m, size=n_active, replace=False)
    Gx = G @ x_opt
    h = Gx - rng.uniform(0.5, 2.0, size=m)
    h[active] = Gx[active]
    y = np.zeros(m)
    y[active] = rng.uniform(0.1, 1.0, size=n_active)
    lam_l = np.where(kind == 0, rng.uniform(0.1, 1.0, n), 0.0)
    lam_u = np.where(kind == 1, rng.uniform(0.1, 1.0, n), 0.0)
    c = G.T @ y + lam_l - lam_u
    K = np.concatenate([G, -np.eye(m)], axis=1)
    return Instance(
        name=name, shape=(m, n + m),
        c=np.concatenate([c, np.zeros(m)]), b=h,
        lb=np.zeros(n + m),
        ub=np.concatenate([np.full(n, box), np.full(m, np.inf)]),
        x_opt=np.concatenate([x_opt, Gx - h]), y_opt=y,
        obj_opt=float(c @ x_opt), K=K)


def as_coo(inst: Instance) -> Instance:
    """The same instance with K handed over as its nonzeros."""
    row, col = np.nonzero(inst.K)
    data = inst.K[row, col]
    return dataclasses.replace(
        inst, K=None, coo=(data, row.astype(np.int32), col.astype(np.int32)))


FAMILIES = {"table1_known_optimum": table1_lp}

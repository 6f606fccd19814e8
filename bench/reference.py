"""The plain reference: every answer solved in the window, checked on
the host in float64 against its instance's known optimum.

Nothing here imports the program.  An answer is the primal-dual pair
``(x, y)`` and the status the program returned for one instance.  For
each answer this computes

* ``obj_rel_err``  ``|c@x - c@x*| / |c@x*|`` against the generator's optimum;
* ``x_rel_err``    ``||x - x*|| / ||x*||``;
* ``y_rel_err``    ``||y - y*|| / ||y*||``;
* ``kkt``          the relative KKT error of ``(x, y)`` on the instance as
  given, with reduced costs ``r = c - K^T y`` priced against the bounds
  ``lb <= x <= ub``: the largest of ``||Kx - b|| / (1 + ||b||)``, the
  bound violation ``||x - clip(x, lb, ub)|| / (1 + ||x||)``, the dual
  violation ``||min(r, 0)||`` over coordinates without an upper bound
  over ``1 + ||c||``, and the gap ``|c@x - d| / (1 + |c@x| + |d|)`` with
  the dual objective ``d = b@y + lb@max(r, 0) - ub@max(-r, 0)``
  (bounds that are infinite drop out);

and a run's numbers are the largest over its answers, beside the count
of answers that are missing or whose status is not ``optimal``.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from bench.gen.lp import Instance

ANSWER_NUMBERS = ("obj_rel_err", "x_rel_err", "y_rel_err", "kkt")
_FLOAT_PRODUCT = re.compile(
    r"= (f16|bf16|f32|f64)\[[^\]]*\]\S* (dot|convolution)\(")


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def _bound_price(bound, r):
    """``sum(bound * r)`` over the finite bounds."""
    finite = np.isfinite(bound)
    return float(bound[finite] @ r[finite])


def answer_numbers(inst: Instance, x, y) -> Dict[str, float]:
    """The reference's numbers for one answer ``(x, y)`` to ``inst``."""
    m, n = inst.shape
    x = np.asarray(x, np.float64).reshape(-1)
    y = np.asarray(y, np.float64).reshape(-1)
    if x.shape != (n,) or y.shape != (m,):
        raise ValueError(f"{inst.name}: answer shapes {x.shape}, {y.shape} "
                         f"for an LP of shape {(m, n)}")
    if not (np.all(np.isfinite(x)) and np.all(np.isfinite(y))):
        return {k: float("inf") for k in ANSWER_NUMBERS}
    r = inst.c - inst.rmatvec(y)
    pobj = float(inst.c @ x)
    dobj = (float(inst.b @ y) + _bound_price(inst.lb, np.maximum(r, 0.0))
            - _bound_price(inst.ub, np.maximum(-r, 0.0)))
    r_pri = np.linalg.norm(inst.matvec(x) - inst.b) / (
        1.0 + np.linalg.norm(inst.b))
    r_bound = np.linalg.norm(x - np.clip(x, inst.lb, inst.ub)) / (
        1.0 + np.linalg.norm(x))
    r_dual = np.linalg.norm(np.minimum(r, 0.0)[~np.isfinite(inst.ub)]) / (
        1.0 + np.linalg.norm(inst.c))
    r_gap = abs(pobj - dobj) / (1.0 + abs(pobj) + abs(dobj))
    return {
        "obj_rel_err": abs(pobj - inst.obj_opt) / max(abs(inst.obj_opt),
                                                      1e-12),
        "x_rel_err": _rel(x, inst.x_opt),
        "y_rel_err": _rel(y, inst.y_opt),
        "kkt": float(max(r_pri, r_bound, r_dual, r_gap)),
    }


def judge(pairs: Iterable[Tuple[Instance, Optional[dict]]],
          limits: Dict[str, float]) -> Tuple[Dict[str, float], List[dict]]:
    """Check every (instance, answer) pair; ``answer`` is ``None`` when the
    program gave none.  Returns the run's numbers (largest per answer
    number, plus the ``not_optimal`` and ``missing`` counts) and one
    record per answer with ``ok`` set when it meets every limit."""
    worst = {k: 0.0 for k in ANSWER_NUMBERS}
    counts = {"missing": 0, "not_optimal": 0}
    records = []
    for inst, ans in pairs:
        if ans is None:
            counts["missing"] += 1
            records.append({"name": inst.name, "ok": False,
                            "status": "missing"})
            continue
        nums = answer_numbers(inst, ans["x"], ans["y"])
        optimal = ans["status"] == "optimal"
        counts["not_optimal"] += int(not optimal)
        for k, v in nums.items():
            worst[k] = max(worst[k], v)
        ok = optimal and all(v <= limits[k] for k, v in nums.items()
                             if k in limits)
        records.append({"name": inst.name, "ok": ok,
                        "status": ans["status"], **nums})
    numbers = {**counts, **worst}
    return numbers, records


def dots_below_highest(hlo_texts: Iterable[str]) -> int:
    """Floating-point matrix products (``dot`` or ``convolution``) in the
    compiled programs' HLO whose operands are not multiplied at the
    highest precision.  A product the compiler turned into an elementwise
    multiply and a reduction runs in its dtype and is not counted."""
    count = 0
    for text in hlo_texts:
        for line in text.splitlines():
            if _FLOAT_PRODUCT.search(line) and \
                    "operand_precision={highest,highest}" not in line:
                count += 1
    return count


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each compared number beside its limit, in the limits' order."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())

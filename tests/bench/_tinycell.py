"""The benchmark's cells driven through the harness without its look for
a chip, one pool call each: the Table-1 instances are small enough for
a CPU test run at the cells' own sizes."""
import contextlib
import json
import time

import _benchpath  # noqa: F401

from bench import harness, reference, spec


def tiny_cell(name: str, max_iters: int = 40000) -> spec.Cell:
    cell = spec.resolve(spec.load_benchmark(), name)
    cell.config = dict(cell.config, max_iters=max_iters)
    cell.mix = dict(cell.mix, distinct_calls=1)
    return cell


def peaks() -> dict:
    with open(harness.PEAKS) as f:
        return json.load(f)["devices"]["TPU v5 lite"]


def run(cell: spec.Cell, seed: int = 7, entry=None):
    """One short run of ``cell`` on the CPU: (correct, numbers, run)."""
    import jax

    r = harness.run_cell(cell, seed, 0.05, False, time.perf_counter(),
                         peaks(), entry=entry)
    checks = harness.check(r)
    numbers = {k: v["value"] for k, v in checks.items()}
    jax.clear_caches()
    return reference.is_correct(checks), numbers, r


@contextlib.contextmanager
def patched(module, name, value):
    import jax

    old = getattr(module, name)
    setattr(module, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, old)
        jax.clear_caches()

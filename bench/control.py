"""The control: the program with its products computed one precision
below what the configuration states, which every cell's check must
refuse; and the sound readings the check's limits are set between.

    python bench/control.py --workload <cell> --seeds 11,12,13 --seconds 8 \
        [--variants program,bfloat16,high] [--out FILE]

Runs, in one process that owns the chip, each seed through the cell's
own set-up, warm-up and a short window, once per variant, and prints one
JSON line per (variant, seed) with every number the reference computes
(the largest over the window's answers) and the run's verdict.  The
benchmark's own runs never run this.

Variants:

* ``program``: the program as it is;
* ``high``: the control of a configuration that states float32 products
  at ``HIGHEST``: the program's own matrix-product precision
  (``MVM_PRECISION``) switched to ``Precision.HIGH``, three one-pass
  bfloat16 products;
* ``bfloat16``: the control of float32 products that are not matrix
  products (the ELL gather-multiply): the operands of every solver
  product, dot and ELL gather-multiply rounded to bfloat16, the sums
  kept in float32.

The configuration's ``control`` names the variant for each storage of K.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

# the solver's product helpers, and every module that imported them by name
_TARGETS = {
    "mv": ("repro.core.symblock", "repro.core.engine", "repro.core.lanczos",
           "repro.core.pdhg"),
    "dot": ("repro.core.symblock", "repro.core.residuals",
            "repro.core.lanczos"),
    "ell_matvec": ("repro.kernels.sparse_mvm", "repro.runtime.batch"),
}


def _mm(a, b):
    import jax
    import jax.numpy as jnp

    # bfloat16 operands, float32 sums: exact products, one pass
    return jnp.matmul(a, b, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


def mv_bf16(A, v):
    return _mm(A.astype("bfloat16"), v.astype("bfloat16")).astype(A.dtype)


def dot_bf16(u, v):
    import jax.numpy as jnp

    return mv_bf16(jnp.reshape(u, (1, -1)), v.reshape(-1))[0]


def ell_matvec_bf16(data, cols, v):
    import jax.numpy as jnp

    if data.shape[1] == 0:
        return jnp.zeros(data.shape[0], v.dtype)
    d = data.astype(jnp.bfloat16).astype(jnp.float32)
    g = jnp.take(v, cols, axis=0).astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.sum(d * g, axis=1).astype(v.dtype)


_REPLACEMENTS = {
    "bfloat16": {"mv": mv_bf16, "dot": dot_bf16,
                 "ell_matvec": ell_matvec_bf16},
}
# the modules that read the program's matrix-product precision
_PRECISION_MODULES = ("repro.core.symblock", "repro.core.engine")


@contextlib.contextmanager
def lower_precision(level: str):
    """Run the program's products at ``level`` inside."""
    import jax

    saved = []
    if level == "high":
        for modname in _PRECISION_MODULES:
            mod = importlib.import_module(modname)
            saved.append((mod, "MVM_PRECISION", mod.MVM_PRECISION))
            mod.MVM_PRECISION = jax.lax.Precision.HIGH
    else:
        for name, fn in _REPLACEMENTS[level].items():
            for modname in _TARGETS[name]:
                mod = importlib.import_module(modname)
                saved.append((mod, name, getattr(mod, name)))
                setattr(mod, name, fn)
    jax.clear_caches()
    try:
        yield level
    finally:
        for mod, name, value in saved:
            setattr(mod, name, value)
        jax.clear_caches()


def control_of(cell) -> str:
    """The control variant of ``cell``: one precision below what its
    configuration states for the products its storage of K runs."""
    return cell.config["control"][cell.mix["storage"]]


def readings(cell, seed: int, seconds: float, peaks: dict) -> dict:
    """One short run of the cell: every reference number, the verdict."""
    from bench import harness, reference

    run = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                           peaks)
    checks = harness.check(run)
    numbers = {k: 0.0 for k in reference.ANSWER_NUMBERS}
    for r in run.records:
        for k in numbers:
            if k in r:
                numbers[k] = max(numbers[k], r[k])
    numbers["not_optimal"] = sum(r["status"] != "optimal"
                                 for r in run.records)
    numbers.update({k: c["value"] for k, c in checks.items()})
    return {"seed": seed, "correct": reference.is_correct(checks),
            "answers": len(run.records), "numbers": numbers,
            "iterations": [a["iterations"] for a in run.answers],
            "per_answer": [{k: r.get(k) for k in
                            ("status",) + reference.ANSWER_NUMBERS}
                           for r in run.records]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--variants", default="program,control",
                    help="program, control (the cell's), high or "
                         "bfloat16")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import harness, spec

    src = os.path.join(spec.ROOT, "src")
    sys.path.insert(0, src)
    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    with open(harness.PEAKS) as f:
        table = json.load(f)
    device = harness.device_info(cell.chips, table)
    peaks = table["devices"][device["kind"]]
    out = open(args.out, "a") if args.out else None
    for variant in args.variants.split(","):
        level = control_of(cell) if variant == "control" else variant
        ctx = (contextlib.nullcontext("program") if level == "program"
               else lower_precision(level))
        with ctx as level:
            for s in args.seeds.split(","):
                rec = dict(readings(cell, int(s), args.seconds, peaks),
                           workload=cell.name, variant=variant, level=level)
                line = json.dumps(rec)
                short = {k: rec[k] for k in ("workload", "variant", "seed",
                                             "correct", "answers",
                                             "numbers")}
                print(json.dumps(short), flush=True)
                if out:
                    out.write(line + "\n")
                    out.flush()
    return 0


if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.exit(main())

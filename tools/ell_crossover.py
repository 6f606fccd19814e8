"""Where a dense product beats the ELL gather, and what a dense bucket
costs in device memory: the chip measurements behind
``kernels.sparse_mvm.DENSE_ELEMENTS_PER_SLOT`` and
``DENSE_OPERATOR_MAX_BYTES``.

    python -m tools.ell_crossover [--out FILE]

Times one PDHG iteration's pair of products, ``K x`` and ``K^T y``, over
a vmapped bucket, two ways: ``ell_matvec`` on the forward (B, m, wf) and
adjoint (B, n, wa) ELL layouts, and ``symblock.mv`` on the dense
(B, m, n) K and its transpose.  Shapes: the Table-1 ELL buckets of the
benchmark's ``table1-coo`` traffic, 2048x4096 and 4096x8192, and the
8 x 4096x8192 bucket of ``chip_smoke.py``'s sparse phase, in float32 and
float64.  Each line gives both times per iteration and the break-even
ratio

    R = (t_ell / t_dense) * m*n / (m*wf + n*wa),

the dense elements one ELL slot is worth at equal time: the dense form
wins at a shape when ``m*n <= R * (m*wf + n*wa)``.  It also times the
one-off scatter of the ELL values into the dense K.  A shape whose dense
loop does not fit the device prints its error instead.  Then, for
4096x8192 buckets, it compiles the ELL bucket program with the dense
operator forced and prints its temporary bytes against the bucket's
dense bytes.  One JSON line per reading, naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

# (label, B, m, n, wf, wa)
SHAPES = (
    ("gen-ip", 2, 32, 64, 32, 32),
    ("gen-ip", 1, 32, 64, 64, 32),
    ("gen-ip", 1, 32, 128, 64, 32),
    ("gen-ip", 1, 64, 128, 32, 64),
    ("assign1-5-8", 1, 256, 512, 32, 32),
    ("neos5", 1, 512, 1024, 64, 64),
    ("sprand", 1, 2048, 4096, 64, 32),
    ("sprand", 1, 4096, 8192, 64, 32),
    ("chip_smoke", 8, 4096, 8192, 64, 32),
)
# (dtype, lanes) of the 4096x8192 bucket programs whose memory is read
PROGRAMS = (("float32", 2), ("float32", 8), ("float64", 1), ("float64", 2))
DTYPES = ("float32", "float64")
TARGET_S = 0.2      # each timed call runs about this long
REPEATS = 5


def _ell(rng, B, rows, cols, w, dtype):
    import numpy as np

    data = rng.normal(size=(B, rows, w)).astype(dtype)
    idx = rng.integers(0, cols, size=(B, rows, w)).astype(np.int32)
    return data, idx


def _timed(fn, args):
    """Median seconds per iteration of ``fn(*args, iters)``."""
    import jax

    jax.block_until_ready(fn(*args, 2))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, 4))
    per = max((time.perf_counter() - t0) / 4, 1e-7)
    iters = max(4, int(TARGET_S / per))
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, iters))
        times.append((time.perf_counter() - t0) / iters)
    return statistics.median(times)


def measure(label, B, m, n, wf, wa, dtype_name, rng):
    import jax
    import jax.numpy as jnp

    from repro.core.symblock import mv
    from repro.kernels.sparse_mvm import ell_matvec

    dt = jnp.dtype(dtype_name)
    df, cf = _ell(rng, B, m, n, wf, dt)
    da, ca = _ell(rng, B, n, m, wa, dt)
    x0 = jnp.ones((B, n), dt)

    def loop(step):
        @jax.jit
        def run(ops, x, iters):
            def body(_, x):
                x = jax.vmap(step)(ops, x)
                return x / (jnp.max(jnp.abs(x), axis=1, keepdims=True) + 1)
            return jax.lax.fori_loop(0, iters, body, x)
        return run

    def ell_step(ops, x):
        df, cf, da, ca = ops
        return ell_matvec(da, ca, ell_matvec(df, cf, x))

    def dense_step(ops, x):
        K, = ops
        return mv(K.T, mv(K, x))

    @jax.jit
    def scatter(df, cf):
        rows = jnp.arange(m)[:, None]
        return jax.vmap(lambda d, c: jnp.zeros((m, n), d.dtype)
                        .at[rows, c].add(d))(df, cf)

    ell_ops = tuple(jnp.asarray(a) for a in (df, cf, da, ca))
    t_ell = _timed(loop(ell_step), (ell_ops, x0))
    K = scatter(ell_ops[0], ell_ops[1])
    jax.block_until_ready(K)
    t0 = time.perf_counter()
    jax.block_until_ready(scatter(ell_ops[0], ell_ops[1]))
    t_scatter = time.perf_counter() - t0
    t_dense = _timed(loop(dense_step), ((K,), x0))
    slots = m * wf + n * wa
    return {"label": label, "B": B, "m": m, "n": n, "wf": wf, "wa": wa,
            "dtype": dtype_name, "slots": slots, "dense": m * n,
            "dense_over_slots": m * n / slots,
            "t_ell_ms": t_ell * 1e3, "t_dense_ms": t_dense * 1e3,
            "t_scatter_ms": t_scatter * 1e3,
            "gather_gslots_per_s": B * slots / t_ell / 1e9,
            "dense_gelem_per_s": 2 * B * m * n / t_dense / 1e9,
            "break_even_R": (t_ell / t_dense) * m * n / slots}


def program_bytes(dtype_name, B, m=4096, n=8192, wf=64, wa=32):
    """Temporary bytes of the ELL bucket program compiled with the dense
    operator, against the bucket's dense bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import PDHGOptions
    from repro.kernels import sparse_mvm
    from repro.runtime.batch import make_ell_bucket_pipeline

    dt = jnp.dtype(dtype_name)
    key = jax.random.PRNGKey(0)
    sds = jax.ShapeDtypeStruct
    args = (sds((B, m, wf), dt), sds((B, m, wf), jnp.int32),
            sds((B, n, wa), dt), sds((B, n, wa), jnp.int32),
            sds((B, m), dt), sds((B, n), dt), sds((B, n), dt),
            sds((B, n), dt), sds((B, *key.shape), key.dtype))
    saved = (sparse_mvm.DENSE_ELEMENTS_PER_SLOT,
             sparse_mvm.DENSE_OPERATOR_MAX_BYTES)
    sparse_mvm.DENSE_ELEMENTS_PER_SLOT = {dt.itemsize: np.inf}
    sparse_mvm.DENSE_OPERATOR_MAX_BYTES = np.inf
    try:
        pipeline = make_ell_bucket_pipeline(PDHGOptions(dtype=dt))
        mem = jax.jit(pipeline).lower(*args).compile().memory_analysis()
    finally:
        (sparse_mvm.DENSE_ELEMENTS_PER_SLOT,
         sparse_mvm.DENSE_OPERATOR_MAX_BYTES) = saved
    dense = B * m * n * dt.itemsize
    return {"program": "ell_bucket_dense", "B": B, "m": m, "n": n,
            "dtype": dtype_name, "dense_bytes": dense,
            "temp_bytes": mem.temp_size_in_bytes,
            "temp_over_dense": mem.temp_size_in_bytes / dense}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    import jax
    import numpy as np

    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    rng = np.random.default_rng(0)
    out = open(args.out, "w") if args.out else None
    try:
        readings = [functools.partial(measure, *shape, dtype_name, rng)
                    for dtype_name in DTYPES for shape in SHAPES]
        readings += [functools.partial(program_bytes, *p) for p in PROGRAMS]
        for read in readings:
            try:
                rec = read()
            except jax.errors.JaxRuntimeError as e:   # does not fit
                rec = {"args": [str(a) for a in read.args[:7]],
                       "error": str(e).splitlines()[0][:300]}
            line = json.dumps(dict(rec, device=device))
            print(line, flush=True)
            if out:
                out.write(line + "\n")
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

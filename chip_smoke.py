"""Smoke test of the PDHG solver's main paths on TPU.

Run from the root of a checkout, in one process that owns the chip(s):

    python chip_smoke.py [--seed N]      # one chip: dense, sparse, crossbar
    python chip_smoke.py --chips 4       # 2x2 mesh: solve_dist vs solve_jit

Every instance comes from the ``repro.lp`` generators, which build it
around a known optimum; that optimum is the reference each phase is
checked against.  Each phase prints one line (size, dtype, backend,
status, iterations, relative error, compile and solve seconds — chip
timings for information, not benchmark numbers).  The last line is a
JSON object naming the device.  The script exits non-zero, without that
line, when JAX finds no TPU, when the ``repro`` sources are not next to
it, or when any phase fails.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Iteration caps sit above what each phase needs (3700 dense, 2800 for
# the slowest sparse instance, 500 crossbar), so a phase that stalls
# fails within minutes instead of running the clock out.
DENSE_SPEC = "rand:4096x8192"
DENSE_TOL = 1e-4
DENSE_MAX_ITERS = 5000
# ~20 nonzeros per row, like the paper's sprand class.  TPU v5e gathers
# 0.05 G elements/s in f64 (0.1 in f32), so one f64 iteration of this
# bucket (8 x (4096x64 + 8192x32) ELL slots) takes ~70 ms; the
# 65536x131072 class would take ~1.3 s per iteration, an hour per solve.
SPARSE_SPEC = "sprand:4096x8192:2.4e-3"        # ~80k nonzeros each
SPARSE_INSTANCES = 8
SPARSE_TOL = 1e-4
SPARSE_MAX_ITERS = 4000
XBAR_SHAPE = (1024, 2048)
# read noise (sigma_read = 1e-3 on EpiRAM) floors the in-loop merit
XBAR_TOL = 3e-3
XBAR_MAX_ITERS = 2000
# f64 matvecs are emulated on TPU (21 ms against 0.22 ms in f32 at
# 4096x8192), so the distributed phase and its reference solve in f32
DIST_DTYPE = "float32"
REL_ERR_BOUND = 1e-3
XBAR_REL_ERR_BOUND = 5e-2        # paper Table-2 gap band


class PhaseFailed(RuntimeError):
    pass


@contextlib.contextmanager
def compile_clock():
    """Seconds XLA spent compiling (or reading the compile cache) inside
    the block, from JAX's own ``backend_compile_duration`` events."""
    import jax
    from jax._src import dispatch

    spent = [0.0]

    def listener(event, duration, **_):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            spent[0] += duration

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield spent
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def rel_err(obj, obj_opt):
    return abs(obj - obj_opt) / max(abs(obj_opt), 1e-12)


def report(phase, *, size, dtype, backend, status, iters, err, compile_s,
           total_s, ok):
    print(f"phase={phase} size={size} dtype={dtype} backend={backend} "
          f"status={status} iters={iters} rel_err={err:.3e} "
          f"compile_s={compile_s:.1f} solve_s={total_s - compile_s:.1f} "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise PhaseFailed(f"{phase}: status={status} rel_err={err:.3e}")


def run_cli(argv):
    """``repro.launch.solve.main`` timed, with its compile seconds."""
    from repro.launch.solve import main

    t0 = time.perf_counter()
    with compile_clock() as spent:
        out = main(argv)
    return out, spent[0], time.perf_counter() - t0


def phase_dense(seed):
    from repro.launch.solve import load_instance

    res, comp, total = run_cli(
        ["--backend", "exact", "--instance", DENSE_SPEC, "--tol",
         str(DENSE_TOL), "--max-iters", str(DENSE_MAX_ITERS),
         "--seed", str(seed)])
    lp = load_instance(DENSE_SPEC, seed=seed)
    err = rel_err(res.obj, lp.obj_opt)
    report("dense", size="x".join(map(str, lp.K.shape)),
           dtype=res.x.dtype, backend="exact", status=res.status,
           iters=res.iterations, err=err, compile_s=comp, total_s=total,
           ok=res.status == "optimal" and err <= REL_ERR_BOUND)


def phase_sparse(seed):
    from repro.launch.solve import load_instance

    specs = [SPARSE_SPEC] * SPARSE_INSTANCES
    results, comp, total = run_cli(
        ["--backend", "batch", "--sparse", "--instances", ",".join(specs),
         "--tol", str(SPARSE_TOL), "--max-iters", str(SPARSE_MAX_ITERS),
         "--seed", str(seed)])
    # the CLI seeds instance i with seed + i
    lps = [load_instance(s, seed=seed + i) for i, s in enumerate(specs)]
    errs = [rel_err(r.obj, lp.obj_opt) for r, lp in zip(results, lps)]
    statuses = sorted({r.status for r in results})
    m, n = lps[0].K.shape
    nnz = sum(lp.K.nnz for lp in lps) // len(lps)
    report("sparse", size=f"{len(lps)}x{m}x{n}(nnz~{nnz})",
           dtype=results[0].x.dtype, backend="batch-sparse-ell",
           status="/".join(statuses),
           iters=max(r.iterations for r in results), err=max(errs),
           compile_s=comp, total_s=total,
           ok=statuses == ["optimal"] and max(errs) <= REL_ERR_BOUND)


def phase_crossbar(seed):
    import numpy as np

    from repro.core import PDHGOptions
    from repro.crossbar import EPIRAM
    from repro.crossbar.solver import CrossbarBatchSolver
    from repro.lp import random_standard_lp

    lp = random_standard_lp(*XBAR_SHAPE, seed=seed)
    opts = PDHGOptions(max_iters=XBAR_MAX_ITERS, tol=XBAR_TOL,
                       check_every=100, seed=seed, kernel="pallas",
                       dtype=np.float32)
    solver = CrossbarBatchSolver(opts, device=EPIRAM)
    t0 = time.perf_counter()
    with compile_clock() as spent:
        r = solver.solve_stream([lp])[0].result
    total = time.perf_counter() - t0
    if not any("tpu_custom_call" in t for t in solver.hlo_texts()):
        raise PhaseFailed("crossbar: no tpu_custom_call in the compiled "
                          "executable — the Pallas kernels did not compile")
    err = rel_err(r.obj, lp.obj_opt)
    report("crossbar", size="x".join(map(str, XBAR_SHAPE)),
           dtype=r.x.dtype, backend=f"batch-crossbar-{EPIRAM.name}-pallas",
           status=r.status, iters=r.iterations, err=err, compile_s=spent[0],
           total_s=total,
           ok=(r.status == "optimal" and bool(np.all(np.isfinite(r.x)))
               and err <= XBAR_REL_ERR_BOUND))


def phase_distributed(seed):
    """solve_dist on the 2x2 mesh of four chips against solve_jit on one."""
    import jax
    import numpy as np

    from repro.core.pdhg import PDHGOptions, prepare
    from repro.distributed.pdhg_dist import shard_problem
    from repro.launch.solve import load_instance
    from repro.runtime.mesh import make_local_mesh

    common = ["--instance", DENSE_SPEC, "--tol", str(DENSE_TOL),
              "--max-iters", str(DENSE_MAX_ITERS), "--seed", str(seed),
              "--dtype", DIST_DTYPE]
    lp = load_instance(DENSE_SPEC, seed=seed)
    size = "x".join(map(str, lp.K.shape))
    mesh = make_local_mesh()
    if tuple(mesh.devices.shape) != (2, 2):
        raise PhaseFailed(f"distributed: local mesh is "
                          f"{tuple(mesh.devices.shape)}, not 2x2")

    res_d, comp, total = run_cli(["--backend", "distributed"] + common)
    err_d = rel_err(res_d.obj, lp.obj_opt)
    report("distributed", size=size, dtype=res_d.x.dtype,
           backend="distributed-2x2", status=res_d.status,
           iters=res_d.iterations, err=err_d, compile_s=comp, total_s=total,
           ok=res_d.status == "optimal" and err_d <= REL_ERR_BOUND)
    # memory_stats() is None on backends that keep no statistics
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print("peak_bytes_in_use " + " ".join(
        f"dev{d.id}={p}" for d, p in zip(jax.devices(), peaks)), flush=True)

    # the layout solve_dist places: K split into four equal tiles
    scaled, T, Sigma = prepare(lp, PDHGOptions(seed=seed,
                                                dtype=np.dtype(DIST_DTYPE)))
    K = shard_problem(scaled, T, Sigma, mesh).K
    padded = K.shape != lp.K.shape
    shard_bytes = sorted(s.data.nbytes for s in K.addressable_shards)
    devs = {s.device for s in K.addressable_shards}
    print(f"K_shards bytes={shard_bytes} total={K.nbytes} "
          f"devices={len(devs)} padded={padded}", flush=True)
    if len(devs) != 4 or shard_bytes != [K.nbytes // 4] * 4:
        raise PhaseFailed("distributed: K is not split into four quarters")
    del K, scaled, T, Sigma

    res_1, comp1, total1 = run_cli(["--backend", "exact"] + common)
    err_1 = rel_err(res_1.obj, lp.obj_opt)
    report("reference", size=size, dtype=res_1.x.dtype,
           backend="exact-1chip", status=res_1.status,
           iters=res_1.iterations, err=err_1, compile_s=comp1,
           total_s=total1,
           ok=res_1.status == "optimal" and err_1 <= REL_ERR_BOUND)
    agree = rel_err(res_d.obj, res_1.obj)
    print(f"distributed_vs_reference obj_rel_diff={agree:.3e} "
          f"iters={res_d.iterations}/{res_1.iterations}", flush=True)
    # unpadded, the mesh changes only the psum's summation order, so the
    # objectives agree to 1e-6; padding K to the mesh changes the iterates
    # themselves, and then only the solve tolerance binds
    bound = DENSE_TOL if padded else 1e-6
    if not agree <= bound:
        raise PhaseFailed(f"distributed: objective differs from the "
                          f"one-chip reference by {agree:.3e} > {bound:g}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 2x2-mesh distributed phase and "
                         "its one-chip reference")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "__init__.py")):
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.runtime.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    from repro.kernels import interpret_default

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    if interpret_default():
        print("chip_smoke: Pallas kernels would run interpreted",
              file=sys.stderr)
        return 2
    print(f"device={dev.device_kind} count={len(devices)} "
          f"compile_cache={cache_dir}", flush=True)

    phases = ([phase_distributed] if args.chips == 4
              else [phase_dense, phase_sparse, phase_crossbar])
    failed = []
    for phase in phases:
        try:
            phase(args.seed)
        except Exception:           # report every phase, then fail the run
            traceback.print_exc()
            failed.append(phase.__name__)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Stream-throughput benchmark: bucketed batched serving vs. per-instance.

The ROADMAP benchmark item: compare bucketed stream throughput against
per-instance solves on a realistic mixed-shape LP stream, for both the
exact jitted path and the crossbar device-physics path, and record the
energy-ledger totals the device path accumulates (write split into
logical vs. padding cells, so the tile-alignment overhead is visible).

Per-instance baselines replicate what serving without the batch scheduler
looks like: a Python loop calling the jitted single-instance solver on
each (bucket-padded) instance — jit caching still applies per shape, so
the comparison isolates batching, not compilation.

  PYTHONPATH=src python benchmarks/stream_throughput.py --smoke
  PYTHONPATH=src python benchmarks/stream_throughput.py \
      --instances 32 --device taox --out experiments/stream_throughput.json
  PYTHONPATH=src python benchmarks/stream_throughput.py --kernel pallas

Each timed path runs twice: COLD includes compilation, WARM is the
steady-state serving cost (the number that matters for throughput).
``--kernel`` selects the engine's update backend (jnp vs fused Pallas).
Besides the full record, every run emits ``BENCH_stream.json`` at the
repo root (schema ``bench_stream/v7``: per-path warm/cold seconds +
device-MVM totals + per-instance ``iterations_to_tol`` distributions
(median/p90) — including the two sparse backends (``sparse_ell``
= the default ELL pipeline, ``sparse_bcoo`` = nnz-bucketed
COO), the async-vs-sync dispatch split, the per-pod ROUTED
cluster path, the ``exact_adaptive`` step-rule path on a scale-
imbalanced acceptance stream, the ``exact_norm_reuse`` seeded
second pass and the ``crossbar_refined`` mixed-precision refinement
solve — plus ``sparse``/``cluster`` summaries, an ``adaptive``
summary with the fixed-vs-adaptive iteration-reduction statistics, a
``norm_reuse`` summary, a ``refinement`` acceptance summary (merit
contrast, write-cells delta), and a ``sanitize`` section recording the
XLA compilation count of every warm batched pass) as the perf baseline
for future PRs; CI uploads it and ``benchmarks/bench_guard.py`` gates
regressions against it, including the acceptance-criterion gates that
the default sparse pipeline's warm serving is at least as fast as the
densified baseline, that the adaptive rule's median iteration reduction
stays above ``--min-iter-reduction``, that refinement's accuracy gain
stays above ``--min-refine-accuracy`` with zero extra write cells, and
the zero-recompile gate (``--max-warm-compiles 0``) on the warm passes.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def block_until_ready(x):
    """Fence for timing windows (jaxlint R7): today every solve path
    returns host-materialized numpy results, so this is a no-op — but
    the explicit block keeps the perf_counter windows honest if a path
    ever starts returning device arrays under async dispatch."""
    import jax

    return jax.block_until_ready(x)


SMOKE_SHAPES = [(8, 14), (10, 18), (20, 34), (12, 24), (7, 13), (16, 28)]
FULL_SHAPES = [(8, 14), (10, 18), (20, 34), (12, 24), (7, 13), (16, 28),
               (40, 70), (28, 52), (56, 96), (24, 44)]
# the sparse stream: >=95%-sparse paper-class shapes (acceptance target)
SPARSE_DENSITY = 0.05
SPARSE_SMOKE_SHAPES = [(96, 192), (128, 256), (80, 160), (112, 224)]
SPARSE_FULL_SHAPES = [(192, 384), (256, 512), (160, 320), (224, 448)]


def build_stream(n_instances: int, shapes, seed: int = 0):
    from repro.lp import random_standard_lp

    lps = []
    for i in range(n_instances):
        m, n = shapes[i % len(shapes)]
        lps.append(random_standard_lp(m, n, seed=seed + i))
    return lps


def build_imbalanced_stream(n_instances: int, shapes, seed: int = 0):
    """Objective/rhs scale-imbalanced variants of the mixed stream: c is
    scaled by 100 or 0.01 alternately.  Ruiz equilibration of K cannot
    see the mismatch; the adaptive rule's primal weight can — this is
    the stream the ``adaptive`` acceptance gate measures on."""
    import dataclasses

    lps = build_stream(n_instances, shapes, seed=seed)
    return [dataclasses.replace(lp, c=lp.c * (100.0 if i % 2 == 0
                                              else 0.01))
            for i, lp in enumerate(lps)]


def _iter_stats(results):
    """{median, p90} of per-instance iteration counts (iterations to the
    requested tol; iteration-limited instances are included as-is, i.e.
    censored at max_iters)."""
    its = [int(getattr(r, "result", r).iterations) for r in results]
    return {"median": float(np.median(its)),
            "p90": float(np.percentile(its, 90))}


def _sum_ledgers(reports):
    total = {}
    for rep in reports:
        for k, v in rep.ledger.as_dict().items():
            total[k] = total.get(k, 0.0) + v
    return total


def bench_exact(lps, opts):
    """Bucketed BatchSolver vs. a per-instance solve_jit loop."""
    from repro.core import solve_jit
    from repro.runtime import BatchSolver
    from repro.runtime.batch import bucket_dims, pad_problem

    def per_instance():
        results = []
        for lp in lps:
            padded = pad_problem(lp, *bucket_dims(*lp.K.shape))
            results.append(solve_jit(padded, opts))
        return results

    timings = {}
    t0 = time.perf_counter(); loop_results = block_until_ready(per_instance())
    timings["per_instance_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); loop_results = block_until_ready(per_instance())
    timings["per_instance_warm_s"] = time.perf_counter() - t0

    solver = BatchSolver(opts)
    t0 = time.perf_counter(); results = block_until_ready(solver.solve_stream(lps))
    timings["batched_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); block_until_ready(solver.solve_stream(lps))
    timings["batched_warm_s"] = time.perf_counter() - t0

    gaps = [abs(r.obj - lp.obj_opt) / max(abs(lp.obj_opt), 1e-12)
            for lp, r in zip(lps, results)]
    return {
        **timings,
        "speedup_warm": timings["per_instance_warm_s"]
        / max(timings["batched_warm_s"], 1e-12),
        # sanitizer surface: XLA compilations during the warm pass (the
        # executable-cache contract says this must be 0)
        "warm_compiles": solver.last_stream_stats["compiles"],
        "cache": solver.cache_info(),
        "buckets": sorted({str(r.bucket) for r in results}),
        "max_rel_gap": float(max(gaps)),
        "max_rel_disagreement_vs_loop": float(max(
            abs(r.obj - lr.obj) / max(abs(lr.obj), 1e-12)
            for r, lr in zip(results, loop_results))),
        "mvm_total_batched": int(sum(r.mvm_calls for r in results)),
        "mvm_total_per_instance": int(sum(r.mvm_calls
                                          for r in loop_results)),
        "iters_batched": _iter_stats(results),
        "iters_per_instance": _iter_stats(loop_results),
    }


def bench_sparse(lps, opts):
    """Sparse serving backends vs. the densified dense pipeline on the
    SAME >=95%-sparse stream.

    The dense baseline pads every instance into its (B, m_pad, n_pad)
    bucket stack — exactly what serving sparse traffic without the
    sparse path costs; ``host_stack_bytes`` records what each path
    actually materialized on the host.  Two sparse variants run:

      - ``sparse_*``      the default pipeline (= the ELL backend; the
                          steady-state serving number the guard gates)
      - ``bcoo_*``        the nnz-bucketed BCOO backend (memory-optimal,
                          scatter-bound on CPU)
    """
    import dataclasses

    from repro.runtime import BatchSolver

    dense_lps = [lp.densified() for lp in lps]

    def timed(solver, stream, tag, timings):
        t0 = time.perf_counter(); out = block_until_ready(solver.solve_stream(stream))
        timings[f"{tag}_cold_s"] = time.perf_counter() - t0
        t0 = time.perf_counter(); out = block_until_ready(solver.solve_stream(stream))
        timings[f"{tag}_warm_s"] = time.perf_counter() - t0
        return out

    timings = {}
    solver_d = BatchSolver(opts)
    dense_results = timed(solver_d, dense_lps, "dense", timings)
    dense_stats = dict(solver_d.last_stream_stats)

    assert opts.sparse_kernel == "ell"      # default pipeline == ELL
    solver_s = BatchSolver(opts)
    results = timed(solver_s, lps, "sparse", timings)
    sparse_stats = dict(solver_s.last_stream_stats)

    solver_b = BatchSolver(dataclasses.replace(opts, sparse_kernel="bcoo"))
    bcoo_results = timed(solver_b, lps, "bcoo", timings)

    gaps = [abs(r.obj - lp.obj_opt) / max(abs(lp.obj_opt), 1e-12)
            for lp, r in zip(lps, results)]
    mem_dense = dense_stats["dense_stack_bytes"]
    mem_sparse = sparse_stats["sparse_stack_bytes"]
    return {
        **timings,
        "speedup_warm": timings["dense_warm_s"]
        / max(timings["sparse_warm_s"], 1e-12),
        "speedup_warm_bcoo": timings["dense_warm_s"]
        / max(timings["bcoo_warm_s"], 1e-12),
        "density": float(np.mean([lp.K.density for lp in lps])),
        "nnz_total": int(sum(lp.K.nnz for lp in lps)),
        "host_stack_bytes_dense": int(mem_dense),
        "host_stack_bytes_sparse": int(mem_sparse),
        "host_mem_improvement": mem_dense / max(mem_sparse, 1),
        "warm_compiles": sparse_stats["compiles"],
        "cache": solver_s.cache_info(),
        "max_rel_gap": float(max(gaps)),
        "max_rel_disagreement_vs_dense": float(max(
            abs(r.obj - dr.obj) / max(abs(dr.obj), 1e-12)
            for r, dr in zip(results, dense_results))),
        "max_rel_disagreement_bcoo_vs_ell": float(max(
            abs(br.obj - r.obj) / max(abs(r.obj), 1e-12)
            for br, r in zip(bcoo_results, results))),
        "mvm_total_sparse": int(sum(r.mvm_calls for r in results)),
        "mvm_total_dense": int(sum(r.mvm_calls for r in dense_results)),
        "mvm_total_bcoo": int(sum(r.mvm_calls for r in bcoo_results)),
        "iters_sparse": _iter_stats(results),
        "iters_dense": _iter_stats(dense_results),
        "iters_bcoo": _iter_stats(bcoo_results),
    }


def bench_async(lps, opts):
    """Submit-all-then-collect dispatch vs. blocking per-bucket serving
    on the mixed-shape dense stream (same executables, same results —
    the delta is pure dispatch overlap)."""
    from repro.runtime import BatchSolver

    timings = {}
    sync = BatchSolver(opts, async_dispatch=False)
    t0 = time.perf_counter(); block_until_ready(sync.solve_stream(lps))
    timings["sync_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); r_sync = block_until_ready(sync.solve_stream(lps))
    timings["sync_warm_s"] = time.perf_counter() - t0

    al = BatchSolver(opts)          # async is the default
    t0 = time.perf_counter(); block_until_ready(al.solve_stream(lps))
    timings["async_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); r_async = block_until_ready(al.solve_stream(lps))
    timings["async_warm_s"] = time.perf_counter() - t0

    agree = max(abs(a.obj - s.obj) / max(abs(s.obj), 1e-12)
                for a, s in zip(r_async, r_sync))
    return {
        **timings,
        "speedup_warm": timings["sync_warm_s"]
        / max(timings["async_warm_s"], 1e-12),
        "dispatch_s": al.last_stream_stats["dispatch_s"],
        "collect_s": al.last_stream_stats["collect_s"],
        "n_buckets": al.last_stream_stats["n_buckets"],
        "max_rel_disagreement_vs_sync": float(agree),
        "mvm_total_async": int(sum(r.mvm_calls for r in r_async)),
        "mvm_total_sync": int(sum(r.mvm_calls for r in r_sync)),
        "iters_async": _iter_stats(r_async),
        "iters_sync": _iter_stats(r_sync),
    }


def bench_cluster(lps, opts, n_pods: int = 2):
    """Per-pod routed serving vs. the unrouted scheduler on the same
    mixed stream.

    Runs single-process with ``n_pods`` routing targets (pods beyond
    the live process are *virtual*: the coordinator reroutes their
    buckets through the straggler path), so the routed timings capture
    the routing + transport + reroute machinery itself.  Per-pod
    throughput is then measured HONESTLY: each pod's routed sub-stream
    is served on its own (what that pod of a real deployment would
    actually run) and warm-timed separately.
    """
    from repro.runtime import BatchSolver, ClusterBatchSolver
    from repro.runtime.cluster import bucket_tag

    timings = {}
    base = BatchSolver(opts)
    base_results = base.solve_stream(lps)

    # no explicit transport: the solver owns a private scratch dir and
    # cleans it up per stream (single-process virtual-pod mode)
    solver = ClusterBatchSolver(opts, pod=0, n_pods=n_pods, live_pods=1,
                                straggler_timeout=30.0)
    t0 = time.perf_counter(); results = block_until_ready(solver.solve_stream(lps))
    timings["routed_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); results = block_until_ready(solver.solve_stream(lps))
    timings["routed_warm_s"] = time.perf_counter() - t0
    st = solver.last_stream_stats

    # per-pod shares from the solver's own audit surface (the table the
    # routing actually used — never re-derived here), throughput from
    # serving each pod's routed sub-stream separately
    buckets = solver._group_buckets(lps)
    pod_instances = {}
    per_pod = {}
    for key, idxs in buckets.items():
        tag = bucket_tag(key)
        pod = st["routing"][tag]
        d = per_pod.setdefault(str(pod), {"n_buckets": 0, "n_instances": 0,
                                          "flops_cost": 0})
        d["n_buckets"] += 1
        d["n_instances"] += solver.last_bucket_sizes[tag]
        d["flops_cost"] += solver.last_costs[tag]
        pod_instances.setdefault(str(pod), []).extend(
            lps[i] for i in idxs)
    total_cost = max(sum(d["flops_cost"] for d in per_pod.values()), 1)
    for pod, d in per_pod.items():
        d["flops_share"] = d["flops_cost"] / total_cost
        pod_solver = BatchSolver(opts)
        pod_solver.solve_stream(pod_instances[pod])          # compile
        t0 = time.perf_counter(); block_until_ready(pod_solver.solve_stream(pod_instances[pod]))
        d["warm_s"] = time.perf_counter() - t0
        d["instances_per_s_warm"] = d["n_instances"] / max(d["warm_s"],
                                                           1e-12)

    agree = max(abs(r.obj - b.obj) / max(abs(b.obj), 1e-12)
                for r, b in zip(results, base_results))
    return {
        **timings,
        "n_pods": n_pods,
        "routing": dict(st["routing"]),
        "per_pod": per_pod,
        "rerouted_buckets": int(st["rerouted_buckets"]),
        "gather_s": st.get("gather_s", 0.0),
        "max_rel_disagreement_vs_unrouted": float(agree),
        "mvm_total_routed": int(sum(r.mvm_calls for r in results)),
        "iters_routed": _iter_stats(results),
    }


def bench_device(lps, opts, device):
    """CrossbarBatchSolver vs. a per-instance solve_crossbar_jit loop.

    The loop pads each instance to the same device-tile bucket the batch
    path uses (a crossbar burns whole tiles either way), so the delta is
    pure batching + dispatch, not array size.
    """
    import jax
    from repro.crossbar import CrossbarBatchSolver, solve_crossbar_jit
    from repro.runtime.batch import bucket_dims, pad_problem

    tile = (device.crossbar_rows, device.crossbar_cols)

    def per_instance():
        reports = []
        for i, lp in enumerate(lps):
            padded = pad_problem(lp, *bucket_dims(*lp.K.shape, tile=tile))
            reports.append(solve_crossbar_jit(
                padded, opts, device=device,
                key=jax.random.PRNGKey(opts.seed + i)))
        return reports

    timings = {}
    t0 = time.perf_counter(); loop_reports = block_until_ready(per_instance())
    timings["per_instance_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); loop_reports = block_until_ready(per_instance())
    timings["per_instance_warm_s"] = time.perf_counter() - t0

    solver = CrossbarBatchSolver(opts, device=device)
    t0 = time.perf_counter(); reports = block_until_ready(solver.solve_stream(lps))
    timings["batched_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); reports = block_until_ready(solver.solve_stream(lps))
    timings["batched_warm_s"] = time.perf_counter() - t0

    gaps = [abs(rep.result.obj - lp.obj_opt) / max(abs(lp.obj_opt), 1e-12)
            for lp, rep in zip(lps, reports)]
    return {
        **timings,
        "speedup_warm": timings["per_instance_warm_s"]
        / max(timings["batched_warm_s"], 1e-12),
        "warm_compiles": solver.last_stream_stats["compiles"],
        "cache": solver.cache_info(),
        "max_rel_gap": float(max(gaps)),
        "ledger_batched": _sum_ledgers(reports),
        "ledger_per_instance": _sum_ledgers(loop_reports),
        "mvm_total_batched": int(sum(rep.result.mvm_calls
                                     for rep in reports)),
        "mvm_total_per_instance": int(sum(rep.result.mvm_calls
                                          for rep in loop_reports)),
        "iters_batched": _iter_stats(reports),
        "iters_per_instance": _iter_stats(loop_reports),
    }


def bench_adaptive(lps, opts):
    """step_rule="adaptive" vs "fixed" on the scale-imbalanced stream —
    the acceptance gate's measurement: per-instance iterations-to-tol
    under both rules through the SAME BatchSolver serving path, plus the
    warm/cold wall clock of the adaptive stream.

    ``iter_reduction_median`` is the median of per-instance
    fixed/adaptive iteration ratios; every adaptive instance must reach
    the same tol (``n_unconverged_*`` records any censoring at
    max_iters, which deflates the measured reduction rather than
    inflating it)."""
    import dataclasses

    from repro.runtime import BatchSolver

    timings = {}
    solver_f = BatchSolver(opts)
    t0 = time.perf_counter(); r_fixed = block_until_ready(solver_f.solve_stream(lps))
    timings["fixed_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); r_fixed = block_until_ready(solver_f.solve_stream(lps))
    timings["fixed_warm_s"] = time.perf_counter() - t0

    solver_a = BatchSolver(dataclasses.replace(opts,
                                               step_rule="adaptive"))
    t0 = time.perf_counter(); r_adapt = block_until_ready(solver_a.solve_stream(lps))
    timings["adaptive_cold_s"] = time.perf_counter() - t0
    t0 = time.perf_counter(); r_adapt = block_until_ready(solver_a.solve_stream(lps))
    timings["adaptive_warm_s"] = time.perf_counter() - t0

    ratios = [f.iterations / max(a.iterations, 1)
              for f, a in zip(r_fixed, r_adapt)]
    return {
        **timings,
        "iters_fixed": _iter_stats(r_fixed),
        "iters_adaptive": _iter_stats(r_adapt),
        "iter_reduction_median": float(np.median(ratios)),
        "iter_reduction_p10": float(np.percentile(ratios, 10)),
        "n_unconverged_fixed": int(sum(not r.converged for r in r_fixed)),
        "n_unconverged_adaptive": int(sum(not r.converged
                                          for r in r_adapt)),
        "max_merit_adaptive": float(max(r.merit for r in r_adapt)),
        "warm_compiles": solver_a.last_stream_stats["compiles"],
        "speedup_warm": timings["fixed_warm_s"]
        / max(timings["adaptive_warm_s"], 1e-12),
        "mvm_total_fixed": int(sum(r.mvm_calls for r in r_fixed)),
        "mvm_total_adaptive": int(sum(r.mvm_calls for r in r_adapt)),
    }


def bench_refinement(opts, device):
    """Mixed-precision iterative-refinement acceptance experiment: on an
    instance where the exact path converges but the analog solve bottoms
    out at the read-noise floor, the refined crossbar path (digital
    residual outer loop re-solving the correction LP on the SAME
    programmed conductances) must recover exact-path accuracy with ZERO
    additional write cycles — ``bench_guard --min-refine-accuracy``
    gates the unrefined/refined merit ratio and the write-cells delta.

    The instance, iteration budget and ``sigma_read`` are fixed
    (independent of ``--smoke``): this measures convergence behaviour,
    not throughput, and the contrast needs a noise level where the
    single solve demonstrably fails.
    """
    import dataclasses

    import jax
    from repro.core import solve_jit
    from repro.crossbar import solve_crossbar_jit
    from repro.lp import random_standard_lp

    lp = random_standard_lp(16, 28, seed=3)
    noisy = dataclasses.replace(device,
                                sigma_read=max(device.sigma_read, 2e-3))
    base = dataclasses.replace(opts, max_iters=8000, tol=1e-6,
                               check_every=64, refine_rounds=0,
                               refine_tol=0.0)
    refined_opts = dataclasses.replace(base, refine_rounds=4,
                                       refine_tol=base.tol)

    exact = solve_jit(lp, base)
    rep0 = solve_crossbar_jit(lp, base, device=noisy,
                              key=jax.random.PRNGKey(base.seed))
    t0 = time.perf_counter()
    rep1 = block_until_ready(solve_crossbar_jit(
        lp, refined_opts, device=noisy,
        key=jax.random.PRNGKey(base.seed)))
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    rep1 = block_until_ready(solve_crossbar_jit(
        lp, refined_opts, device=noisy,
        key=jax.random.PRNGKey(base.seed)))
    warm_s = time.perf_counter() - t0

    merit_ref = rep1.result.merit
    return {
        "cold_s": cold_s, "warm_s": warm_s,
        "tol": base.tol, "rounds": refined_opts.refine_rounds,
        "sigma_read": noisy.sigma_read,
        "merit_exact": float(exact.merit),
        "merit_unrefined": float(rep0.result.merit),
        "merit_refined": float(merit_ref),
        "accuracy_gain": float(rep0.result.merit / max(merit_ref, 1e-300)),
        "status_unrefined": rep0.result.status,
        "status_refined": rep1.result.status,
        "refined_reached_tol": bool(merit_ref <= base.tol),
        "cells_written_unrefined": int(rep0.ledger.cells_written),
        "cells_written_refined": int(rep1.ledger.cells_written),
        "write_cells_delta": int(rep1.ledger.cells_written
                                 - rep0.ledger.cells_written),
        "executed_iterations": int(rep1.executed_iterations),
        "digital_mvms": int(rep1.digital_mvms),
        "mvm_total": int(rep1.result.mvm_calls),
        "mvm_total_unrefined": int(rep0.result.mvm_calls),
    }


def bench_norm_reuse(lps, opts):
    """Cross-instance norm reuse: pass 2 of the same stream is served by
    the seeded executables (short power refine instead of full Lanczos).
    Records the warm-pass compile count (must stay 0: the seeded twin is
    compiled eagerly during the cold pass) and the per-pass MVM ledgers
    whose delta is the reused Lanczos work."""
    from repro.runtime import BatchSolver

    solver = BatchSolver(opts, norm_reuse=True)
    t0 = time.perf_counter(); r1 = block_until_ready(solver.solve_stream(lps))
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter(); r2 = block_until_ready(solver.solve_stream(lps))
    warm_s = time.perf_counter() - t0
    agree = max(abs(a.obj - b.obj) / max(abs(b.obj), 1e-12)
                for a, b in zip(r2, r1))
    return {
        "cold_s": cold_s,
        "warm_s": warm_s,
        "warm_compiles": solver.last_stream_stats["compiles"],
        "norm_seeded_buckets":
            solver.last_stream_stats["norm_seeded_buckets"],
        "cache_entries": len(solver._norm_cache),
        "mvm_total_cold": int(sum(r.mvm_calls for r in r1)),
        "mvm_total_warm": int(sum(r.mvm_calls for r in r2)),
        "max_rel_disagreement_vs_cold": float(agree),
        "iters_warm": _iter_stats(r2),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small stream + loose tolerance (CI)")
    ap.add_argument("--instances", type=int, default=None,
                    help="stream length (default: 16 smoke / 32 full)")
    ap.add_argument("--device", default="epiram",
                    choices=["epiram", "taox"])
    ap.add_argument("--kernel", default="jnp", choices=["jnp", "pallas"],
                    help="engine update backend (pallas = fused kernels; "
                         "on the crossbar path also the differential-pair "
                         "MVM kernel)")
    ap.add_argument("--max-iters", type=int, default=None)
    ap.add_argument("--tol", type=float, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pods", type=int, default=2,
                    help="routing targets for the cluster path (pods "
                         "beyond the live process are virtual)")
    ap.add_argument("--out", default=None,
                    help="JSON output path (default under experiments/)")
    args = ap.parse_args(argv)

    import jax
    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    from repro.core import PDHGOptions
    from repro.crossbar import DEVICES

    n = args.instances if args.instances is not None \
        else (16 if args.smoke else 32)
    shapes = SMOKE_SHAPES if args.smoke else FULL_SHAPES
    max_iters = args.max_iters if args.max_iters is not None \
        else (2000 if args.smoke else 20000)
    # the device path bottoms out at the read-noise floor; don't ask the
    # while_loop to chase an unreachable tolerance in smoke mode
    tol = args.tol if args.tol is not None else (1e-3 if args.smoke else 1e-5)
    device = DEVICES["EpiRAM" if args.device == "epiram" else "TaOx-HfOx"]
    opts = PDHGOptions(max_iters=max_iters, tol=tol, check_every=64,
                       lanczos_iters=16 if args.smoke else 48,
                       seed=args.seed, kernel=args.kernel)

    from repro.lp import sparse_lp_stream

    lps = build_stream(n, shapes, seed=args.seed)
    sparse_shapes = SPARSE_SMOKE_SHAPES if args.smoke else SPARSE_FULL_SHAPES
    sparse_lps = sparse_lp_stream(n, sparse_shapes, density=SPARSE_DENSITY,
                                  seed=args.seed)
    record = {
        "config": {
            "n_instances": n, "shapes": [list(s) for s in shapes],
            "sparse_shapes": [list(s) for s in sparse_shapes],
            "sparse_density": SPARSE_DENSITY,
            "max_iters": max_iters, "tol": tol, "device": device.name,
            "tile": [device.crossbar_rows, device.crossbar_cols],
            "kernel": args.kernel,
            "smoke": bool(args.smoke), "seed": args.seed,
            "jax": jax.__version__,
        },
        "exact": bench_exact(lps, opts),
        "crossbar": bench_device(lps, opts, device),
        "sparse": bench_sparse(sparse_lps, opts),
        "async": bench_async(lps, opts),
        "cluster": bench_cluster(lps, opts, n_pods=args.pods),
    }

    # the adaptive acceptance stream: scale-imbalanced instances with a
    # generous iteration budget so the FIXED baseline is not censored at
    # max_iters (censoring deflates the measured reduction)
    import dataclasses
    imb_lps = build_imbalanced_stream(min(n, 8 if args.smoke else 16),
                                      shapes, seed=args.seed)
    adapt_opts = dataclasses.replace(
        opts, max_iters=max(max_iters, 20000 if args.smoke else 40000))
    record["adaptive"] = bench_adaptive(imb_lps, adapt_opts)
    record["norm_reuse"] = bench_norm_reuse(lps, opts)
    record["refinement"] = bench_refinement(opts, device)

    out = args.out or os.path.join(
        "experiments",
        "stream_throughput_smoke.json" if args.smoke
        else "stream_throughput.json")
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=1)

    # Compact perf-baseline record for future PRs: per-path warm/cold
    # seconds + device-MVM totals, written at the repo root so CI can
    # upload it as a stable-named artifact next to the full record and
    # ``bench_guard.py`` can gate schema + warm-path regressions on it.
    from repro.runtime import sanitize

    bench = {
        "schema": "bench_stream/v7",
        "kernel": args.kernel,
        "config": record["config"],
        # runtime-sanitizer surface: XLA compilations during each warm
        # serving pass.  The executable-cache contract says all of these
        # are 0; ``bench_guard --max-warm-compiles 0`` gates it in CI.
        "sanitize": {
            "compile_counting": bool(sanitize.supported()),
            "warm_compiles": {
                "exact_batched": record["exact"]["warm_compiles"],
                "sparse_batched": record["sparse"]["warm_compiles"],
                "crossbar_batched": record["crossbar"]["warm_compiles"],
                "adaptive_batched": record["adaptive"]["warm_compiles"],
                "norm_reuse_batched":
                    record["norm_reuse"]["warm_compiles"],
            },
        },
        "paths": {
            **{
                f"{path}_{variant}": {
                    "cold_s": record[path][f"{variant}_cold_s"],
                    "warm_s": record[path][f"{variant}_warm_s"],
                    "mvm_total": record[path][f"mvm_total_{variant}"],
                }
                for path in ("exact", "crossbar")
                for variant in ("batched", "per_instance")
            },
            "sparse_batched": {
                "cold_s": record["sparse"]["sparse_cold_s"],
                "warm_s": record["sparse"]["sparse_warm_s"],
                "mvm_total": record["sparse"]["mvm_total_sparse"],
            },
            "sparse_batched_dense": {
                "cold_s": record["sparse"]["dense_cold_s"],
                "warm_s": record["sparse"]["dense_warm_s"],
                "mvm_total": record["sparse"]["mvm_total_dense"],
            },
            # the default sparse pipeline IS the ELL backend; the
            # explicit entry keeps the backend comparison stable even
            # if the default ever changes
            "sparse_ell": {
                "cold_s": record["sparse"]["sparse_cold_s"],
                "warm_s": record["sparse"]["sparse_warm_s"],
                "mvm_total": record["sparse"]["mvm_total_sparse"],
            },
            "sparse_bcoo": {
                "cold_s": record["sparse"]["bcoo_cold_s"],
                "warm_s": record["sparse"]["bcoo_warm_s"],
                "mvm_total": record["sparse"]["mvm_total_bcoo"],
            },
            "exact_batched_async": {
                "cold_s": record["async"]["async_cold_s"],
                "warm_s": record["async"]["async_warm_s"],
                "mvm_total": record["async"]["mvm_total_async"],
            },
            "exact_batched_sync": {
                "cold_s": record["async"]["sync_cold_s"],
                "warm_s": record["async"]["sync_warm_s"],
                "mvm_total": record["async"]["mvm_total_sync"],
            },
            "exact_routed": {
                "cold_s": record["cluster"]["routed_cold_s"],
                "warm_s": record["cluster"]["routed_warm_s"],
                "mvm_total": record["cluster"]["mvm_total_routed"],
            },
            # v6: the adaptive step rule served on the imbalanced
            # acceptance stream (its fixed-rule twin rides in the
            # top-level "adaptive" section, same stream, same opts)
            "exact_adaptive": {
                "cold_s": record["adaptive"]["adaptive_cold_s"],
                "warm_s": record["adaptive"]["adaptive_warm_s"],
                "mvm_total": record["adaptive"]["mvm_total_adaptive"],
            },
            "exact_norm_reuse": {
                "cold_s": record["norm_reuse"]["cold_s"],
                "warm_s": record["norm_reuse"]["warm_s"],
                "mvm_total": record["norm_reuse"]["mvm_total_warm"],
            },
            # v7: the iterative-refinement crossbar solve (acceptance
            # instance; convergence details in the "refinement" section)
            "crossbar_refined": {
                "cold_s": record["refinement"]["cold_s"],
                "warm_s": record["refinement"]["warm_s"],
                "mvm_total": record["refinement"]["mvm_total"],
            },
        },
        "cluster": {
            "n_pods": record["cluster"]["n_pods"],
            "routing": record["cluster"]["routing"],
            "per_pod": record["cluster"]["per_pod"],
            "rerouted_buckets": record["cluster"]["rerouted_buckets"],
            "max_rel_disagreement_vs_unrouted":
                record["cluster"]["max_rel_disagreement_vs_unrouted"],
        },
        # v6: per-instance iteration-count distributions per path — the
        # iteration-reduction gate reads these, and cross-PR drift in
        # them flags algorithmic (not wall-clock) regressions
        "adaptive": {
            "iter_reduction_median":
                record["adaptive"]["iter_reduction_median"],
            "iter_reduction_p10":
                record["adaptive"]["iter_reduction_p10"],
            "iters_fixed": record["adaptive"]["iters_fixed"],
            "iters_adaptive": record["adaptive"]["iters_adaptive"],
            "n_unconverged_fixed":
                record["adaptive"]["n_unconverged_fixed"],
            "n_unconverged_adaptive":
                record["adaptive"]["n_unconverged_adaptive"],
            "max_merit_adaptive":
                record["adaptive"]["max_merit_adaptive"],
            "tol": adapt_opts.tol,
        },
        "norm_reuse": {
            "norm_seeded_buckets":
                record["norm_reuse"]["norm_seeded_buckets"],
            "cache_entries": record["norm_reuse"]["cache_entries"],
            "mvm_total_cold": record["norm_reuse"]["mvm_total_cold"],
            "mvm_total_warm": record["norm_reuse"]["mvm_total_warm"],
            "max_rel_disagreement_vs_cold":
                record["norm_reuse"]["max_rel_disagreement_vs_cold"],
        },
        # v7: the refinement acceptance summary bench_guard's
        # --min-refine-accuracy gate reads — unrefined/refined merit
        # ratio and the zero-additional-writes evidence
        "refinement": {k: record["refinement"][k] for k in (
            "merit_exact", "merit_unrefined", "merit_refined",
            "accuracy_gain", "refined_reached_tol",
            "cells_written_unrefined", "cells_written_refined",
            "write_cells_delta", "digital_mvms", "rounds",
            "sigma_read", "tol")},
        "sparse": {
            "density": record["sparse"]["density"],
            "host_stack_bytes_dense":
                record["sparse"]["host_stack_bytes_dense"],
            "host_stack_bytes_sparse":
                record["sparse"]["host_stack_bytes_sparse"],
            "host_mem_improvement":
                record["sparse"]["host_mem_improvement"],
            "speedup_warm": record["sparse"]["speedup_warm"],
            "speedup_warm_bcoo": record["sparse"]["speedup_warm_bcoo"],
        },
    }
    # v6: every path entry carries its iterations-to-tol distribution
    iters_map = {
        "exact_batched": record["exact"]["iters_batched"],
        "exact_per_instance": record["exact"]["iters_per_instance"],
        "crossbar_batched": record["crossbar"]["iters_batched"],
        "crossbar_per_instance": record["crossbar"]["iters_per_instance"],
        "sparse_batched": record["sparse"]["iters_sparse"],
        "sparse_batched_dense": record["sparse"]["iters_dense"],
        "sparse_ell": record["sparse"]["iters_sparse"],
        "sparse_bcoo": record["sparse"]["iters_bcoo"],
        "exact_batched_async": record["async"]["iters_async"],
        "exact_batched_sync": record["async"]["iters_sync"],
        "exact_routed": record["cluster"]["iters_routed"],
        "exact_adaptive": record["adaptive"]["iters_adaptive"],
        "exact_norm_reuse": record["norm_reuse"]["iters_warm"],
        # single acceptance instance: the distribution degenerates to
        # the executed (bucket-max, all-rounds) iteration count
        "crossbar_refined": {
            "median": float(record["refinement"]["executed_iterations"]),
            "p90": float(record["refinement"]["executed_iterations"]),
        },
    }
    for name, st in iters_map.items():
        bench["paths"][name]["iterations_to_tol"] = st

    bench_out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_stream.json")
    with open(bench_out, "w") as f:
        json.dump(bench, f, indent=1)

    for path in ("exact", "crossbar"):
        r = record[path]
        print(f"[{path}] per-instance warm {r['per_instance_warm_s']:.3f}s"
              f" | batched warm {r['batched_warm_s']:.3f}s"
              f" | speedup {r['speedup_warm']:.2f}x"
              f" | max rel gap {r['max_rel_gap']:.2e}"
              f" | cache {r['cache']}")
    r = record["sparse"]
    print(f"[sparse] dense warm {r['dense_warm_s']:.3f}s"
          f" | ell warm {r['sparse_warm_s']:.3f}s"
          f" ({r['speedup_warm']:.2f}x)"
          f" | bcoo warm {r['bcoo_warm_s']:.3f}s"
          f" ({r['speedup_warm_bcoo']:.2f}x)"
          f" | host stack {r['host_stack_bytes_dense']}B ->"
          f" {r['host_stack_bytes_sparse']}B"
          f" ({r['host_mem_improvement']:.1f}x smaller)"
          f" | density {r['density']:.3f}")
    r = record["async"]
    print(f"[async] sync warm {r['sync_warm_s']:.3f}s"
          f" | async warm {r['async_warm_s']:.3f}s"
          f" | speedup {r['speedup_warm']:.2f}x"
          f" | dispatch {r['dispatch_s']:.3f}s"
          f" collect {r['collect_s']:.3f}s over {r['n_buckets']} buckets")
    r = record["cluster"]
    pods = ", ".join(
        f"pod{p}: {d['n_buckets']}bkt/{d['n_instances']}inst "
        f"({d['flops_share']:.0%} FLOPs)"
        for p, d in sorted(r["per_pod"].items()))
    print(f"[cluster] routed warm {r['routed_warm_s']:.3f}s over "
          f"{r['n_pods']} pods | {pods} | rerouted "
          f"{r['rerouted_buckets']} | max disagreement "
          f"{r['max_rel_disagreement_vs_unrouted']:.2e}")
    r = record["adaptive"]
    print(f"[adaptive] fixed median {r['iters_fixed']['median']:.0f} it"
          f" (p90 {r['iters_fixed']['p90']:.0f})"
          f" | adaptive median {r['iters_adaptive']['median']:.0f} it"
          f" (p90 {r['iters_adaptive']['p90']:.0f})"
          f" | reduction {r['iter_reduction_median']:.2f}x"
          f" (p10 {r['iter_reduction_p10']:.2f}x)"
          f" | unconverged fixed/adaptive "
          f"{r['n_unconverged_fixed']}/{r['n_unconverged_adaptive']}")
    r = record["norm_reuse"]
    print(f"[norm_reuse] seeded buckets {r['norm_seeded_buckets']}"
          f" | cache entries {r['cache_entries']}"
          f" | mvms {r['mvm_total_cold']} -> {r['mvm_total_warm']}"
          f" | warm compiles {r['warm_compiles']}")
    r = record["refinement"]
    print(f"[refinement] unrefined merit {r['merit_unrefined']:.2e}"
          f" ({r['status_unrefined']})"
          f" | refined merit {r['merit_refined']:.2e}"
          f" ({r['status_refined']}, {r['rounds']} rounds)"
          f" | gain {r['accuracy_gain']:.1e}x"
          f" | write cells delta {r['write_cells_delta']}"
          f" | digital mvms {r['digital_mvms']}")
    led = record["crossbar"]["ledger_batched"]
    print(f"[crossbar] stream write={led['write_energy_j']:.3f}J "
          f"(padding {led['write_energy_padding_j']:.3f}J) "
          f"read={led['read_energy_j']:.3f}J mvms={led['mvm_count']:.0f}")
    print(f"wrote {out} and {bench_out}")
    return record


if __name__ == "__main__":
    main()

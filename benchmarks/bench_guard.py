"""Benchmark guard: validate BENCH_stream.json and gate warm regressions.

CI runs this right after the smoke stream benchmark:

  1. **Schema validation** — the candidate record must be
     ``bench_stream/v7``: every serving path (dense batched /
     per-instance, crossbar batched / per-instance, the
     mixed-precision refined crossbar solve, the ELL sparse backend
     and the densified sparse baseline,
     async + sync dispatch, per-pod routed cluster serving, the
     adaptive step rule on the imbalanced acceptance stream, and the
     norm-reuse seeded second pass) present with finite numeric
     ``cold_s``/``warm_s``/``mvm_total`` AND a finite per-instance
     ``iterations_to_tol`` {median, p90} distribution, plus the
     ``sparse`` host-memory summary, the ``cluster`` routing summary
     (non-empty routing table, per-pod throughput shares), the
     ``adaptive`` iteration-reduction summary, the ``norm_reuse``
     summary, the ``refinement`` acceptance summary, and the
     ``sanitize`` section (per-path warm-pass XLA compile counts from
     ``repro.runtime.sanitize``).
  2. **Regression gate** — the warm BUCKETED paths (the steady-state
     serving numbers) must not regress more than ``--max-regression``
     (default 2x) against the committed baseline
     (``git show HEAD:BENCH_stream.json`` in CI).  v1-v6 baselines are
     accepted: only the path keys both records share are compared.
  3. **Sparse-wins gate** — the acceptance criterion of the ELL
     backend: the default sparse pipeline's warm serving must be at
     least ``--min-sparse-speedup`` (default 1x) as fast as the
     densified dense baseline on the same >=95%-sparse stream.
  4. **Iteration-reduction gate** — with ``--min-iter-reduction R``,
     the adaptive step rule's median per-instance iteration reduction
     over the fixed rule (same scale-imbalanced stream, same tol) must
     be at least R, and no adaptive instance may have failed to reach
     the tol the fixed rule was asked for.  Skipped when R is omitted
     or the record predates the ``adaptive`` section.
  5. **Refinement gate** — with ``--min-refine-accuracy G``, the
     iterative-refinement acceptance experiment must show an
     unrefined/refined KKT-merit improvement of at least G, the
     refined solve must reach the exact-path tolerance, and ZERO
     additional cells may have been written across refinement rounds
     (correction solves reuse the original programmed conductances).
     Skipped when G is omitted.
  6. **Zero-recompile gate** — with ``--max-warm-compiles N`` (CI
     passes 0), every warm batched pass must have compiled at most N
     fresh XLA executables.  A violation means an executable-cache key
     drifted (stale ``opts_static`` field, unstable bucket signature).
     Skipped when the record says compile counting was unsupported.

Exit code 0 = pass; 1 = schema or regression failure (messages on
stderr).

  python benchmarks/bench_guard.py --candidate BENCH_stream.json \
      --baseline /tmp/bench_baseline.json --max-regression 2.0
"""
from __future__ import annotations

import argparse
import json
import math
import sys

SCHEMA = "bench_stream/v7"

# every serving path a v7 record must carry
REQUIRED_PATHS = (
    "exact_batched",
    "exact_per_instance",
    "crossbar_batched",
    "crossbar_per_instance",
    "crossbar_refined",
    "sparse_batched",
    "sparse_batched_dense",
    "sparse_ell",
    "exact_batched_async",
    "exact_batched_sync",
    "exact_routed",
    "exact_adaptive",
    "exact_norm_reuse",
)
PATH_FIELDS = ("cold_s", "warm_s", "mvm_total")
ITER_FIELDS = ("median", "p90")      # per-path iterations_to_tol (v6)
ADAPTIVE_FIELDS = ("iter_reduction_median", "iter_reduction_p10",
                   "n_unconverged_fixed", "n_unconverged_adaptive",
                   "max_merit_adaptive", "tol")
NORM_REUSE_FIELDS = ("norm_seeded_buckets", "cache_entries",
                     "mvm_total_cold", "mvm_total_warm",
                     "max_rel_disagreement_vs_cold")
REFINEMENT_FIELDS = ("merit_exact", "merit_unrefined", "merit_refined",
                     "accuracy_gain", "cells_written_unrefined",
                     "cells_written_refined", "write_cells_delta",
                     "digital_mvms", "rounds", "sigma_read", "tol")
SPARSE_FIELDS = ("density", "host_stack_bytes_dense",
                 "host_stack_bytes_sparse", "host_mem_improvement",
                 "speedup_warm")
CLUSTER_FIELDS = ("n_pods", "routing", "per_pod", "rerouted_buckets",
                  "max_rel_disagreement_vs_unrouted")
PER_POD_FIELDS = ("n_buckets", "n_instances", "flops_cost", "flops_share",
                  "warm_s", "instances_per_s_warm")

# warm steady-state serving paths gated against the committed baseline
GUARDED_WARM_PATHS = ("exact_batched", "crossbar_batched", "sparse_batched",
                      "exact_routed")

# warm passes whose XLA compile counts the sanitize section must carry
SANITIZE_PATHS = ("exact_batched", "sparse_batched", "crossbar_batched",
                  "adaptive_batched", "norm_reuse_batched")

def _fail(msg: str) -> None:
    print(f"bench_guard: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _finite_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) \
        and math.isfinite(v)


def validate_schema(bench: dict) -> None:
    if bench.get("schema") != SCHEMA:
        _fail(f"schema is {bench.get('schema')!r}, expected {SCHEMA!r}")
    paths = bench.get("paths")
    if not isinstance(paths, dict):
        _fail("missing 'paths' object")
    for name in REQUIRED_PATHS:
        entry = paths.get(name)
        if not isinstance(entry, dict):
            _fail(f"missing path entry {name!r}")
        for field in PATH_FIELDS:
            if not _finite_number(entry.get(field)):
                _fail(f"paths.{name}.{field} is not a finite number: "
                      f"{entry.get(field)!r}")
            if entry[field] < 0:
                _fail(f"paths.{name}.{field} is negative: {entry[field]}")
        iters = entry.get("iterations_to_tol")
        if not isinstance(iters, dict):
            _fail(f"paths.{name}.iterations_to_tol missing (v6 requires "
                  f"a median/p90 distribution per path)")
        for field in ITER_FIELDS:
            if not _finite_number(iters.get(field)) or iters[field] <= 0:
                _fail(f"paths.{name}.iterations_to_tol.{field} is not a "
                      f"positive finite number: {iters.get(field)!r}")
    adaptive = bench.get("adaptive")
    if not isinstance(adaptive, dict):
        _fail("missing 'adaptive' summary")
    for field in ADAPTIVE_FIELDS:
        if not _finite_number(adaptive.get(field)):
            _fail(f"adaptive.{field} is not a finite number: "
                  f"{adaptive.get(field)!r}")
    for leg in ("iters_fixed", "iters_adaptive"):
        d = adaptive.get(leg)
        if not isinstance(d, dict) \
                or not all(_finite_number(d.get(f)) for f in ITER_FIELDS):
            _fail(f"adaptive.{leg} must carry finite median/p90")
    reuse = bench.get("norm_reuse")
    if not isinstance(reuse, dict):
        _fail("missing 'norm_reuse' summary")
    for field in NORM_REUSE_FIELDS:
        if not _finite_number(reuse.get(field)):
            _fail(f"norm_reuse.{field} is not a finite number: "
                  f"{reuse.get(field)!r}")
    refinement = bench.get("refinement")
    if not isinstance(refinement, dict):
        _fail("missing 'refinement' summary")
    for field in REFINEMENT_FIELDS:
        if not _finite_number(refinement.get(field)):
            _fail(f"refinement.{field} is not a finite number: "
                  f"{refinement.get(field)!r}")
    if not isinstance(refinement.get("refined_reached_tol"), bool):
        _fail("refinement.refined_reached_tol must be a bool")
    sparse = bench.get("sparse")
    if not isinstance(sparse, dict):
        _fail("missing 'sparse' summary")
    for field in SPARSE_FIELDS:
        if not _finite_number(sparse.get(field)):
            _fail(f"sparse.{field} is not a finite number: "
                  f"{sparse.get(field)!r}")
    cluster = bench.get("cluster")
    if not isinstance(cluster, dict):
        _fail("missing 'cluster' summary")
    for field in CLUSTER_FIELDS:
        if field not in cluster:
            _fail(f"cluster.{field} missing")
    if not isinstance(cluster["routing"], dict) or not cluster["routing"]:
        _fail("cluster.routing must be a non-empty bucket->pod table")
    if not isinstance(cluster["per_pod"], dict) or not cluster["per_pod"]:
        _fail("cluster.per_pod must be a non-empty pod->stats table")
    for pod, entry in cluster["per_pod"].items():
        for field in PER_POD_FIELDS:
            if not _finite_number(entry.get(field)):
                _fail(f"cluster.per_pod[{pod}].{field} is not a finite "
                      f"number: {entry.get(field)!r}")
    pods_routed = set(cluster["routing"].values())
    if not pods_routed <= set(range(int(cluster["n_pods"]))):
        _fail(f"cluster.routing targets unknown pods: {pods_routed}")
    san = bench.get("sanitize")
    if not isinstance(san, dict):
        _fail("missing 'sanitize' section")
    if not isinstance(san.get("compile_counting"), bool):
        _fail("sanitize.compile_counting must be a bool")
    warm = san.get("warm_compiles")
    if not isinstance(warm, dict):
        _fail("sanitize.warm_compiles must be a path->count object")
    for name in SANITIZE_PATHS:
        v = warm.get(name)
        if not (isinstance(v, int) and not isinstance(v, bool) and v >= 0):
            _fail(f"sanitize.warm_compiles.{name} is not a non-negative "
                  f"int: {v!r}")


def check_regressions(candidate: dict, baseline: dict,
                      max_regression: float) -> None:
    base_paths = baseline.get("paths") or {}
    compared = 0
    for name in GUARDED_WARM_PATHS:
        base = base_paths.get(name)
        if not isinstance(base, dict):
            continue        # v1/v2 baselines predate sparse/async/routed
        base_warm = base.get("warm_s")
        cand_warm = candidate["paths"][name]["warm_s"]
        if not _finite_number(base_warm) or base_warm <= 0:
            continue
        compared += 1
        ratio = cand_warm / base_warm
        status = "ok" if ratio <= max_regression else "REGRESSION"
        print(f"bench_guard: {name}: warm {base_warm:.3f}s -> "
              f"{cand_warm:.3f}s ({ratio:.2f}x) [{status}]")
        if ratio > max_regression:
            _fail(f"{name} warm path regressed {ratio:.2f}x "
                  f"(> {max_regression}x allowed)")
    if compared == 0:
        print("bench_guard: no comparable warm paths in baseline "
              "(schema migration?); regression gate skipped")


def check_sparse_wins(candidate: dict, min_speedup: float) -> None:
    """Acceptance criterion: sparse serving must not lose to densifying."""
    dense = candidate["paths"]["sparse_batched_dense"]["warm_s"]
    sparse = candidate["paths"]["sparse_batched"]["warm_s"]
    speedup = dense / max(sparse, 1e-12)
    status = "ok" if speedup >= min_speedup else "TOO SLOW"
    print(f"bench_guard: sparse_batched warm {sparse:.3f}s vs densified "
          f"{dense:.3f}s ({speedup:.2f}x) [{status}]")
    if speedup < min_speedup:
        _fail(f"sparse_batched warm is only {speedup:.2f}x the densified "
              f"baseline (>= {min_speedup}x required)")


def check_iter_reduction(candidate: dict, min_reduction: float) -> None:
    """Acceptance criterion of the adaptive step rule: median
    per-instance iteration reduction over fixed on the imbalanced
    stream, at the SAME tol (unconverged adaptive instances fail the
    gate outright — a reduction bought by stopping early is no
    reduction)."""
    ad = candidate["adaptive"]
    red = ad["iter_reduction_median"]
    unconv = ad["n_unconverged_adaptive"]
    status = "ok" if red >= min_reduction and unconv == 0 else "TOO SLOW"
    print(f"bench_guard: adaptive median iteration reduction "
          f"{red:.2f}x (p10 {ad['iter_reduction_p10']:.2f}x), "
          f"{unconv} unconverged [{status}]")
    if unconv > 0:
        _fail(f"{unconv} adaptive instance(s) missed tol "
              f"{ad['tol']:g} within the iteration budget")
    if red < min_reduction:
        _fail(f"adaptive median iteration reduction is only {red:.2f}x "
              f"(>= {min_reduction}x required)")


def check_refinement(candidate: dict, min_gain: float) -> None:
    """Acceptance criterion of mixed-precision refinement: the refined
    crossbar solve must reach the exact-path tolerance at a sigma_read
    where the single solve fails, improve the KKT merit by at least
    ``min_gain``x, and program ZERO additional cells across refinement
    rounds (the same conductances serve every correction solve)."""
    ref = candidate["refinement"]
    gain = ref["accuracy_gain"]
    reached = ref["refined_reached_tol"]
    delta = ref["write_cells_delta"]
    ok = gain >= min_gain and reached and delta == 0
    print(f"bench_guard: refinement merit {ref['merit_unrefined']:.2e} -> "
          f"{ref['merit_refined']:.2e} ({gain:.1e}x gain, "
          f"{ref['rounds']} rounds), write cells delta {delta} "
          f"[{'ok' if ok else 'FAIL'}]")
    if delta != 0:
        _fail(f"refinement programmed {delta} additional cell(s) — the "
              "correction solves must reuse the original conductances")
    if not reached:
        _fail(f"refined merit {ref['merit_refined']:.2e} missed the "
              f"exact-path tol {ref['tol']:g} at sigma_read "
              f"{ref['sigma_read']:g}")
    if gain < min_gain:
        _fail(f"refinement accuracy gain is only {gain:.2f}x "
              f"(>= {min_gain}x required)")


def check_warm_compiles(candidate: dict, max_compiles: int) -> None:
    """Zero-recompile gate: warm batched passes must stay compile-free."""
    san = candidate["sanitize"]
    if not san["compile_counting"]:
        print("bench_guard: compile counting unsupported on the producing "
              "runtime; warm-compile gate skipped")
        return
    for name, count in sorted(san["warm_compiles"].items()):
        status = "ok" if count <= max_compiles else "RECOMPILE"
        print(f"bench_guard: {name}: warm pass compiled {count} "
              f"executable(s) [{status}]")
        if count > max_compiles:
            _fail(f"{name} warm pass compiled {count} fresh XLA "
                  f"executable(s) (> {max_compiles} allowed) — an "
                  f"executable-cache key drifted (stale opts_static "
                  f"field or unstable bucket signature)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidate", default="BENCH_stream.json",
                    help="freshly produced benchmark record")
    ap.add_argument("--baseline", default=None,
                    help="committed baseline record (omit to skip the "
                         "regression gate and only validate schema)")
    ap.add_argument("--max-regression", type=float, default=2.0,
                    help="max allowed warm-time ratio candidate/baseline")
    ap.add_argument("--min-sparse-speedup", type=float, default=1.0,
                    help="min required densified/sparse warm-time ratio "
                         "(0 disables the sparse-wins gate)")
    ap.add_argument("--max-warm-compiles", type=int, default=None,
                    help="max XLA compilations allowed in each warm "
                         "batched pass (CI passes 0; omit to skip)")
    ap.add_argument("--min-iter-reduction", type=float, default=None,
                    help="min required median iteration reduction of "
                         "step_rule=adaptive over fixed on the "
                         "imbalanced stream (omit to skip)")
    ap.add_argument("--min-refine-accuracy", type=float, default=None,
                    help="min required unrefined/refined KKT-merit "
                         "ratio of the iterative-refinement acceptance "
                         "experiment; also enforces refined-reaches-tol "
                         "and a zero write-cells delta (omit to skip)")
    args = ap.parse_args(argv)

    with open(args.candidate) as f:
        candidate = json.load(f)
    validate_schema(candidate)
    print(f"bench_guard: schema {SCHEMA} ok "
          f"({len(candidate['paths'])} paths)")
    if args.min_sparse_speedup > 0:
        check_sparse_wins(candidate, args.min_sparse_speedup)
    if args.max_warm_compiles is not None:
        check_warm_compiles(candidate, args.max_warm_compiles)
    if args.min_iter_reduction is not None:
        check_iter_reduction(candidate, args.min_iter_reduction)
    if args.min_refine_accuracy is not None:
        check_refinement(candidate, args.min_refine_accuracy)

    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
        check_regressions(candidate, baseline, args.max_regression)
    print("bench_guard: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""LP solving driver — the paper's workload as a service.

  PYTHONPATH=src python -m repro.launch.solve --instance gen-ip002 \
      --backend taox          # crossbar-simulated (device physics + ledger)
  PYTHONPATH=src python -m repro.launch.solve --instance rand:64x128 \
      --backend exact         # jitted dense PDHG
  PYTHONPATH=src python -m repro.launch.solve --instance rand:96x160 \
      --backend distributed   # shard_map PDHG on all local devices
  PYTHONPATH=src python -m repro.launch.solve --backend batch \
      --instances rand:8x14,rand:10x18,rand:24x40   # bucketed stream
  PYTHONPATH=src python -m repro.launch.solve --backend batch \
      --device epiram --instances rand:8x14,rand:10x18,rand:24x40
      # device-tile-aware bucketed stream through the crossbar simulator
  PYTHONPATH=src python -m repro.launch.solve --backend batch --sparse \
      --instances sprand:96x192:0.05,sprand:128x256:0.02
      # sparse COO stream: nonzero-proportional memory, async dispatch
  REPRO_COORDINATOR=host0:9876 REPRO_NUM_PROCESSES=2 REPRO_PROCESS_ID=0 \
  PYTHONPATH=src python -m repro.launch.solve --backend batch \
      --cluster auto --instances rand:8x14,rand:10x18,rand:24x40
      # multi-host serving: per-pod bucket routing + straggler reroute
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from ..core.pdhg import PDHGOptions, solve_jit
from ..crossbar import (
    EPIRAM,
    TAOX_HFOX,
    solve_crossbar_jit,
    solve_crossbar_stream,
)
from ..lp import (
    TABLE1_SIZES,
    pagerank_lp,
    random_standard_lp,
    sparse_random_standard_lp,
    table1_instance,
)
from ..runtime import BatchSolver
from ..runtime.batch import STREAM_PHASES
from ..runtime.cache import enable_compile_cache
from ..runtime.mesh import make_local_mesh


def load_instance(spec: str, seed: int = 0):
    if spec in TABLE1_SIZES:
        return table1_instance(spec, seed=seed)
    if spec.startswith("rand:"):
        m, n = spec[5:].split("x")
        return random_standard_lp(int(m), int(n), seed=seed)
    if spec.startswith("sprand:"):
        # sprand:MxN[:density] — COO-native sparse instance
        parts = spec[7:].split(":")
        m, n = parts[0].split("x")
        density = float(parts[1]) if len(parts) > 1 else 0.05
        return sparse_random_standard_lp(int(m), int(n), density=density,
                                         seed=seed)
    if spec.startswith("pagerank:"):
        return pagerank_lp(int(spec.split(":")[1]), seed=seed)
    raise ValueError(f"unknown instance {spec!r}")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--instance", default="gen-ip002")
    ap.add_argument("--instances", default=None,
                    help="comma-separated specs for --backend batch")
    ap.add_argument("--backend", default="exact",
                    choices=["exact", "epiram", "taox", "distributed",
                             "batch"])
    ap.add_argument("--device", default="none",
                    choices=["none", "epiram", "taox"],
                    help="with --backend batch: serve the stream through "
                         "the device-tile-aware crossbar simulator")
    ap.add_argument("--sparse", action="store_true",
                    help="with --backend batch: serve the stream through "
                         "the sparse COO pipeline (instances loaded as "
                         "sprand: specs are sparse already; dense specs "
                         "are converted).  Host memory is proportional "
                         "to nonzeros — no dense (B, m, n) stack exists "
                         "on the host; small buckets multiply by a dense "
                         "K built on the device")
    ap.add_argument("--sync", action="store_true",
                    help="with --backend batch: block per bucket instead "
                         "of the default submit-all-then-collect async "
                         "dispatch")
    ap.add_argument("--cluster", default="off", choices=["auto", "off"],
                    help="multi-host serving: 'auto' initializes "
                         "jax.distributed from REPRO_COORDINATOR/"
                         "REPRO_NUM_PROCESSES/REPRO_PROCESS_ID (falling "
                         "back to single-process when unset) and routes "
                         "buckets across pods; 'off' serves everything "
                         "in-process")
    ap.add_argument("--pods", type=int, default=None,
                    help="route buckets across N pods (default: the "
                         "detected process count).  N beyond the live "
                         "process count creates virtual pods whose "
                         "buckets the coordinator reroutes — a single-"
                         "process way to exercise the routing table")
    ap.add_argument("--kernel", default="jnp", choices=["jnp", "pallas"],
                    help="engine update backend: reference jnp vector "
                         "algebra or the fused Pallas kernels (interpret "
                         "mode auto-detected; on the crossbar batch path "
                         "'pallas' also routes every MVM through the "
                         "differential-pair crossbar kernel)")
    from ..core.engine import STEP_RULES
    from ..core.lanczos import NORM_BACKENDS

    ap.add_argument("--step-rule", default="fixed", choices=STEP_RULES,
                    help="'fixed' = classic constant steps; 'adaptive' = "
                         "data-driven primal-weight init + PDLP-style "
                         "rebalancing at restarts + down-only step "
                         "safeguard (boundary-only, megakernel-safe); "
                         "'strongly_convex' = accelerated theta schedule "
                         "(requires --gamma > 0)")
    ap.add_argument("--gamma", type=float, default=0.0,
                    help="strong-convexity modulus for "
                         "--step-rule strongly_convex")
    ap.add_argument("--norm-backend", default="lanczos",
                    choices=NORM_BACKENDS,
                    help="jitted operator-norm estimator seeding the "
                         "step sizes")
    ap.add_argument("--norm-reuse", action="store_true",
                    help="with --backend batch: reuse operator-norm "
                         "estimates across stream passes, keyed by "
                         "(shape bucket, sparsity fingerprint) — repeat "
                         "instances pay a short power-iteration refine "
                         "instead of the full Lanczos run")
    ap.add_argument("--refine-rounds", type=int, default=0,
                    help="crossbar backends only: digital iterative-"
                         "refinement rounds — each re-solves the "
                         "residual-correction LP on the SAME programmed "
                         "conductances (shifted b/c, zero extra write "
                         "cycles), recovering exact-path accuracy from "
                         "noisy analog reads")
    ap.add_argument("--refine-tol", type=float, default=0.0,
                    help="with --refine-rounds: the answer's target, "
                         "'optimal' only once the exact digital KKT "
                         "merit reaches it, after which corrections stop "
                         "being adopted; --tol is then the inner stop of "
                         "each analog solve, at the read-noise floor "
                         "(default 0 = refine for all rounds, --tol the "
                         "target)")
    ap.add_argument("--ecc", type=int, default=1,
                    help="crossbar backends only: k-fold differential-"
                         "pair replication with median decode — tolerates "
                         "stuck cells/drift at k-fold write+read energy, "
                         "ledgered separately under the *_ecc fields")
    ap.add_argument("--dtype", default="float64",
                    choices=["float64", "float32"],
                    help="solve dtype (PDHGOptions.dtype); compiled Pallas "
                         "kernels on TPU take float32 only")
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--max-iters", type=int, default=40000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    crossbar_backend = (args.backend in ("epiram", "taox")
                        or (args.backend == "batch"
                            and args.device != "none"))
    if (args.refine_rounds or args.refine_tol or args.ecc != 1) \
            and not crossbar_backend:
        ap.error("--refine-rounds/--refine-tol/--ecc only apply to the "
                 "crossbar backends (--backend epiram/taox or "
                 "--backend batch --device ...): refinement re-reads the "
                 "programmed array and ECC replicates its cells — exact "
                 "digital paths have neither")
    if args.ecc < 1:
        ap.error("--ecc must be >= 1 (1 = replication off)")
    if args.device != "none" and args.backend != "batch":
        ap.error("--device only applies to --backend batch "
                 "(use --backend epiram/taox for single instances)")
    if (args.sparse or args.sync) and args.backend != "batch":
        ap.error("--sparse/--sync only apply to --backend batch")
    if args.sparse and args.device != "none":
        ap.error("--sparse does not combine with --device: a crossbar "
                 "programs every physical cell, so device streams are "
                 "served densely")
    if args.kernel != "jnp" and args.backend == "distributed":
        ap.error("--kernel pallas is not wired into the shard_map path "
                 "(the distributed engine runs the psum-tiled operator "
                 "with jnp updates)")
    if args.pods is not None and args.backend != "batch":
        ap.error("--pods only applies to --backend batch (distributed "
                 "spans processes through the global mesh directly)")
    if (args.cluster != "off" or args.pods is not None) \
            and args.device != "none":
        ap.error("--cluster/--pods do not combine with --device: the "
                 "crossbar batch path is single-process")

    from ..runtime import cluster as cluster_mod

    info = cluster_mod.init_cluster(args.cluster)
    jax.config.update("jax_enable_x64", True)
    opts = PDHGOptions(max_iters=args.max_iters, tol=args.tol,
                       check_every=100, seed=args.seed,
                       dtype=np.dtype(args.dtype), kernel=args.kernel,
                       step_rule=args.step_rule,
                       gamma=args.gamma, norm_backend=args.norm_backend,
                       refine_rounds=args.refine_rounds,
                       refine_tol=args.refine_tol)

    def crossbar_device(name: str):
        import dataclasses as _dc
        dev = EPIRAM if name == "epiram" else TAOX_HFOX
        if args.ecc != 1:
            dev = _dc.replace(dev, ecc=args.ecc)
        return dev
    if args.norm_reuse and (args.backend != "batch"
                            or args.device != "none"):
        ap.error("--norm-reuse only applies to --backend batch without "
                 "--device (single solves estimate the norm once by "
                 "construction; the crossbar stream programs every cell "
                 "per instance, so there is nothing to reuse)")
    if args.backend == "batch":
        specs = (args.instances or args.instance).split(",")
        lps = [load_instance(s.strip(), seed=args.seed + i)
               for i, s in enumerate(specs)]
        if args.device != "none":
            dev = crossbar_device(args.device)
            reports = solve_crossbar_stream(lps, opts, device=dev)
            for lp, rep in zip(lps, reports):
                r, led = rep.result, rep.ledger
                line = (f"instance={lp.name} shape={lp.K.shape} "
                        f"device={dev.name} status={r.status} "
                        f"iters={r.iterations} objective={r.obj:.6f}")
                if lp.obj_opt is not None:
                    rel = abs(r.obj - lp.obj_opt) / max(abs(lp.obj_opt),
                                                        1e-12)
                    line += (f" (known optimum {lp.obj_opt:.6f}, "
                             f"rel err {rel:.2e})")
                line += (f" | write={led.write_energy_j:.4f}J "
                         f"(padding {led.write_energy_padding_j:.4f}J"
                         + (f", ecc {led.write_energy_ecc_j:.4f}J"
                            if dev.ecc > 1 else "")
                         + f") read={led.read_energy_j:.4f}J")
                if args.refine_rounds:
                    line += (f" | refine: rounds={args.refine_rounds} "
                             f"executed_iters={rep.executed_iterations} "
                             f"digital_mvms={rep.digital_mvms}")
                print(line)
            return reports
        if args.sparse:
            lps = [lp.sparsified() for lp in lps]
        n_pods = args.pods if args.pods is not None else info.num_processes
        if n_pods > 1 or info.is_multiprocess:
            from ..runtime import ClusterBatchSolver
            solver = ClusterBatchSolver(opts, async_dispatch=not args.sync,
                                        n_pods=n_pods,
                                        norm_reuse=args.norm_reuse)
        else:
            solver = BatchSolver(opts, async_dispatch=not args.sync,
                                 norm_reuse=args.norm_reuse)
        results = solver.solve_stream(lps)
        for lp, r in zip(lps, results):
            line = (f"instance={r.name} shape={lp.K.shape} "
                    f"bucket={r.bucket} status={r.status} "
                    f"iters={r.iterations} objective={r.obj:.6f}")
            if r.sparse:
                line += f" sparse(nnz={lp.K.nnz})"
            if lp.obj_opt is not None:
                rel = abs(r.obj - lp.obj_opt) / max(abs(lp.obj_opt), 1e-12)
                line += f" (known optimum {lp.obj_opt:.6f}, rel err {rel:.2e})"
            print(line)
        st = solver.last_stream_stats
        phases = " ".join(f"{p}={st[p + '_s']:.3f}s" for p in STREAM_PHASES)
        print(f"stream: buckets={st['n_buckets']} {phases} "
              f"host_stack_bytes=dense:{st['dense_stack_bytes']}"
              f"/sparse:{st['sparse_stack_bytes']}")
        if "routing" in st:
            print(f"cluster: pod={st['pod']}/{st['n_pods']} "
                  f"local_buckets={st['n_local_buckets']} "
                  f"rerouted={st['rerouted_buckets']} "
                  f"routing={st['routing']}")
        return results

    lp = load_instance(args.instance, seed=args.seed)
    if args.backend == "exact":
        res = solve_jit(lp, opts)
        led = None
    elif args.backend in ("epiram", "taox"):
        dev = crossbar_device(args.backend)
        rep = solve_crossbar_jit(lp, opts, device=dev)
        res, led = rep.result, rep.ledger
        if args.refine_rounds:
            print(f"refine: rounds={args.refine_rounds} "
                  f"executed_iters={rep.executed_iterations} "
                  f"digital_mvms={rep.digital_mvms} "
                  f"cells_written={led.cells_written} (all pre-refinement; "
                  f"rounds add READ windows only)")
        if dev.ecc > 1:
            print(f"ecc: k={dev.ecc} decode={dev.ecc_decode} "
                  f"write_ecc={led.write_energy_ecc_j:.4f}J "
                  f"cells_ecc={led.cells_written_ecc}")
    else:
        if args.cluster != "off":
            # shard_map over the process-spanning global mesh
            from ..distributed.pdhg_dist import solve_dist_auto
            res = solve_dist_auto(lp, opts, cluster=args.cluster)
        else:
            from ..distributed.pdhg_dist import solve_dist
            mesh = make_local_mesh()
            res = solve_dist(lp, mesh, opts)
        led = None

    print(f"instance={lp.name} shape={lp.K.shape} backend={args.backend}")
    print(f"status={res.status} iters={res.iterations} "
          f"sigma_max={res.sigma_max:.6f}")
    print(f"objective={res.obj:.6f}"
          + (f" (known optimum {lp.obj_opt:.6f}, "
             f"rel err {abs(res.obj-lp.obj_opt)/max(abs(lp.obj_opt),1e-12):.2e})"
             if lp.obj_opt is not None else ""))
    if led is not None:
        print(f"energy: write={led.write_energy_j:.4f}J "
              f"read={led.read_energy_j:.4f}J | latency: "
              f"write={led.write_latency_s:.4f}s read={led.read_latency_s:.4f}s")
    return res


if __name__ == "__main__":
    main()

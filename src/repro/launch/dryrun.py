from repro.runtime.mesh import request_cpu_devices
assert request_cpu_devices(512), \
    "JAX backend initialized before repro.launch.dryrun import"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. assembles the step function (train_step / prefill / serve_step for LM
     archs; the distributed PDHG step for the paper's own LP configs),
  3. jits with explicit in/out shardings and lowers against
     ShapeDtypeStruct inputs (zero allocation),
  4. compiles, prints memory_analysis() (proves it fits) and
     cost_analysis() (FLOPs/bytes), parses collective traffic from the
     partitioned HLO (trip-count-scaled), and
  5. writes a JSON artifact under experiments/dryrun/ that the roofline
     harness (benchmarks/roofline.py) consumes.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --arch lp_256k --shape dist_step
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import (
    ARCH_NAMES,
    LP_CONFIGS,
    SHAPES,
    cell_supported,
    get_config,
    input_specs,
)
from ..runtime.mesh import make_production_mesh
from ..launch import hlo as hlo_mod
from ..models import lm as lm_mod
from ..train.serve_step import make_prefill_step, make_serve_step
from ..train.train_step import TrainConfig, make_train_step, opt_state_shapes

# v5e-class roofline constants (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # B/s
ICI_BW = 50e9              # B/s per link


def _sanitize(mesh, spec: P) -> P:
    """Drop spec axes absent from the mesh (e.g. 'pod' on single-pod)."""
    names = mesh.axis_names
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, tuple):
            t = tuple(a for a in s if a in names)
            clean.append(t if t else None)
        else:
            clean.append(s if s in names else None)
    return P(*clean)


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, _sanitize(mesh, s)), spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def _dp_axes(mesh, batch: int):
    """Largest data-parallel axis set that divides the global batch."""
    names = mesh.axis_names
    full = tuple(a for a in ("pod", "data") if a in names)
    size = int(np.prod([mesh.shape[a] for a in full])) if full else 1
    if full and batch % size == 0:
        return full
    if "data" in names and batch % mesh.shape["data"] == 0:
        return ("data",)
    return ()


def _retarget_dp(spec_tree, dp):
    """Replace ('pod','data') batch axes in a spec tree with ``dp``."""
    def fix(spec):
        clean = []
        for s in spec:
            if s == ("pod", "data"):
                clean.append(dp if dp else None)
            else:
                clean.append(s)
        return P(*clean)

    return jax.tree.map(fix, spec_tree, is_leaf=lambda s: isinstance(s, P))


def _batch_specs(batch_sds, dp=("pod", "data")):
    specs = {}
    for k, v in batch_sds.items():
        if k in ("tokens", "labels"):
            specs[k] = P(dp if dp else None, None)
        elif k == "embeddings":
            specs[k] = P(dp if dp else None, None, None)
        else:
            raise ValueError(k)
    return specs


def _opt_specs(param_specs, optimizer: str = "adamw"):
    if optimizer == "adamw":
        return {
            "m": param_specs,
            "v": param_specs,
            "step": P(),
        }
    # adafactor: factored second moments — row drops the last dim,
    # col drops the second-to-last (mirrors train.optimizer.adafactor_init)
    def fac(spec: P):
        if len(spec) >= 2:
            return {
                "row": P(*spec[:-1]),
                "col": P(*spec[:-2], spec[-1]),
            }
        return {"v": P(*spec)}

    f = jax.tree.map(fac, param_specs,
                     is_leaf=lambda s: isinstance(s, P))
    return {"f": f, "step": P()}


def lower_lm_cell(arch: str, shape_name: str, mesh, sharding_mode="fsdp",
                  tcfg: TrainConfig = TrainConfig(microbatch=8),
                  cfg_overrides=None, prefill_last_only=True):
    """microbatch=8: gradient accumulation bounds activation residency
    (global 256-batch -> 32-sample microbatches); the production-memory
    default.  ``cfg_overrides`` (dict of ModelConfig fields) and
    ``prefill_last_only`` are the hillclimb knobs."""
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, reason = cell_supported(cfg, shape)
    if not ok:
        return {"skipped": reason}
    params_sds = lm_mod.param_shapes(cfg)
    pspecs = lm_mod.partition_specs(cfg, mode=sharding_mode)
    dp = _dp_axes(mesh, shape.global_batch)
    if shape.kind == "train":
        step = make_train_step(cfg, tcfg)
        opt_sds = opt_state_shapes(params_sds, tcfg)
        batch_sds = input_specs(cfg, shape)
        bspecs = _batch_specs(batch_sds, dp)
        ospecs = _opt_specs(pspecs, tcfg.optimizer)
        in_sh = (_ns(mesh, pspecs), _ns(mesh, ospecs),
                 _ns(mesh, bspecs))
        out_sh = (_ns(mesh, pspecs), _ns(mesh, ospecs),
                  NamedSharding(mesh, P()))
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0, 1))
        args = (params_sds, opt_sds, batch_sds)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, last_only=prefill_last_only)
        batch_sds = input_specs(cfg, shape)
        bspecs = _batch_specs(batch_sds, dp)
        in_sh = (_ns(mesh, pspecs), _ns(mesh, bspecs))
        out_sh = NamedSharding(mesh, _sanitize(mesh, P(dp, "model")))
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        args = (params_sds, batch_sds)
    else:  # decode
        step = make_serve_step(cfg)
        ins = input_specs(cfg, shape)
        cspecs = _retarget_dp(lm_mod.cache_specs(cfg), dp)
        in_sh = (_ns(mesh, pspecs),
                 NamedSharding(mesh, _sanitize(mesh, P(dp, None))),
                 _ns(mesh, cspecs))
        out_sh = (NamedSharding(mesh, _sanitize(mesh, P(dp))),
                  NamedSharding(mesh, _sanitize(mesh, P(dp, "model"))),
                  _ns(mesh, cspecs))
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(2,))
        args = (params_sds, ins["tokens"], ins["cache"])
    return _compile_and_analyze(fn, args, mesh, cfg=cfg, shape=shape)


def lower_lp_cell(lp_name: str, mesh, n_inner: int = 64):
    from ..distributed.pdhg_dist import make_dist_step
    from ..distributed.sharding import axis_size, col_axes, padded_dim, row_axes

    lpc = LP_CONFIGS[lp_name]
    Rax, Cax = row_axes(mesh), col_axes(mesh)
    R, C = axis_size(mesh, Rax), axis_size(mesh, Cax)
    m, n = padded_dim(lpc.m, R), padded_dim(lpc.n, C)
    dt = jnp.dtype(lpc.dtype)
    tdt = jnp.dtype(lpc.tile_dtype)
    step = make_dist_step(mesh, n_inner=n_inner)
    sds = lambda *s: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    args = (jax.ShapeDtypeStruct((m, n), tdt),   # device-resident K tiles
            sds(m), sds(n), sds(n), sds(n), sds(n), sds(m),
            sds(n), sds(n), sds(m), jax.ShapeDtypeStruct((), dt),
            jax.ShapeDtypeStruct((), dt))
    specs = (P(Rax, Cax), P(Rax), P(Cax), P(Cax), P(Cax), P(Cax), P(Rax),
             P(Cax), P(Cax), P(Rax), P(), P())
    in_sh = tuple(NamedSharding(mesh, s) for s in specs)
    out_sh = tuple(NamedSharding(mesh, s)
                   for s in (P(Cax), P(Cax), P(Rax), P(), P()))
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
    return _compile_and_analyze(fn, args, mesh, lp=lpc, n_inner=n_inner)


def _compile_and_analyze(fn, args, mesh, cfg=None, shape=None, lp=None,
                         n_inner=None):
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    text = compiled.as_text()
    colls = hlo_mod.parse_collectives(text)
    est = hlo_mod.estimate_costs(text)
    n_chips = mesh.devices.size
    out = {
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_chips": int(n_chips),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_bytes": (mem.argument_size_in_bytes
                                      + mem.temp_size_in_bytes
                                      + mem.output_size_in_bytes
                                      - mem.alias_size_in_bytes),
        },
        "xla_cost": {
            "flops_per_device_static": float(cost.get("flops", -1.0)),
            "bytes_accessed_static": float(cost.get("bytes accessed", -1.0)),
        },
        "hlo_estimate": est.as_dict(),       # trip-scaled, per device
        "collectives": colls.as_dict(),      # trip-scaled, per device
    }
    if cfg is not None:
        n_params = cfg.param_count()
        n_active = cfg.active_param_count()
        tokens = (shape.global_batch * shape.seq_len
                  if shape.kind != "decode" else shape.global_batch)
        mult = 6.0 if shape.kind == "train" else 2.0
        out["model"] = {
            "arch": cfg.name,
            "shape": shape.name,
            "kind": shape.kind,
            "params": n_params,
            "active_params": n_active,
            "tokens_per_step": tokens,
            "model_flops": mult * n_active * tokens,
        }
    if lp is not None:
        # 2 MVMs per PDHG iteration over the (m, n) tile grid
        out["model"] = {
            "arch": lp.name,
            "shape": f"dist_step_x{n_inner}",
            "kind": "lp",
            "model_flops": 2.0 * 2.0 * lp.m * lp.n * n_inner,
        }
    # roofline terms (seconds) — spec formulas, HLO totals = per_device*chips
    flops_total = est.flops * n_chips
    bytes_total = est.bytes * n_chips
    out["roofline"] = {
        "compute_s": flops_total / (n_chips * PEAK_FLOPS),
        "memory_s": bytes_total / (n_chips * HBM_BW),
        "collective_s": colls.total_bytes * n_chips / (n_chips * ICI_BW),
    }
    terms = out["roofline"]
    out["roofline"]["bottleneck"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    if cfg is not None or lp is not None:
        mf = out["model"]["model_flops"]
        out["roofline"]["model_flops_ratio"] = (
            mf / flops_total if flops_total > 0 else 0.0)
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             sharding_mode: str = "fsdp", verbose: bool = True,
             cfg_overrides=None, prefill_last_only=True, tag_suffix="",
             tcfg=None):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}_{shape_name}_{mesh_name}{tag_suffix}"
    path = os.path.join(out_dir, f"{tag}.json")
    mesh = make_production_mesh(multi_pod=multi_pod)
    jax.set_mesh(mesh)
    try:
        if arch in LP_CONFIGS:
            result = lower_lp_cell(arch, mesh)
        else:
            result = lower_lm_cell(
                arch, shape_name, mesh, sharding_mode=sharding_mode,
                cfg_overrides=cfg_overrides,
                prefill_last_only=prefill_last_only,
                **({"tcfg": tcfg} if tcfg is not None else {}))
        result["cell"] = tag
        result["sharding_mode"] = sharding_mode
        if cfg_overrides:
            result["cfg_overrides"] = cfg_overrides
        result["prefill_last_only"] = prefill_last_only
        status = "SKIP" if "skipped" in result else "OK"
    except Exception as e:  # noqa: BLE001
        result = {"cell": tag, "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-2000:]}
        status = "FAIL"
    os.makedirs(out_dir, exist_ok=True)
    with open(path, "w") as f:
        json.dump(result, f, indent=2)
    if verbose:
        if status == "OK":
            mem = result["memory"]["peak_per_device_bytes"] / 2**30
            rf = result["roofline"]
            print(f"[{status}] {tag}: peak/dev={mem:.2f}GiB "
                  f"compute={rf['compute_s']:.3e}s "
                  f"memory={rf['memory_s']:.3e}s "
                  f"collective={rf['collective_s']:.3e}s "
                  f"bottleneck={rf['bottleneck']} "
                  f"(compile {result['compile_s']:.0f}s)", flush=True)
        elif status == "SKIP":
            print(f"[{status}] {tag}: {result['skipped']}", flush=True)
        else:
            print(f"[{status}] {tag}: {result['error']}", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="arch id or lp config (lp_crossbar/lp_64k/lp_256k)")
    ap.add_argument("--shape", default="train_4k",
                    help="train_4k|prefill_32k|decode_32k|long_500k|dist_step")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--sharding", default="fsdp", choices=["fsdp", "dp"])
    ap.add_argument("--out", default="experiments/dryrun")
    # hillclimb knobs
    ap.add_argument("--ssm-chunk", type=int, default=None,
                    help="override ModelConfig.ssm_chunk")
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--prefill-naive", action="store_true",
                    help="materialize full (B,S,V) logits in prefill")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache for decode cells")
    ap.add_argument("--microbatch", type=int, default=8)
    ap.add_argument("--tag-suffix", default="")
    args = ap.parse_args()
    overrides = {}
    if args.ssm_chunk is not None:
        overrides["ssm_chunk"] = args.ssm_chunk
    if args.attn_chunk is not None:
        overrides["attn_chunk"] = args.attn_chunk
    if args.kv_int8:
        overrides["kv_cache_int8"] = True

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh]
    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in SHAPES:
                cells.append((arch, shape))
        for lp in LP_CONFIGS:
            cells.append((lp, "dist_step"))
    else:
        cells.append((args.arch, args.shape))
    for multi_pod in meshes:
        for arch, shape in cells:
            run_cell(arch, shape, multi_pod, args.out,
                     sharding_mode=args.sharding,
                     cfg_overrides=overrides or None,
                     prefill_last_only=not args.prefill_naive,
                     tag_suffix=args.tag_suffix,
                     tcfg=TrainConfig(optimizer=args.optimizer,
                                      microbatch=args.microbatch))


if __name__ == "__main__":
    main()

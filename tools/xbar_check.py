"""The crossbar cell's program against its plain reference on the chip,
and where its device time goes, by bucket and by device scope.

    python -m tools.xbar_check [--call 0] [--out FILE]

For one call of the ``crossbar-t1`` cell's pool (``BENCHMARK.json``):

* reads: each tile bucket's instances are scaled and programmed as the
  bucket program does it (``prep_scale``, then ``encode_core`` on the
  tile-padded M), and compiled, vmapped Pallas reads of those
  conductances with a read-noise draw are compared with
  ``bench/xbar_reference.read`` in float64 on the host (relative error
  in the 2-norm over the bucket's lanes): the read of the whole pair
  (``kernels.ops.crossbar_mvm``), and the windowed ``fwd`` and ``adj``
  products of ``engine.crossbar_operator``, as the cell runs them;
* ledgers: the call is solved through the cell's entry, and every
  answer's ledger is compared with ``bench/xbar_reference.ledger``, fed
  the programmed pairs the reference counts on the same scaled M and
  the reads it counts from the answer's executed iterations;
* time: each bucket's instances are solved again alone under the
  profiler; the device's operations are joined by name to that bucket
  program's compiled HLO and their self time summed by the innermost
  ``repro.*`` scope of their ``op_name``.

Prints one JSON line per bucket and a summary line, naming the device.
Refuses to run (exit 2) without a TPU.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "crossbar-t1"
SCOPE = re.compile(r"repro\.[a-z]+")
HLO_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')
EVENT_NAME = re.compile(r"^%?([\w.\-]+)")


def _paths():
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def scope_of(op_name: str) -> str:
    """The innermost ``repro.*`` scope of an ``op_name``."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else "none"


def op_scopes(hlo_text: str) -> dict:
    """Instruction name -> innermost scope, from compiled HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = HLO_LINE.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2))
    return out


def _event_op(ev) -> str:
    stats = dict((k, v) for k, v in getattr(ev, "stats", ()))
    if "hlo_op" in stats:
        return str(stats["hlo_op"])
    m = EVENT_NAME.match(ev.name)
    return m.group(1) if m else ev.name


def device_ops(trace_dir: str):
    """[(start_ns, end_ns, op)] of the first accelerator plane's XLA ops."""
    from jax.profiler import ProfileData

    from bench import devtrace

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    profile = ProfileData.from_file(paths[-1])
    for plane in profile.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            line = devtrace._op_line(plane)
            if line is not None:
                return [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                         _event_op(ev)) for ev in line.events]
    return []


def by_scope(ops, scopes: dict) -> dict:
    """Self seconds of the device operations per scope."""
    from bench import devtrace

    tot = collections.Counter()
    for (s, e, op), own in zip(ops, devtrace.self_times(ops)):
        tot[scopes.get(op, "unjoined")] += own * 1e-9
    return dict(tot)


def _rel(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def check(call: int, trace_root: str) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import devtrace, spec, traffic, xbar_reference
    from repro.core import engine
    from repro.core.symblock import build_sym_block
    from repro.crossbar import encode_core
    from repro.crossbar.solver import _array_dims
    from repro.kernels import ops
    from repro.runtime.batch import prep_scale, stack_problems

    cell = spec.resolve(spec.load_benchmark(), CELL)
    cfg = cell.config
    insts = traffic.make_calls(dict(cell.mix, distinct_calls=call + 1),
                               cfg, 0)
    insts = next(c for c in insts if c[0].name.endswith(f".{call}"))
    entry = spec.load_file(spec.entry_path(cell.entry)).make(cfg)
    lps = entry.prepare(insts)
    solver = entry.solver
    opts, device = solver.opts, solver.device
    t0 = time.perf_counter()
    answers = entry.solve(lps)           # compiles (or loads) every bucket
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    answers = entry.solve(lps)
    call_s = time.perf_counter() - t0
    stats = dict(solver.last_stream_stats)
    groups = collections.defaultdict(list)
    for i, lp in enumerate(lps):
        groups[solver._bucket(*lp.K.shape)].append(i)
    lines = []
    for (mb, nb), idxs in groups.items():
        B = solver._padded_batch(len(idxs))
        group = [lps[i] for i in idxs]
        stacked = stack_problems(group + [group[0]] * (B - len(group)),
                                 m=mb, n=nb)
        Ks = np.asarray(jax.jit(jax.vmap(
            lambda K, b, c, lb, ub: prep_scale(K, b, c, lb, ub, opts)[0]))(
                *(jnp.asarray(a, opts.dtype) for a in stacked)))
        R, C = _array_dims(mb, nb, device)

        def program(K, key):
            M = build_sym_block(K)
            Mp = jnp.zeros((R, C), M.dtype).at[:mb + nb, :mb + nb].set(M)
            return encode_core(Mp, key, device.g_levels,
                               device.sigma_program)[:3]

        keys = jax.random.split(jax.random.PRNGKey(call), 3 * B)
        gp, gn, scale = jax.jit(jax.vmap(program))(jnp.asarray(Ks),
                                                   keys[:B])
        v = jax.vmap(lambda k: jax.random.normal(k, (C,), gp.dtype))(
            keys[B:2 * B])
        noise = device.sigma_read * jnp.clip(jax.vmap(
            lambda k: jax.random.normal(k, (R,), gp.dtype))(keys[2 * B:]),
            -4.0, 4.0)
        got = jax.jit(jax.vmap(lambda a, b, s, z, x: ops.crossbar_mvm(
            a, b, x, s, z)))(gp, gn, scale, noise, v)
        gp_, gn_, scale_, noise_, v_ = (np.asarray(a) for a in (
            gp, gn, scale, noise, v))
        read_err = {"read_rel_err": _rel(got, xbar_reference.read(
            gp_, gn_, scale_, noise_, v_))}
        for mode, cols, rows in (("fwd", slice(mb, mb + nb), slice(0, mb)),
                                 ("adj", slice(0, mb), slice(mb, mb + nb))):
            got = jax.jit(jax.vmap(
                lambda a, b, s, x, k, mode=mode: getattr(
                    engine.crossbar_operator(
                        a, b, s, mb, nb, sigma_read=device.sigma_read),
                    mode)(x, k)))(gp, gn, scale, v[:, cols], keys[2 * B:])
            vm = np.zeros_like(v_)
            vm[:, cols] = v_[:, cols]
            read_err[f"{mode}_rel_err"] = _rel(got, xbar_reference.read(
                gp_, gn_, scale_, noise_, vm)[:, rows])

        worst = 0.0
        for k, i in enumerate(idxs):
            M = np.zeros((R, C), Ks.dtype)
            M[:mb, mb:mb + nb] = Ks[k]
            M[mb:mb + nb, :mb] = Ks[k].T
            m, n = lps[i].K.shape
            a = answers[i]
            want = xbar_reference.ledger(
                cfg["device_model"],
                xbar_reference.programmed_pairs(M, device.g_levels),
                (m + n) ** 2, R * C,
                xbar_reference.reads(a["executed_iterations"],
                                     opts.check_every, opts.lanczos_iters))
            for key_, v_ in want.items():
                worst = max(worst, abs(a["ledger"][key_] - v_)
                            / max(abs(v_), 1e-300))

        hlo = next(exe.as_text() for key_, exe in solver._cache.items()
                   if key_[0] == ("dense", mb, nb) and key_[1] == B)
        tdir = os.path.join(trace_root, f"{mb}x{nb}")
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        solver.solve_stream(group)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        dev = device_ops(tdir)
        busy = devtrace.covered(devtrace.merge((s, e) for s, e, _ in dev),
                                0, 2 ** 62) * 1e-9 if dev else None
        scopes = op_scopes(hlo)
        top = collections.Counter()
        for (s, e, op), own in zip(dev, devtrace.self_times(dev)):
            top[op] += own * 1e-9
        lines.append({
            "bucket": [mb, nb], "lanes": B,
            "instances": [lps[i].name for i in idxs],
            "iterations": [answers[i]["iterations"] for i in idxs],
            "executed_iterations": answers[idxs[0]]["executed_iterations"],
            "status": [answers[i]["status"] for i in idxs],
            **read_err, "ledger_max_rel_diff": worst,
            "alone_wall_s": wall, "alone_busy_s": busy,
            "scope_self_s": by_scope(dev, scopes),
            "hlo_scopes": sorted(set(scopes.values())),
            "top_ops_s": top.most_common(8),
            "device_op_events": len(dev)})
        print(json.dumps(lines[-1]), flush=True)
    d = jax.devices()[0]
    summary = {"summary": True, "device": d.device_kind,
               "platform": d.platform, "call": call,
               "first_call_s": first_s, "call_s": call_s,
               "stream_stats": stats,
               "read_rel_err_max": max(x[k] for x in lines for k in (
                   "read_rel_err", "fwd_rel_err", "adj_rel_err")),
               "ledger_max_rel_diff": max(x["ledger_max_rel_diff"]
                                          for x in lines)}
    print(json.dumps(summary), flush=True)
    return lines + [summary]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--call", type=int, default=0,
                    help="which call of the cell's pool")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _paths()
    import jax

    from repro.runtime.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    if jax.devices()[0].platform != "tpu":
        print("xbar_check: needs a TPU", file=sys.stderr)
        return 2
    lines = check(args.call, os.path.join(ROOT, "bench", "out",
                                          "xbar_trace"))
    if args.out:
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

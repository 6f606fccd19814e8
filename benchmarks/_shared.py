"""Shared benchmark machinery: run each (instance x backend) solve once,
cache the results + energy ledgers, let every table module read from the
cache.  Mirrors the paper's experimental setup (§5.1):

  instances : Table-1 shapes (generated with known optima — see
              DESIGN.md ground-truth caveat)
  backends  : gpuPDLP (analytic RTX6000 cost model wrapping the exact
              jitted solver), EpiRAM, TaOx-HfOx (device-physics sim)
  metrics   : relative objective gap (eq. 13), per-phase energy/latency
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

CACHE_PATH = os.environ.get(
    "REPRO_BENCH_CACHE", os.path.join("experiments", "bench_cache.json"))

INSTANCES = ["gen-ip002", "gen-ip016", "gen-ip021", "gen-ip036",
             "gen-ip054", "neos5", "assign1-5-8"]
BACKENDS = ["gpuPDLP", "EpiRAM", "TaOx-HfOx"]

MAX_ITERS = int(os.environ.get("REPRO_BENCH_MAX_ITERS", "30000"))
TOL = 1e-6


def _solve_all():
    import jax
    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    from repro.core import (
        PDHGOptions, encode_exact, lanczos_svd, solve_jit)
    from repro.crossbar import (
        EPIRAM, TAOX_HFOX, Ledger, RTX6000, solve_crossbar_jit)
    from repro.crossbar.encode import encode_matrix
    from repro.core.symblock import build_sym_block, scaled_accel, Accel
    from repro.lp import table1_instance

    results = {}
    for name in INSTANCES:
        lp = table1_instance(name)
        m, n = lp.K.shape
        true_sigma = float(np.linalg.svd(np.asarray(lp.K),
                                         compute_uv=False)[0])
        opts = PDHGOptions(max_iters=MAX_ITERS, tol=TOL, check_every=100,
                           lanczos_iters=48)
        inst = {"shape": [int(m), int(n)], "obj_opt": float(lp.obj_opt),
                "sigma_true": true_sigma, "backends": {}}

        # ---- gpuPDLP: exact solve + analytic GPU cost model ------------
        import jax as _jax
        t0 = time.perf_counter()
        acc = encode_exact(lp.K)
        lres = lanczos_svd(acc, k_max=64, tol=1e-10)
        res = solve_jit(lp, opts)
        # results are host numpy today; the explicit fence keeps the
        # wall-clock honest under async dispatch (jaxlint R7)
        _jax.block_until_ready((lres.sigma_max, res.obj))
        wall = time.perf_counter() - t0
        led = Ledger()
        nbytes = 8 * (m * n + m + n)
        RTX6000.h2d(nbytes, led)
        for _ in range(lres.iterations):
            RTX6000.lanczos_iteration(m + n, led)
        lan_snapshot = led.snapshot()
        for _ in range(res.iterations):
            RTX6000.pdhg_iteration(m, n, led)
        RTX6000.d2h(8 * (m + n), led)
        inst["backends"]["gpuPDLP"] = {
            "wall_s": wall,
            "lanczos": {
                "sigma": float(lres.sigma_max),
                "k": int(lres.iterations),
                "gap": abs(lres.sigma_max - true_sigma) / true_sigma,
                "energy_j": lan_snapshot.total_energy_j,
                "latency_s": lan_snapshot.total_latency_s,
                "breakdown": lan_snapshot.as_dict(),
            },
            "pdhg": {
                "obj": float(res.obj),
                "k": int(res.iterations),
                "gap": abs(res.obj - lp.obj_opt) / abs(lp.obj_opt),
                "energy_j": led.total_energy_j - lan_snapshot.total_energy_j,
                "latency_s": (led.total_latency_s
                              - lan_snapshot.total_latency_s),
                "breakdown": led.diff(lan_snapshot).as_dict(),
            },
            "total": led.as_dict(),
        }

        # ---- RRAM devices ---------------------------------------------
        for dev in (EPIRAM, TAOX_HFOX):
            t0 = time.perf_counter()
            # Lanczos phase on the device (noisy MVMs through encoded M)
            import jax as _jax
            led = Ledger()
            # deliberate fixed programming key: Table-1 numbers must be
            # reproducible across benchmark runs
            enc = encode_matrix(
                build_sym_block(np.asarray(lp.K)), dev,
                _jax.random.PRNGKey(1),  # jaxlint: disable=R2
                ledger=led)
            Mp = enc.decode()

            def noisy_mvm(v, key=None, _Mp=Mp, _dev=dev, _led=led,
                          _cells=enc.active_cells):
                w = _Mp @ v
                _led.read_energy_j += _dev.read_energy_per_cell_j * _cells
                _led.read_latency_s += _dev.read_latency_s
                _led.mvm_count += 1
                if key is not None:
                    g = _jax.random.normal(key, w.shape, w.dtype)
                    w = w * (1.0 + _dev.sigma_read * g)
                return w

            acc = Accel(mvm_full=noisy_mvm, m=m, n=n, name="crossbar:bench")
            lres = lanczos_svd(acc, k_max=64, tol=1e-10,
                               noise_keys=True)
            lan_snapshot = led.snapshot()
            rep = solve_crossbar_jit(lp, opts, device=dev, ledger=led)
            _jax.block_until_ready((lres.sigma_max, rep.result.obj))
            wall = time.perf_counter() - t0
            res = rep.result
            inst["backends"][dev.name] = {
                "wall_s": wall,
                "lanczos": {
                    "sigma": float(lres.sigma_max),
                    "k": int(lres.iterations),
                    "gap": abs(lres.sigma_max - true_sigma) / true_sigma,
                    "energy_j": lan_snapshot.total_energy_j,
                    "latency_s": lan_snapshot.total_latency_s,
                    "breakdown": lan_snapshot.as_dict(),
                },
                "pdhg": {
                    "obj": float(res.obj),
                    "k": int(res.iterations),
                    "gap": abs(res.obj - lp.obj_opt) / abs(lp.obj_opt),
                    "energy_j": (led.total_energy_j
                                 - lan_snapshot.total_energy_j),
                    "latency_s": (led.total_latency_s
                                  - lan_snapshot.total_latency_s),
                    "breakdown": led.diff(lan_snapshot).as_dict(),
                },
                "total": led.as_dict(),
            }
        results[name] = inst
    return results


def cached_results(refresh: bool = False):
    if not refresh and os.path.exists(CACHE_PATH):
        with open(CACHE_PATH) as f:
            return json.load(f)
    results = _solve_all()
    os.makedirs(os.path.dirname(CACHE_PATH) or ".", exist_ok=True)
    with open(CACHE_PATH, "w") as f:
        json.dump(results, f, indent=1)
    return results


def fmt_factor(gpu: float, dev: float) -> str:
    if dev <= 0:
        return "--"
    return f"{gpu / dev:.2f}x"

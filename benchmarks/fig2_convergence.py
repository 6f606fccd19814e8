"""Paper Figure 2: convergence (primal residual / optimality gap) versus
simulated latency on gen-ip054, for EpiRAM / TaOx-HfOx / GPU.

Writes a CSV trace per accelerator under experiments/fig2/ and prints a
coarse ASCII rendition (this container has no display)."""
from __future__ import annotations

import os

import numpy as np

OUT_DIR = os.path.join("experiments", "fig2")


def run(max_iters: int = 30000):
    import jax
    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    from repro.core import PDHGOptions, solve
    from repro.crossbar import EPIRAM, RTX6000, TAOX_HFOX, Ledger
    from repro.crossbar.array import crossbar_accel_factory
    from repro.lp import table1_instance

    lp = table1_instance("gen-ip054")
    m, n = lp.K.shape
    opts = PDHGOptions(max_iters=max_iters, tol=1e-7, check_every=200,
                       track_history=True, lanczos_iters=40)
    traces = {}

    # GPU: exact solve; latency from the analytic per-iteration model
    res = solve(lp, opts)
    led = Ledger()
    RTX6000.pdhg_iteration(m, n, led)
    per_iter_gpu = led.solve_latency_s
    traces["GPU"] = [
        (h["iter"] * per_iter_gpu, h["r_pri"],
         abs(h["obj"] - lp.obj_opt) / abs(lp.obj_opt))
        for h in res.history
    ]

    for dev in (EPIRAM, TAOX_HFOX):
        fac = crossbar_accel_factory(device=dev)
        res = solve(lp, opts, accel_factory=fac)
        per_iter = 2 * dev.read_latency_s
        traces[dev.name] = [
            (h["iter"] * per_iter, h["r_pri"],
             abs(h["obj"] - lp.obj_opt) / abs(lp.obj_opt))
            for h in res.history
        ]
    os.makedirs(OUT_DIR, exist_ok=True)
    for name, tr in traces.items():
        with open(os.path.join(OUT_DIR, f"{name}.csv"), "w") as f:
            f.write("latency_s,r_pri,opt_gap\n")
            for t, rp, g in tr:
                f.write(f"{t:.6e},{rp:.6e},{g:.6e}\n")
    return traces


def ascii_plot(traces, field: int = 2, width: int = 70, height: int = 16):
    lines = []
    pts = []
    for name, tr in traces.items():
        for t, rp, g in tr:
            v = (rp, g)[field - 1]
            if t > 0 and v > 0:
                pts.append((np.log10(t), np.log10(v), name[0]))
    if not pts:
        return ""
    xs = [p[0] for p in pts]
    ys = [p[1] for p in pts]
    x0, x1 = min(xs), max(xs)
    y0, y1 = min(ys), max(ys)
    grid = [[" "] * width for _ in range(height)]
    for x, y, c in pts:
        i = int((x - x0) / max(x1 - x0, 1e-9) * (width - 1))
        j = int((y1 - y) / max(y1 - y0, 1e-9) * (height - 1))
        grid[j][i] = c
    lines.append(f"log10(metric) {y1:.1f} .. {y0:.1f} | "
                 f"log10(latency s) {x0:.1f} .. {x1:.1f}")
    lines.extend("".join(row) for row in grid)
    lines.append("G=GPU  E=EpiRAM  T=TaOx-HfOx")
    return "\n".join(lines)


def main():
    traces = run()
    print("fig2: traces written to", OUT_DIR)
    for name, tr in traces.items():
        print(f"  {name}: {len(tr)} checkpoints, "
              f"final gap {tr[-1][2]:.2e} at {tr[-1][0]:.2f}s")
    print(ascii_plot(traces))


if __name__ == "__main__":
    main()

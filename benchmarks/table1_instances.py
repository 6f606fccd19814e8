"""Paper Table 1: problem types, sizes, objective values, solve times.

Instances are generated to the MIPLIB-2017 shapes with known optima
(DESIGN.md ground-truth caveat); "solve time" is the bundled simplex
oracle (Gurobi stand-in) on instances small enough, else the
high-precision jitted PDHG.
"""
from __future__ import annotations

import time


def run():
    import jax
    from repro.runtime.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_enable_x64", True)
    from repro.lp import TABLE1_SIZES, simplex, table1_instance

    rows = []
    for name, (m, n) in TABLE1_SIZES.items():
        lp = table1_instance(name)
        t0 = time.perf_counter()
        if lp.K.shape[1] <= 120:
            r = simplex.solve(lp)
            solver, obj = "simplex", r.obj
        else:
            from repro.core import PDHGOptions, solve_jit
            r = solve_jit(lp, PDHGOptions(max_iters=60000, tol=1e-8))
            solver, obj = "pdhg-hp", r.obj
        # simplex branch is pure host; solve_jit returns host numpy —
        # the fence makes the window honest either way (jaxlint R7)
        jax.block_until_ready(obj)
        dt = time.perf_counter() - t0
        rows.append((name, f"{m}x{n}", f"{lp.obj_opt:.4f}", f"{obj:.4f}",
                     solver, f"{dt:.2f}"))
    header = ("problem", "size(mxn)", "known_obj", "solved_obj", "oracle",
              "time_s")
    return header, rows


def main():
    header, rows = run()
    print(",".join(header))
    for r in rows:
        print(",".join(str(x) for x in r))


if __name__ == "__main__":
    main()

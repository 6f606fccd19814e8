"""Distribution runtime tests.

Single-device meshes exercise the full shard_map code paths here; the
8-fake-device equivalence test runs in a subprocess (XLA device count is
process-global and must stay 1 for everything else)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import PDHGOptions, solve_jit
from repro.distributed import (
    CheckpointManager,
    load_checkpoint,
    reshard,
    save_checkpoint,
    solve_batch,
    stack_problems,
)
from repro.distributed.pdhg_dist import solve_dist
from repro.launch.mesh import make_mesh
from repro.lp import random_standard_lp


def test_solve_dist_single_device_mesh(x64):
    mesh = make_mesh((1, 1), ("data", "model"))
    lp = random_standard_lp(10, 18, seed=0)
    opts = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)
    r = solve_dist(lp, mesh, opts)
    assert r.status == "optimal"
    assert abs(r.obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4


def test_solve_dist_residual_components_are_real(x64):
    """Regression (ISSUE 4): ``solve_dist`` used to stuff the scalar
    in-loop merit into all four ``KKTResiduals`` fields, so
    ``residuals.as_dict()`` reported r_pri == r_dual == r_iter == r_gap.
    The components must now be the actual per-component KKT residuals of
    the unscaled solution — matching a dense ``kkt_residuals``
    evaluation on the same (x, y)."""
    from repro.core.residuals import kkt_residuals

    mesh = make_mesh((1, 1), ("data", "model"))
    lp = random_standard_lp(10, 18, seed=0)
    opts = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)
    r = solve_dist(lp, mesh, opts)
    got = r.residuals.as_dict()
    # four genuinely distinct components (the old bug made them equal)
    assert len({f"{v:.12e}" for v in got.values()}) > 1, got
    import jax.numpy as jnp
    want = kkt_residuals(
        jnp.asarray(r.x), jnp.asarray(r.x), jnp.asarray(r.y),
        jnp.asarray(lp.c), jnp.asarray(lp.b),
        jnp.asarray(lp.K @ r.x), jnp.asarray(lp.K.T @ r.y),
        lb=jnp.asarray(lp.lb), ub=jnp.asarray(lp.ub)).as_dict()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-12,
                                   err_msg=k)
    # the in-loop merit still drives status, and the post-hoc noiseless
    # residuals must corroborate the claimed convergence
    assert r.status == "optimal"
    assert float(r.residuals.max) < 10 * opts.tol


def test_solve_dist_auto_single_process_fallback(x64):
    """``solve_dist_auto`` degrades to the local mesh when the env
    names no cluster — existing entry points work unchanged."""
    from repro.distributed import solve_dist_auto
    from repro.runtime import cluster as cluster_mod

    cluster_mod._reset_for_tests()
    try:
        lp = random_standard_lp(10, 18, seed=0)
        opts = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)
        r = solve_dist_auto(lp, opts, cluster="off")
        assert r.status == "optimal"
        assert abs(r.obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4
    finally:
        cluster_mod._reset_for_tests()


def test_batch_solve(x64):
    mesh = make_mesh((1,), ("data",))
    lps = [random_standard_lp(8, 14, seed=s) for s in range(3)]
    Ks, bs, cs, lbs, ubs = stack_problems(lps)
    out = solve_batch(Ks, bs, cs, lbs, ubs, mesh,
                      PDHGOptions(max_iters=20000, tol=1e-6, check_every=64))
    objs = np.einsum("bn,bn->b", cs, out["x"])
    for lp, obj in zip(lps, objs):
        assert abs(obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4


def test_checkpoint_atomicity_and_roundtrip(tmp_path):
    path = str(tmp_path / "ck.npz")
    arrays = {"x": np.arange(10.0), "nested/w": np.ones((3, 4))}
    save_checkpoint(path, 7, arrays, {"tag": "t"})
    ck = load_checkpoint(path)
    assert ck.step == 7
    assert ck.meta["tag"] == "t"
    np.testing.assert_array_equal(ck.arrays["x"], np.arange(10.0))
    # overwrite is atomic (file is always loadable)
    save_checkpoint(path, 8, arrays)
    assert load_checkpoint(path).step == 8


def test_checkpoint_manager_rotation(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, every=10)
    for step in range(1, 51):
        mgr.maybe_save(step, {"a": np.zeros(2)})
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 2
    assert mgr.latest().endswith("ckpt_000000000050.npz")


def test_elastic_reshard_roundtrip(tmp_path):
    """Save on one mesh topology, restore onto another."""
    path = str(tmp_path / "ck.npz")
    arrays = {"w": np.arange(32.0).reshape(8, 4)}
    save_checkpoint(path, 1, arrays)
    ck = load_checkpoint(path)
    mesh = make_mesh((1,), ("data",))
    placed = reshard(ck.arrays, mesh, {"w": P("data", None)})
    np.testing.assert_array_equal(np.asarray(placed["w"]), arrays["w"])


def test_quantize_roundtrip():
    from repro.distributed import dequantize_int8, quantize_int8
    import jax.numpy as jnp

    x = jnp.asarray(np.random.default_rng(0).normal(size=256),
                    jnp.float32)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    rel = float(jnp.abs(back - x).max() / jnp.abs(x).max())
    assert rel < 1.0 / 100            # int8 grid error


MULTIDEV_SCRIPT = textwrap.dedent("""
    from repro.runtime.mesh import request_cpu_devices
    assert request_cpu_devices(8), "backend initialized too early"
    import jax
    jax.config.update("jax_enable_x64", True)
    import numpy as np
    from repro.lp import random_standard_lp
    from repro.core import PDHGOptions, solve_jit
    from repro.distributed.pdhg_dist import solve_dist
    from repro.runtime.mesh import make_mesh

    assert len(jax.devices()) == 8
    lp = random_standard_lp(24, 40, seed=11)
    opts = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)
    r_single = solve_jit(lp, opts)
    for shape, axes in [((2, 4), ("data", "model")),
                        ((2, 2, 2), ("pod", "data", "model"))]:
        mesh = make_mesh(shape, axes)
        r = solve_dist(lp, mesh, opts)
        rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel < 1e-4, (shape, rel)
        print(f"OK {shape} obj={r.obj:.8f} iters={r.iterations}")
    print("MULTIDEV PASS")
""")


@pytest.mark.slow
def test_distributed_solve_multidevice_subprocess():
    """2-axis and 3-axis sharded PDHG on 8 fake devices == known optimum."""
    from conftest import repo_root, subprocess_env

    proc = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT], env=subprocess_env(),
        cwd=repo_root(), capture_output=True, text=True, timeout=900,
    )
    assert "MULTIDEV PASS" in proc.stdout, proc.stdout + proc.stderr

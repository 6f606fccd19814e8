"""Runtime layer: mesh API and sharding helpers, bucketed batching."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PDHGOptions, solve_jit
from repro.lp import random_standard_lp
from repro.runtime import BatchSolver, mesh as rmesh, solve_stream
from repro.runtime.batch import bucket_dims, pad_problem, stack_problems
from repro.runtime.mesh import make_local_mesh, make_mesh

OPTS = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)


# ------------------------------------------------- sharding helpers ---

def test_compat_shims_resolve_on_installed_jax():
    """Meshes are built with Auto axes, and without an ambient mesh the
    helpers report none."""
    mesh = make_mesh((1,), ("data",))
    assert tuple(mesh.axis_names) == ("data",)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    assert jax.sharding.get_abstract_mesh().empty
    assert rmesh.mesh_axis_names() == ()
    assert rmesh.batch_axes() == ()


def test_compat_constrain_no_mesh_is_identity():
    x = jnp.ones((4, 8))
    out = rmesh.constrain(x, "data", None)
    assert out is x or np.array_equal(np.asarray(out), np.asarray(x))


def test_compat_use_mesh_scopes_ambient_mesh():
    mesh = make_mesh({"data": 1})
    with jax.set_mesh(mesh):
        assert rmesh.mesh_axis_names() == ("data",)
        assert rmesh.batch_axes() == ("data",)
        # constraining against the ambient mesh works inside jit
        y = jax.jit(lambda v: rmesh.constrain(v, "data") * 2)(jnp.ones(4))
        np.testing.assert_array_equal(np.asarray(y), 2 * np.ones(4))
    assert "data" not in rmesh.mesh_axis_names()


def test_compat_shard_map_psum():
    mesh = make_mesh({"data": 1})
    from jax.sharding import PartitionSpec as P

    f = jax.shard_map(
        lambda x: jax.lax.psum(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P(), check_vma=False)
    out = f(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


# -------------------------------------------------------------- mesh ---

def test_make_mesh_roundtrips_axes_single_device():
    mesh = make_mesh({"data": 1, "model": 1})
    assert tuple(mesh.axis_names) == ("data", "model")
    assert tuple(mesh.devices.shape) == (1, 1)
    legacy = make_mesh((1, 1), ("data", "model"))
    assert tuple(legacy.axis_names) == tuple(mesh.axis_names)
    pairs = make_mesh([("data", 1), ("model", 1)])
    assert tuple(pairs.axis_names) == ("data", "model")


def test_make_mesh_capacity_error_names_the_fallback():
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_mesh({"data": 4096, "model": 4096})


def test_make_local_mesh_covers_all_devices():
    mesh = make_local_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert tuple(mesh.axis_names) == ("data", "model")


def test_make_production_mesh_pod_axis_from_process_count(monkeypatch):
    """Regression (ISSUE 5): ``multi_pod=True`` used to hard-code a
    2-pod axis regardless of how many processes the cluster actually
    has.  The pod axis must now derive from the process count (the old
    2 survives only as the single-process dry-run default)."""
    from repro.runtime import cluster, mesh as rmesh

    # single process: legacy 2-pod dry-run grid
    monkeypatch.setattr(cluster, "pod_count", lambda: 1)
    assert rmesh._default_pod_count() == 2
    # multi-process: one pod per process
    for n in (2, 3, 8):
        monkeypatch.setattr(cluster, "pod_count", lambda n=n: n)
        assert rmesh._default_pod_count() == n
    # the derived count reaches the mesh: with a shrunken per-pod grid
    # the pod axis is exactly the process count (build it if this host
    # has the devices; otherwise the capacity error must name it)
    monkeypatch.setattr(cluster, "pod_count", lambda: 3)
    try:
        mesh = rmesh.make_production_mesh(multi_pod=True, grid=(1, 1))
        assert tuple(mesh.devices.shape) == (3, 1, 1)
        assert tuple(mesh.axis_names) == ("pod", "data", "model")
    except RuntimeError as e:
        assert "'pod': 3" in str(e)
    # explicit override beats derivation
    mesh1 = rmesh.make_production_mesh(multi_pod=True, pods=1, grid=(1, 1))
    assert tuple(mesh1.devices.shape) == (1, 1, 1)


def test_make_cluster_mesh_single_process_fallback():
    """Single-process: a 1-pod mesh over all local devices, so callers
    need no separate code path."""
    from repro.runtime.mesh import make_cluster_mesh

    mesh = make_cluster_mesh()
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert mesh.shape["pod"] == max(1, jax.process_count())
    assert mesh.devices.size == len(jax.devices())


@pytest.mark.slow
def test_make_mesh_multidevice_subprocess():
    """make_mesh round-trips axis names/sizes on 8 fan-out CPU devices."""
    from conftest import repo_root, subprocess_env

    script = textwrap.dedent("""
        from repro.runtime.mesh import request_cpu_devices
        assert request_cpu_devices(8)
        import jax
        from repro.runtime.mesh import make_mesh
        mesh = make_mesh({"pod": 2, "data": 2, "model": 2})
        assert tuple(mesh.axis_names) == ("pod", "data", "model")
        assert tuple(mesh.devices.shape) == (2, 2, 2)
        assert len(jax.devices()) == 8
        print("MESH PASS")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], env=subprocess_env(),
        cwd=repo_root(), capture_output=True, text=True, timeout=300)
    assert "MESH PASS" in proc.stdout, proc.stdout + proc.stderr


# ----------------------------------------------------- compile cache ---

@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_enable_compile_cache_one_fixed_directory(monkeypatch, tmp_path,
                                                  env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache
    goes to the one fixed directory inside the checkout."""
    from repro.runtime import cache

    old = jax.config.jax_compilation_cache_dir
    sentinel = str(tmp_path / "untouched")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    try:
        if env_dir is None:
            monkeypatch.delenv(cache.ENV_VAR, raising=False)
            want = str(cache.CHECKOUT_CACHE_DIR)
            assert cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert cache.CHECKOUT_CACHE_DIR.parent == \
                cache.Path(__file__).resolve().parents[1]
        else:
            monkeypatch.setenv(cache.ENV_VAR, str(tmp_path / env_dir))
            assert cache.enable_compile_cache() == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == sentinel
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


# ------------------------------------------------------------- batch ---

def test_bucket_dims_power_of_two():
    assert bucket_dims(8, 14) == (8, 16)
    assert bucket_dims(9, 16) == (16, 16)
    assert bucket_dims(1, 1) == (8, 8)          # floor
    assert bucket_dims(129, 300) == (256, 512)


def test_bucket_dims_device_tile_mode():
    """Tile mode snaps to multiples of the physical crossbar dims."""
    assert bucket_dims(8, 70, tile=(64, 64)) == (64, 128)
    assert bucket_dims(64, 64, tile=(64, 64)) == (64, 64)
    assert bucket_dims(65, 1, tile=(64, 32)) == (128, 32)
    assert bucket_dims(1, 1, tile=(64, 64)) == (64, 64)
    # device models feed their geometry straight in
    from repro.crossbar import EPIRAM
    tile = (EPIRAM.crossbar_rows, EPIRAM.crossbar_cols)
    assert bucket_dims(20, 70, tile=tile) == (64, 128)


def test_pad_problem_preserves_optimum(x64):
    lp = random_standard_lp(8, 14, seed=3)
    padded = pad_problem(lp, 16, 32)
    assert padded.K.shape == (16, 32)
    r = solve_jit(padded, OPTS)
    rel = abs(r.obj - lp.obj_opt) / abs(lp.obj_opt)
    assert r.status == "optimal" and rel < 1e-4


def test_pad_problem_preserves_dtype():
    """Regression (ISSUE 4): padding used to allocate ``np.zeros`` in
    the default float64 regardless of ``lp.K.dtype``, doubling host
    memory for f32 streams before the device cast."""
    from repro.lp import StandardLP

    rng = np.random.default_rng(0)
    lp32 = StandardLP(
        c=rng.normal(size=14).astype(np.float32),
        K=rng.normal(size=(8, 14)).astype(np.float32),
        b=rng.normal(size=8).astype(np.float32),
        lb=np.zeros(14, np.float32), ub=np.full(14, np.inf, np.float32))
    assert lp32.K.dtype == np.float32          # StandardLP preserves f32
    padded = pad_problem(lp32, 16, 32)
    for field in ("K", "b", "c", "lb", "ub"):
        assert getattr(padded, field).dtype == np.float32, field
    # f64 problems still pad in f64
    padded64 = pad_problem(random_standard_lp(8, 14, seed=0), 16, 32)
    assert padded64.K.dtype == np.float64
    # and stacking follows the padded dtype (no silent promotion)
    Ks, bs, cs, lbs, ubs = stack_problems([lp32, lp32])
    assert Ks.dtype == np.float32 and cs.dtype == np.float32


def test_solve_stream_async_matches_sync(x64):
    """Submit-all-then-collect dispatch returns the SAME results as
    blocking per-bucket serving (async is pure scheduling, not math)."""
    lps = [
        random_standard_lp(8, 14, seed=0),
        random_standard_lp(10, 18, seed=1),
        random_standard_lp(20, 34, seed=2),
        random_standard_lp(7, 13, seed=3),
    ]
    opts = PDHGOptions(max_iters=2000, tol=1e-4, check_every=64,
                       lanczos_iters=16)
    r_async = BatchSolver(opts).solve_stream(lps)
    r_sync = BatchSolver(opts, async_dispatch=False).solve_stream(lps)
    for a, s in zip(r_async, r_sync):
        assert a.name == s.name and a.iterations == s.iterations
        np.testing.assert_allclose(a.x, s.x)
        assert a.merit == s.merit


def test_solve_stream_records_stream_stats(x64):
    """Every solve_stream call audits what it stacked and when it
    dispatched/collected (the serving observability surface)."""
    solver = BatchSolver(PDHGOptions(max_iters=128, tol=1e-30,
                                     check_every=64, lanczos_iters=8))
    solver.solve_stream([random_standard_lp(8, 14, seed=0),
                         random_standard_lp(20, 34, seed=1)])
    st = solver.last_stream_stats
    assert st["n_buckets"] == 2
    assert st["dense_stack_bytes"] > 0
    assert st["sparse_stack_bytes"] == 0
    assert st["dispatch_s"] >= 0 and st["collect_s"] >= 0


def test_stack_problems_legacy_max_shape():
    lps = [random_standard_lp(8, 14, seed=0), random_standard_lp(6, 11, seed=1)]
    Ks, bs, cs, lbs, ubs = stack_problems(lps)
    assert Ks.shape == (2, 8, 14) and cs.shape == (2, 14)
    # padded variables are pinned at zero
    assert np.all(lbs[1, 11:] == 0) and np.all(ubs[1, 11:] == 0)


def test_solve_stream_mixed_shapes_matches_single_solve(x64):
    """>= 3 distinct-shape LPs in ONE call, each matching the
    single-solve objective to <= 1e-4 relative gap."""
    lps = [
        random_standard_lp(8, 14, seed=0),
        random_standard_lp(10, 18, seed=1),
        random_standard_lp(20, 34, seed=2),
        random_standard_lp(7, 13, seed=3),
    ]
    assert len({lp.K.shape for lp in lps}) >= 3
    results = solve_stream(lps, OPTS)
    assert [r.name for r in results] == [lp.name for lp in lps]
    for lp, r in zip(lps, results):
        single = solve_jit(lp, OPTS)
        assert r.converged, (lp.K.shape, r.merit)
        assert abs(r.obj - single.obj) / max(abs(single.obj), 1e-12) < 1e-4
        assert abs(r.obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4
        assert r.x.shape == (lp.K.shape[1],)
        assert r.y.shape == (lp.K.shape[0],)


def test_solve_stream_executable_cache_hits_on_repeat_shapes(x64):
    solver = BatchSolver(OPTS)
    first = solver.solve_stream([random_standard_lp(8, 14, seed=0),
                                 random_standard_lp(7, 13, seed=1)])
    assert solver.cache_info() == {"hits": 0, "misses": 1, "entries": 1}
    # same bucket, same batch size, new instances -> compiled reuse
    second = solver.solve_stream([random_standard_lp(6, 12, seed=2),
                                  random_standard_lp(8, 15, seed=3)])
    assert solver.cache_hits == 1 and solver.cache_misses == 1
    # a genuinely new bucket still compiles
    third = solver.solve_stream([random_standard_lp(20, 40, seed=4)] * 2)
    assert solver.cache_misses == 2
    for r in first + second + third:
        assert r.converged


def test_solve_stream_on_mesh(x64):
    """The zero-collective data-parallel path through an explicit mesh."""
    mesh = make_mesh({"data": 1})
    lps = [random_standard_lp(8, 14, seed=s) for s in range(3)]
    results = solve_stream(lps, OPTS, mesh=mesh)
    for lp, r in zip(lps, results):
        assert abs(r.obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4


def test_batch_instances_get_distinct_streams(x64):
    """Regression: every instance in a bucket used to share PRNGKey(1),
    giving identical inits and read-noise streams.  Two copies of the
    SAME problem must now follow different trajectories."""
    lp = random_standard_lp(8, 14, seed=4)
    opts = PDHGOptions(max_iters=128, tol=1e-30, check_every=64)
    solver = BatchSolver(opts, sigma_read=0.01)
    r = solver.solve_stream([lp, lp])
    assert not np.allclose(r[0].x, r[1].x)
    assert r[0].merit != r[1].merit


def test_batch_sigma_read_is_applied(x64):
    """Regression: the batched path used to drop ``sigma_read`` on the
    floor (always solving noiselessly)."""
    lp = random_standard_lp(8, 14, seed=5)
    opts = PDHGOptions(max_iters=256, tol=1e-30, check_every=64)
    clean = BatchSolver(opts).solve_stream([lp])[0]
    noisy = BatchSolver(opts, sigma_read=0.05).solve_stream([lp])[0]
    assert not np.allclose(clean.x, noisy.x)


def test_batch_seed_reaches_bucket_pipeline(x64):
    """opts.seed drives the per-instance keys of the compiled pipeline."""
    lp = random_standard_lp(8, 14, seed=6)
    mk = lambda s: PDHGOptions(  # noqa: E731
        max_iters=128, tol=1e-30, check_every=64, seed=s)
    r0 = BatchSolver(mk(0)).solve_stream([lp])[0]
    r0b = BatchSolver(mk(0)).solve_stream([lp])[0]
    r1 = BatchSolver(mk(7)).solve_stream([lp])[0]
    np.testing.assert_allclose(r0.x, r0b.x)
    assert not np.allclose(r0.x, r1.x)


# --------------------------------------------------- crossbar streaming ---

CB_OPTS = PDHGOptions(max_iters=2000, tol=1e-3, check_every=64,
                      lanczos_iters=16)


def test_crossbar_stream_bucket_reuse_and_cache(x64):
    """Device-tile-aware serving: distinct shapes share one tile bucket,
    encode+solve compiles once per (bucket, batch, device) signature,
    and per-instance ledgers survive."""
    from repro.crossbar import EPIRAM, TAOX_HFOX, CrossbarBatchSolver

    solver = CrossbarBatchSolver(CB_OPTS, device=EPIRAM)
    lps = [random_standard_lp(8, 14, seed=0), random_standard_lp(7, 12, seed=1)]
    reports = solver.solve_stream(lps)
    assert solver.cache_info() == {"hits": 0, "misses": 1, "entries": 1}
    for lp, rep in zip(lps, reports):
        assert rep.result.x.shape == (lp.K.shape[1],)
        rel = abs(rep.result.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel < 5e-2      # device physics (quantization + read noise)
        assert rep.ledger.write_energy_j > 0
        assert rep.ledger.write_energy_padding_j > 0   # 64x64 tile, small LP
        assert rep.ledger.mvm_count == rep.lanczos_mvms + rep.pdhg_mvms

    # same tile bucket + batch size, new instances -> compiled reuse
    solver.solve_stream([random_standard_lp(9, 13, seed=2),
                         random_standard_lp(6, 10, seed=3)])
    assert solver.cache_info() == {"hits": 1, "misses": 1, "entries": 1}

    # the executable cache key carries the device model
    other = CrossbarBatchSolver(CB_OPTS, device=TAOX_HFOX)
    other.solve_stream([random_standard_lp(8, 14, seed=0),
                        random_standard_lp(7, 12, seed=1)])
    assert other.cache_misses == 1
    assert set(other._cache).isdisjoint(set(solver._cache))


def test_crossbar_stream_rectangular_tiles_ledger_whole_tiles(x64):
    """With non-square tiles the symmetric block M lands mid-tile in one
    dimension; the ledger must still account whole physical tiles."""
    import dataclasses as dc

    from repro.crossbar import EPIRAM, CrossbarBatchSolver

    dev = dc.replace(EPIRAM, name="rect", crossbar_rows=32, crossbar_cols=16)
    opts = PDHGOptions(max_iters=128, tol=1.0, check_every=64,
                       lanczos_iters=4)
    lp = random_standard_lp(8, 14, seed=0)      # bucket (32, 16), M is 48x48
    rep = CrossbarBatchSolver(opts, device=dev).solve_stream([lp])[0]
    # M tile-pads to (64, 48): rows to 2x32, cols already 3x16
    assert rep.ledger.cells_written == 2 * 64 * 48
    assert rep.ledger.cells_written_padding == 2 * (64 * 48 - (8 + 14) ** 2)


def test_crossbar_stream_matches_per_instance_jit(x64):
    """Batched encode->solve agrees with the single-instance crossbar
    path on a mixed-shape stream (both sit at the device noise floor)."""
    from repro.crossbar import TAOX_HFOX, solve_crossbar_jit, \
        solve_crossbar_stream

    lps = [
        random_standard_lp(8, 14, seed=0),
        random_standard_lp(10, 18, seed=3),
        random_standard_lp(16, 28, seed=4),
        random_standard_lp(20, 70, seed=2),     # second tile bucket
    ]
    opts = PDHGOptions(max_iters=8000, tol=1e-4, check_every=64,
                       lanczos_iters=32)
    reports = solve_crossbar_stream(lps, opts, device=TAOX_HFOX)
    tile = (TAOX_HFOX.crossbar_rows, TAOX_HFOX.crossbar_cols)
    for lp, rep in zip(lps, reports):
        single = solve_crossbar_jit(
            pad_problem(lp, *bucket_dims(*lp.K.shape, tile=tile)),
            opts, device=TAOX_HFOX)
        assert rep.result.x.shape == (lp.K.shape[1],)
        rel_b = abs(rep.result.obj - lp.obj_opt) / abs(lp.obj_opt)
        rel_s = abs(single.result.obj - lp.obj_opt) / abs(lp.obj_opt)
        assert rel_b < 5e-2, (lp.name, rel_b)
        assert rel_s < 5e-2, (lp.name, rel_s)
        agree = abs(rep.result.obj - single.result.obj) \
            / max(abs(single.result.obj), 1e-12)
        assert agree < 1e-1, (lp.name, agree)

"""Multi-host serving layer: routing, transport, cluster solver, harness.

Fast tests cover the deterministic routing/cost model, the env-driven
cluster bring-up fallback, the fault.py-snapshot transport, and the
single-process ClusterBatchSolver parity guarantees (virtual pods force
the reroute path without any second process).  The ``slow``-marked tests
spawn real coordinator+worker processes over localhost through
``tests/_cluster_harness.py`` — including one with an actual
``jax.distributed.initialize`` — and assert the routed stream is
bitwise-identical to the single-process path, with and without a worker
being killed mid-stream.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import _cluster_harness as harness
from conftest import repo_root, subprocess_env

from repro.core import PDHGOptions
from repro.distributed.fault import load_checkpoint
from repro.lp import random_standard_lp
from repro.runtime import BatchSolver, ClusterBatchSolver
from repro.runtime import cluster as cluster_mod
from repro.runtime.cluster import (
    DirectoryTransport,
    bucket_cost,
    bucket_tag,
    route_buckets,
)

OPTS = PDHGOptions(max_iters=2000, tol=1e-4, check_every=64,
                   lanczos_iters=16)


def _stream():
    return [random_standard_lp(8, 14, seed=0),
            random_standard_lp(10, 18, seed=1),
            random_standard_lp(20, 34, seed=2),
            random_standard_lp(7, 13, seed=3)]


# ------------------------------------------------------------- routing ---

def test_bucket_cost_model():
    """Cost = padded FLOPs per MVM x queue depth; ELL buckets pay for
    their gather slots, not the logical dense rectangle."""
    assert bucket_cost(((16, 32), None), 4) == 2 * 16 * 32 * 4
    ell = ((128, 256), ("ell", 8, 4))
    assert bucket_cost(ell, 4) == (128 * 8 + 256 * 4) * 4
    # an ELL bucket is cheaper than its dense twin whenever its slots
    # are fewer than the dense cell count
    assert bucket_cost(ell, 4) < bucket_cost(((128, 256), None), 4)


def test_route_buckets_lpt_and_determinism():
    keys = [((64, 64), None), ((16, 32), None), ((8, 16), None)]
    costs = {k: bucket_cost(k, 8) for k in keys}
    routing = route_buckets(costs, 2)
    # the heaviest bucket lands alone; the two lighter ones balance it
    assert routing[((64, 64), None)] == 0
    assert routing[((16, 32), None)] == 1
    assert routing[((8, 16), None)] == 1
    # pure function of (costs, n_pods): insertion order is irrelevant
    shuffled = {k: costs[k] for k in reversed(keys)}
    assert route_buckets(shuffled, 2) == routing
    # single pod: everything local
    assert set(route_buckets(costs, 1).values()) == {0}
    # more pods than buckets: no pod gets two before another gets one
    spread = route_buckets(costs, 8)
    assert len(set(spread.values())) == len(keys)


def test_bucket_tag_distinguishes_sparse_and_dense():
    ell = ((16, 32), ("ell", 8, 4))
    assert bucket_tag(((16, 32), None)) != bucket_tag(ell)
    assert bucket_tag(((16, 32), None)) == "16x32-dense"
    assert bucket_tag(ell) == "16x32-ell8x4"


# ------------------------------------------------------- cluster init ---

def test_detect_env_requires_complete_spec(monkeypatch):
    for var in ("REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
                "REPRO_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    assert cluster_mod.detect_env() is None
    monkeypatch.setenv("REPRO_COORDINATOR", "host0:1234")
    assert cluster_mod.detect_env() is None       # partial spec: no cluster
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2")
    monkeypatch.setenv("REPRO_PROCESS_ID", "1")
    spec = cluster_mod.detect_env()
    assert spec == {"coordinator_address": "host0:1234",
                    "num_processes": 2, "process_id": 1}
    # a 1-process "cluster" is the single-process fallback
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "1")
    assert cluster_mod.detect_env() is None


def test_init_cluster_single_process_fallback(monkeypatch):
    for var in ("REPRO_COORDINATOR", "REPRO_NUM_PROCESSES",
                "REPRO_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    cluster_mod._reset_for_tests()
    try:
        info = cluster_mod.init_cluster("auto")
        assert info.num_processes == 1 and info.process_id == 0
        assert not info.is_multiprocess and info.is_coordinator
        # idempotent: the same resolution comes back
        assert cluster_mod.init_cluster("auto") is info
        assert cluster_mod.pod_count() == 1 and cluster_mod.pod_id() == 0
    finally:
        cluster_mod._reset_for_tests()


def test_detect_env_tolerates_malformed_values(monkeypatch):
    """Stray/typo'd numeric vars mean 'no cluster', never a crash (the
    single-process fallback contract)."""
    monkeypatch.setenv("REPRO_COORDINATOR", "host0:1234")
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2x")
    monkeypatch.setenv("REPRO_PROCESS_ID", "0")
    assert cluster_mod.detect_env() is None
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "2")
    monkeypatch.setenv("REPRO_PROCESS_ID", "zero")
    assert cluster_mod.detect_env() is None


def test_cluster_solver_multiprocess_requires_shared_transport(monkeypatch,
                                                               tmp_path):
    """A private mkdtemp per pod would silently hide every result from
    the other pods — a live multi-process solver without a shared
    transport dir must fail loudly at construction."""
    monkeypatch.delenv("REPRO_TRANSPORT_DIR", raising=False)
    monkeypatch.setattr(cluster_mod, "pod_count", lambda: 2)
    with pytest.raises(RuntimeError, match="REPRO_TRANSPORT_DIR"):
        ClusterBatchSolver(OPTS, pod=0, n_pods=2)
    # ...but the env var satisfies it
    shared = str(tmp_path / "shared")
    monkeypatch.setenv("REPRO_TRANSPORT_DIR", shared)
    s = ClusterBatchSolver(OPTS, pod=0, n_pods=2)
    assert s.transport.root == shared
    assert not s._owns_transport


def test_cluster_solver_owned_scratch_is_cleaned_per_stream(x64):
    """Single-process virtual-pod serving with no explicit transport
    uses a private scratch dir and leaves nothing behind."""
    lps = [random_standard_lp(8, 14, seed=0)]
    solver = ClusterBatchSolver(OPTS, pod=0, n_pods=2, live_pods=1,
                                straggler_timeout=30.0)
    assert solver._owns_transport
    solver.solve_stream(lps)
    assert os.listdir(solver.transport.root) == []


def test_init_cluster_off_ignores_env(monkeypatch):
    monkeypatch.setenv("REPRO_COORDINATOR", "nowhere:1")
    monkeypatch.setenv("REPRO_NUM_PROCESSES", "4")
    monkeypatch.setenv("REPRO_PROCESS_ID", "2")
    cluster_mod._reset_for_tests()
    try:
        info = cluster_mod.init_cluster("off")
        assert info.num_processes == 1 and not info.initialized
    finally:
        cluster_mod._reset_for_tests()
    with pytest.raises(ValueError, match="auto|off"):
        cluster_mod.init_cluster("definitely")


# ----------------------------------------------------------- transport ---

def test_transport_publish_fetch_and_manifest(tmp_path):
    tr = DirectoryTransport(str(tmp_path))
    key = ((16, 32), None)
    routing = {key: 1, ((8, 16), None): 0}
    tr.publish_manifest(0, routing, {"n_pods": 2})
    ck = tr.fetch_manifest(0)
    assert ck.meta["routing"] == {"16x32-dense": 1, "8x16-dense": 0}
    # nothing published yet: both pods' buckets are pending
    assert tr.pending_from_manifest(0, [0, 1]) == ["16x32-dense",
                                                   "8x16-dense"] or \
        set(tr.pending_from_manifest(0, [0, 1])) == {"16x32-dense",
                                                     "8x16-dense"}
    assert tr.try_fetch_bucket(0, bucket_tag(key)) is None
    tr.publish_bucket(0, bucket_tag(key), 1,
                      {"xs": np.ones((2, 3))}, {"idxs": [0, 1]})
    got = tr.try_fetch_bucket(0, bucket_tag(key))
    np.testing.assert_array_equal(got.arrays["xs"], np.ones((2, 3)))
    assert got.meta["idxs"] == [0, 1] and got.meta["pod"] == 1
    # pod 1's pending list is now empty; pod 0 still owes its bucket
    assert tr.pending_from_manifest(0, [1]) == []
    assert tr.pending_from_manifest(0, [0]) == ["8x16-dense"]
    # streams are isolated
    assert tr.try_fetch_bucket(1, bucket_tag(key)) is None


def test_transport_never_observes_torn_writes(tmp_path):
    """A crash mid-publish leaves a *.tmp the reader never opens."""
    tr = DirectoryTransport(str(tmp_path))
    sd = tr._stream_dir(0)
    with open(os.path.join(sd, "bucket_16x32-dense.npz.tmp"), "wb") as f:
        f.write(b"\x00garbage torn write")
    assert tr.try_fetch_bucket(0, "16x32-dense") is None


# ------------------------------------------- single-process cluster ---

def test_cluster_solver_single_pod_is_base_solver(x64):
    lps = _stream()
    base = BatchSolver(OPTS).solve_stream(lps)
    clus = ClusterBatchSolver(OPTS, n_pods=1).solve_stream(lps)
    for b, c in zip(base, clus):
        assert np.array_equal(b.x, c.x) and np.array_equal(b.y, c.y)
        assert b.merit == c.merit and b.iterations == c.iterations


def test_cluster_solver_virtual_pod_reroute_bitwise(x64, tmp_path):
    """Buckets routed to a pod with no live process are rerouted by the
    coordinator — and the results are bitwise-identical to the
    single-process path (keys derive from global stream positions)."""
    lps = _stream()
    base = BatchSolver(OPTS).solve_stream(lps)
    solver = ClusterBatchSolver(
        OPTS, pod=0, n_pods=2, live_pods=1,
        transport=DirectoryTransport(str(tmp_path)),
        straggler_timeout=30.0)
    routed = solver.solve_stream(lps)
    st = solver.last_stream_stats
    assert st["n_pods"] == 2
    assert st["rerouted_buckets"] > 0          # pod 1 is virtual
    assert st["n_local_buckets"] < st["n_buckets"]
    assert set(st["routing"].values()) == {0, 1}
    for b, c in zip(base, routed):
        assert np.array_equal(b.x, c.x), b.name
        assert np.array_equal(b.y, c.y)
        assert b.merit == c.merit and b.iterations == c.iterations
    # the rerouted buckets were published for (hypothetical) survivors,
    # and the manifest snapshot shows nothing pending anywhere
    assert solver.transport.pending_from_manifest(0, [0, 1]) == []


def test_cluster_solver_repeat_streams_use_fresh_transport_dirs(x64,
                                                                tmp_path):
    """A warm solver serves stream after stream without colliding on the
    transport (per-stream subdirectories) and keeps its executable
    cache across them."""
    lps = _stream()
    solver = ClusterBatchSolver(
        OPTS, pod=0, n_pods=2, live_pods=1,
        transport=DirectoryTransport(str(tmp_path)),
        straggler_timeout=30.0)
    first = solver.solve_stream(lps)
    misses = solver.cache_misses
    second = solver.solve_stream(lps)
    assert solver.cache_misses == misses       # warm: no recompilation
    for a, b in zip(first, second):
        assert np.array_equal(a.x, b.x)
    assert solver.stream_seq == 2


def test_cluster_solver_gather_timeout_raises(x64, tmp_path):
    """A non-coordinator pod that never receives a remote bucket fails
    loudly (StragglerTimeout) instead of hanging forever."""
    from repro.runtime.cluster import StragglerTimeout

    lps = _stream()
    solver = ClusterBatchSolver(
        OPTS, pod=1, n_pods=2, live_pods=2,
        transport=DirectoryTransport(str(tmp_path)),
        straggler_timeout=0.2, gather_timeout=1.0)
    with pytest.raises(StragglerTimeout):
        solver.solve_stream(lps)


# ------------------------------------------------- multi-process harness ---

def _wait(proc: subprocess.Popen, timeout: float) -> str:
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail(f"harness pod timed out; output so far:\n{out}")
    return out


def _single_process_reference():
    """The bitwise ground truth, computed in-process on the SAME stream
    the harness pods rebuild from (n, seed)."""
    lps = harness.build_stream()
    results = BatchSolver(harness.harness_opts()).solve_stream(lps)
    return harness.results_arrays(lps, results)


@pytest.mark.slow
def test_harness_two_process_routed_stream_bitwise(x64, tmp_path):
    """Coordinator + worker over localhost (real jax.distributed
    bring-up): a mixed 16-instance stream routed across 2 pods returns
    results bitwise-identical to single-process ``solve_stream``."""
    out = str(tmp_path / "final.npz")
    env = subprocess_env()
    coord = f"localhost:{harness.free_port()}"
    procs = [harness.spawn_pod(p, 2, str(tmp_path / "transport"),
                               out=out, jaxdist=coord, env=env,
                               straggler_timeout=180.0)
             for p in (1, 0)]
    logs = [_wait(p, 600) for p in procs]
    for p, log in zip(procs, logs):
        assert p.returncode == 0, log
    log0 = logs[1]
    assert "HARNESS JAXDIST OK" in log0, log0
    assert "HARNESS POD0 DONE" in log0, log0
    ck = load_checkpoint(out)
    # both pods actually served something
    assert set(ck.meta["routing"].values()) == {0, 1}, ck.meta
    assert ck.meta["rerouted"] == 0, ck.meta
    ref = _single_process_reference()
    for k, v in ref.items():
        np.testing.assert_array_equal(ck.arrays[k], v, err_msg=k)


@pytest.mark.slow
def test_harness_worker_killed_mid_stream_reroutes(x64, tmp_path):
    """Kill the worker mid-stream (stalled before publishing anything):
    the coordinator's straggler policy reroutes the worker's pending
    buckets through the manifest snapshot and the final iterates are
    STILL bitwise-identical to the single-process path."""
    out = str(tmp_path / "final.npz")
    env = subprocess_env()
    tdir = str(tmp_path / "transport")
    worker = harness.spawn_pod(1, 2, tdir, stall_after=0, env=env)
    time.sleep(2.0)                    # worker is now solving or stalled
    worker.kill()                      # ... either way: dead mid-stream
    coord = harness.spawn_pod(0, 2, tdir, out=out, straggler_timeout=5.0,
                              env=env)
    log0 = _wait(coord, 600)
    worker.communicate()
    assert coord.returncode == 0, log0
    assert "HARNESS POD0 DONE" in log0, log0
    ck = load_checkpoint(out)
    assert ck.meta["rerouted"] > 0, (ck.meta, log0)
    ref = _single_process_reference()
    for k, v in ref.items():
        np.testing.assert_array_equal(ck.arrays[k], v, err_msg=k)


@pytest.mark.slow
def test_launch_solve_cluster_flags_smoke():
    """--cluster auto (single-process fallback) + --pods 2 virtual
    routing through the CLI."""
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.solve", "--backend", "batch",
         "--pods", "2", "--cluster", "auto",
         "--instances", "rand:8x14,rand:10x18",
         "--max-iters", "500", "--tol", "1e-3"],
        env=subprocess_env(), cwd=repo_root(), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "cluster: pod=0/2" in proc.stdout, proc.stdout
    assert "routing=" in proc.stdout

"""The host spans of ``BatchSolver.solve_stream`` and the device scopes
of its bucket programs, read back from a profiler trace and the
compiled HLO."""
import glob
import os
import re
import time

import jax
import pytest

from repro.core import PDHGOptions, engine
from repro.lp import random_standard_lp, sparse_random_standard_lp
from repro.runtime import BatchSolver
from repro.runtime.batch import STREAM_PHASES, STREAM_SPAN, bucket_tag

OPTS = PDHGOptions(max_iters=256, tol=1e-30, check_every=64,
                   lanczos_iters=8)
SHAPES = ((8, 14), (10, 18), (20, 34))     # three buckets
PER_BUCKET = ("stack", "upload", "dispatch", "wait", "collect")


def _lps(storage):
    if storage == "dense":
        return [random_standard_lp(m, n, seed=k)
                for k, (m, n) in enumerate(SHAPES)]
    return [sparse_random_standard_lp(m, n, density=0.3, seed=k)
            for k, (m, n) in enumerate(SHAPES)]


def _traced_call(solver, lps, trace_dir):
    """One ``solve_stream`` call under the profiler: its host-clock
    seconds, its stats and the ``repro.stream`` spans of the trace as
    ``(start_ns, end_ns, name, args)``."""
    jax.profiler.start_trace(trace_dir)
    try:
        t0 = time.perf_counter()
        solver.solve_stream(lps)
        wall = time.perf_counter() - t0
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(STREAM_SPAN):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, dict(ev.stats)))
    return wall, dict(solver.last_stream_stats), spans


@pytest.fixture(scope="module", params=["dense", "ell"])
def stream(request, tmp_path_factory):
    """A cold and a warm traced call over three tiny LPs in three
    buckets, K dense or as nonzeros (ELL)."""
    old = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    try:
        lps = _lps(request.param)
        solver = BatchSolver(OPTS)
        tags = {bucket_tag(k) for k in solver._group_buckets(lps)}
        cold = _traced_call(solver, lps, str(tmp_path_factory.mktemp("c")))
        warm = _traced_call(solver, lps, str(tmp_path_factory.mktemp("w")))
        yield solver, tags, cold, warm
    finally:
        jax.config.update("jax_enable_x64", old)


def _named(spans, phase):
    return [s for s in spans if s[2] == f"{STREAM_SPAN}.{phase}"]


def test_stream_spans_nest_in_one_root(stream):
    _, tags, cold, warm = stream
    for _, _, spans in (cold, warm):
        root, = [s for s in spans if s[2] == STREAM_SPAN]
        assert root[3] == {"instances": 3, "buckets": 3}
        children = [s for s in spans if s[2] != STREAM_SPAN]
        assert {s[2] for s in children} <= {
            f"{STREAM_SPAN}.{p}" for p in STREAM_PHASES}
        assert all(root[0] <= s[0] and s[1] <= root[1] for s in children)
        assert len(_named(spans, "group")) == 1


def test_stream_spans_once_per_bucket(stream):
    _, tags, cold, warm = stream
    for _, _, spans in (cold, warm):
        for phase in PER_BUCKET:
            assert sorted(s[3]["bucket"] for s in _named(spans, phase)) \
                == sorted(tags), phase
        for s in _named(spans, "stack"):
            assert s[3]["lanes"] == 1 and s[3]["instances"] == 1
            assert s[3]["bytes"] > 0


def test_stream_compile_span_on_cache_misses_only(stream):
    solver, tags, cold, warm = stream
    assert sorted(s[3]["bucket"] for s in _named(cold[2], "compile")) \
        == sorted(tags)
    assert _named(warm[2], "compile") == []
    assert cold[1]["compile_s"] > 0 and warm[1]["compile_s"] == 0.0
    assert solver.cache_info()["misses"] == len(tags)


def test_stream_phase_seconds_match_spans_and_fit_the_call(stream):
    _, _, cold, warm = stream
    for wall, stats, spans in (cold, warm):
        phases = [stats[f"{p}_s"] for p in STREAM_PHASES]
        assert all(v >= 0 for v in phases)
        assert sum(phases) <= wall
        for p in STREAM_PHASES:
            traced = sum(e - s for s, e, _, _ in _named(spans, p)) * 1e-9
            assert stats[f"{p}_s"] == pytest.approx(traced, abs=1e-3), p


def test_bucket_programs_carry_device_scopes(stream):
    """Every scope reaches the compiled program's ``op_name`` metadata
    (under ``vmap`` a scope reads ``vmap(repro.prep)``)."""
    solver = stream[0]
    for text in solver.hlo_texts():
        scopes = {m.group(1) for m in re.finditer(
            r'[/(](repro\.[a-z]+)[/)]',
            " ".join(re.findall(r'op_name="([^"]*)"', text)))}
        assert scopes == {engine.PREP_SCOPE, engine.NORM_SCOPE,
                          engine.WINDOW_SCOPE, engine.CHECK_SCOPE}


def test_dispatch_spans_name_each_bucket_operator(x64, tmp_path,
                                                  monkeypatch):
    """In a stream of dense and ELL buckets, ``dense_operator_buckets``
    counts the ELL buckets the rule sends dense, and every dispatch span
    names the operator its bucket's program multiplies by."""
    from repro.kernels import sparse_mvm

    # the byte cap between the two smaller ELL buckets and the largest
    monkeypatch.setattr(sparse_mvm, "DENSE_OPERATOR_MAX_BYTES", 16 * 32 * 8)
    lps = _lps("dense") + _lps("ell")
    solver = BatchSolver(OPTS)
    expected = {}
    for key, idxs in solver._group_buckets(lps).items():
        (mb, nb), sig = key
        dense = sig is None or sparse_mvm.ell_goes_dense(
            solver._padded_batch(len(idxs)), mb, nb, sig[1], sig[2], 8)
        expected[bucket_tag(key)] = (sig is not None, "dense" if dense
                                     else "ell")
    assert sorted(v for v in expected.values() if v[0]) == [
        (True, "dense"), (True, "dense"), (True, "ell")]
    _, stats, spans = _traced_call(solver, lps, str(tmp_path))
    assert stats["dense_operator_buckets"] == 2
    assert {s[3]["bucket"]: s[3]["operator"]
            for s in _named(spans, "dispatch")} == {
        tag: op for tag, (_, op) in expected.items()}


def test_crossbar_stream_names_its_operator_and_scopes(x64, tmp_path):
    """The crossbar subclass: every dispatch span names the ``crossbar``
    operator, its bucket programs carry the programming and refinement
    scopes besides the engine's, and the call's ledger totals are in
    ``last_stream_stats``."""
    from repro.crossbar import EPIRAM, CrossbarBatchSolver

    opts = PDHGOptions(max_iters=256, tol=1e-3, check_every=64,
                       lanczos_iters=8, refine_rounds=1, refine_tol=1e-4)
    solver = CrossbarBatchSolver(opts, device=EPIRAM)
    _, stats, spans = _traced_call(solver, _lps("dense"), str(tmp_path))
    assert {s[3]["operator"] for s in _named(spans, "dispatch")} == \
        {"crossbar"}
    for text in solver.hlo_texts():
        scopes = {m.group(1) for m in re.finditer(
            r'[/(](repro\.[a-z]+)[/)]',
            " ".join(re.findall(r'op_name="([^"]*)"', text)))}
        assert scopes == {engine.PREP_SCOPE, engine.NORM_SCOPE,
                          engine.ENCODE_SCOPE, engine.REFINE_SCOPE,
                          engine.WINDOW_SCOPE, engine.CHECK_SCOPE}
    assert stats["digital_mvms"] == 3 * 4
    assert stats["analog_mvms"] > 0 and stats["cells_written"] > 0
    assert stats["write_energy_j"] > 0 and stats["read_energy_j"] > 0

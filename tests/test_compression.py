"""Quantized-collective (``distributed.compression``) and stacked-batch
(``distributed.batch_solve``) contracts.

Round-trip / error-bound properties of the int8 pipeline, unbiasedness
of the stochastic-rounding mode, and parity of the compressed psum with
the exact (uncompressed) collective at high bit width — plus the
stacked same-shape serving path against the single-instance solver.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import PDHGOptions, solve_jit
from repro.distributed import solve_batch, stack_problems
from repro.distributed.compression import (
    _stochastic_round,
    compressed_psum,
    dequantize_int8,
    quantize_int8,
)
from repro.lp import random_standard_lp
from repro.runtime.mesh import make_mesh


def _x(n=256, seed=0, scale=3.0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(scale=scale, size=n),
        jnp.float32)


# -------------------------------------------------- (de)quantization ---

def test_quantize_int8_error_bound():
    """Deterministic rounding lands within half a quantization step
    everywhere (no clipping bias: the max-abs element maps to ±127)."""
    x = _x()
    q, scale = quantize_int8(x)
    assert q.dtype == jnp.int8
    back = dequantize_int8(q, scale)
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert err.max() <= 0.5 * float(scale) * (1 + 1e-6)
    # the extreme element is represented exactly at the grid edge
    i = int(jnp.argmax(jnp.abs(x)))
    assert abs(int(q[i])) == 127


def test_quantize_roundtrip_exact_on_grid():
    """Values already on the int8 grid survive the round trip exactly."""
    ints = jnp.arange(-127, 128, dtype=jnp.float32)
    q, scale = quantize_int8(ints)
    np.testing.assert_array_equal(np.asarray(q), np.arange(-127, 128))
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, scale)),
                               np.asarray(ints), rtol=1e-6)


def test_stochastic_round_is_unbiased():
    """E[stochastic_round(x)] == x: the mean over many keys converges to
    the unquantized value (this is what preserves Assumption 2)."""
    x = jnp.asarray([0.25, 1.75, -2.4, 3.0, -0.1], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 4096)
    rounded = jax.vmap(lambda k: _stochastic_round(x, k))(keys)
    mean = np.asarray(rounded).mean(axis=0)
    # integers round to themselves, always
    np.testing.assert_array_equal(np.asarray(rounded)[:, 3], 3.0)
    np.testing.assert_allclose(mean, np.asarray(x), atol=0.05)


# ----------------------------------------------------- compressed psum ---

def _psum_fn(bits, with_key=False):
    mesh = make_mesh({"data": 1})
    if with_key:
        return jax.shard_map(
            lambda x, k: compressed_psum(x, "data", key=k, bits=bits),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)
    return jax.shard_map(
        lambda x: compressed_psum(x, "data", bits=bits),
        mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False)


def test_compressed_psum_error_bound_and_monotone_bits():
    """Per-element error stays within half a step of the GLOBAL scale,
    and more bits mean a finer grid (monotonically tighter error)."""
    x = _x(seed=1)
    errs = {}
    for bits in (4, 8, 16):
        out = _psum_fn(bits)(x)
        qmax = 2.0 ** (bits - 1) - 1.0
        scale = float(jnp.max(jnp.abs(x))) / qmax
        err = np.abs(np.asarray(out) - np.asarray(x))
        assert err.max() <= 0.5 * scale * (1 + 1e-5), bits
        errs[bits] = err.max()
    assert errs[16] < errs[8] < errs[4]


def test_compressed_psum_parity_with_exact_collective():
    """At high bit width the quantized collective matches the exact
    psum to float32 round-off — compression is lossless in the limit."""
    x = _x(seed=2)
    mesh = make_mesh({"data": 1})
    exact = jax.shard_map(lambda v: jax.lax.psum(v, "data"),
                             mesh=mesh, in_specs=(P(),), out_specs=P(),
                             check_vma=False)(x)
    compressed = _psum_fn(24)(x)
    np.testing.assert_allclose(np.asarray(compressed), np.asarray(exact),
                               rtol=1e-5, atol=1e-5)


def test_compressed_psum_stochastic_mode_unbiased():
    x = _x(n=64, seed=3)
    f = _psum_fn(6, with_key=True)
    keys = jax.random.split(jax.random.PRNGKey(1), 512)
    outs = np.stack([np.asarray(f(x, k)) for k in keys[:128]])
    qmax = 2.0 ** 5 - 1.0
    scale = float(jnp.max(jnp.abs(x))) / qmax
    # mean error well under the worst-case half-step of a single draw
    np.testing.assert_allclose(outs.mean(axis=0), np.asarray(x),
                               atol=0.25 * scale)


def test_compressed_psum_int32_accumulation_is_exact():
    """The transport sum runs in int32 (bit-exact associativity): on a
    1-device axis the output is exactly dequantize(quantize(x))."""
    x = _x(seed=4)
    out = _psum_fn(8)(x)
    q, scale = quantize_int8(x)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(q.astype(jnp.float32) * scale))


# ------------------------------------------------------- batch_solve ---

BATCH_OPTS = PDHGOptions(max_iters=20000, tol=1e-6, check_every=64)


def test_solve_batch_parity_with_single_instance(x64):
    """The stacked same-shape path agrees with per-instance solve_jit
    on every component of the result dict."""
    lps = [random_standard_lp(8, 14, seed=s) for s in (0, 1, 2)]
    mesh = make_mesh({"data": 1})
    Ks, bs, cs, lbs, ubs = stack_problems(lps)
    out = solve_batch(Ks, bs, cs, lbs, ubs, mesh, BATCH_OPTS)
    assert out["x"].shape == (3, 14) and out["y"].shape == (3, 8)
    assert out["converged"].all()
    for k, lp in enumerate(lps):
        single = solve_jit(lp, BATCH_OPTS)
        obj = float(lp.c @ out["x"][k])
        assert abs(obj - single.obj) / max(abs(single.obj), 1e-12) < 1e-4
        assert abs(obj - lp.obj_opt) / abs(lp.obj_opt) < 1e-4
        assert out["merit"][k] <= BATCH_OPTS.tol


def test_solve_batch_deterministic_and_seeded(x64):
    """Same call -> identical arrays; different seed -> different
    trajectories (per-instance keys split from opts.seed)."""
    lps = [random_standard_lp(8, 14, seed=4)] * 2
    mesh = make_mesh({"data": 1})
    stacked = stack_problems(lps)
    short = PDHGOptions(max_iters=128, tol=1e-30, check_every=64)
    a = solve_batch(*stacked, mesh, short)
    b = solve_batch(*stacked, mesh, short)
    np.testing.assert_array_equal(a["x"], b["x"])
    c = solve_batch(*stacked, mesh,
                    PDHGOptions(max_iters=128, tol=1e-30, check_every=64,
                                seed=11))
    assert not np.allclose(a["x"], c["x"])
    # instances in one stack follow distinct trajectories
    assert not np.allclose(a["x"][0], a["x"][1])


def test_solve_batch_rejects_mismatched_stack(x64):
    """Stacked arrays must agree on B (shape errors surface as the
    assertion/lowering error, not silent truncation)."""
    lps = [random_standard_lp(8, 14, seed=s) for s in (0, 1)]
    mesh = make_mesh({"data": 1})
    Ks, bs, cs, lbs, ubs = stack_problems(lps)
    with pytest.raises(Exception):
        solve_batch(Ks[:1], bs, cs, lbs, ubs, mesh, BATCH_OPTS)

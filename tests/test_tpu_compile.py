"""Compile the main-path kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles at real width against a
``v5e:2x2`` topology description, which raises whatever the chip's
compiler (Mosaic for the Pallas kernels) would refuse.  Interpret mode
hides those refusals, so these are the CPU suite's guard on the kernels
the chip runs.  The refusals the program makes itself — f64 operands
into a compiled kernel, a megakernel bucket past its VMEM budget — are
checked here too, before any compiler is reached.

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU library; the test that needs it
loads it, never an import).  The persistent compilation cache is off
around these compiles: what they write could not be read back without a
chip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import engine
from repro.kernels import crossbar_mvm as xbar
from repro.kernels import pdhg_megakernel as mega
from repro.kernels import pdhg_update as upd
from repro.kernels.sparse_mvm import ell_matvec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


# The CLI and the benchmarks run with x64 on (the ``x64`` fixture); the
# f32 kernels must compile regardless.
def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def test_crossbar_mvm_compiles_f32_with_x64(one_chip, x64):
    R, C = 4096, 8192
    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                        sharding=one_chip)
    exe = _compile(
        lambda gp, gn, v, g: xbar.crossbar_mvm_padded(gp, gn, v, g,
                                                      interpret=False),
        sd((R, C)), sd((R, C)), sd((C, 1)), sd((R, 1)))
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("which", ["primal", "dual"])
def test_update_kernels_compile_f32_with_x64(one_chip, x64, which):
    col = jax.ShapeDtypeStruct((1 << 16, 1), jnp.float32, sharding=one_chip)
    scl = jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip)
    if which == "primal":
        exe = _compile(
            lambda *a: upd.primal_update_padded(*a, interpret=False),
            *([col] * 6), scl, scl)
    else:
        exe = _compile(
            lambda *a: upd.dual_update_padded(*a, interpret=False),
            *([col] * 4), scl)
    assert "tpu_custom_call" in exe.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_ell_matvec_compiles_as_xla_gather(one_chip, x64, dtype):
    """The one ELL path is an XLA gather, in either solve dtype."""
    rows, width, n = 1 << 16, 16, 1 << 17
    exe = _compile(
        ell_matvec,
        jax.ShapeDtypeStruct((rows, width), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip))
    text = exe.as_text()
    assert "gather" in text and "tpu_custom_call" not in text


def test_compiled_kernels_refuse_f64(x64):
    """f64 into a compiled Pallas kernel is refused at mount time, by
    name of the option that sets the solve dtype."""
    with pytest.raises(ValueError, match=r"PDHGOptions\.dtype"):
        engine.make_updates("pallas", jnp.float64, interpret=False)
    g = jnp.zeros((8, 8), jnp.float64)
    with pytest.raises(ValueError, match="float64"):
        engine.crossbar_operator(g, g, 1.0, 4, 4, interpret=False)
    # f32 mounts; interpreted kernels take f64
    engine.make_updates("pallas", jnp.float32, interpret=False)
    engine.make_updates("pallas", jnp.float64, interpret=True)


def test_megakernel_refuses_bucket_past_vmem(one_chip):
    """A bucket over the budget is refused with a message before Mosaic
    sees it; the largest accepted shape compiles for the chip."""
    def fused(m, n):
        sd = lambda s: jax.ShapeDtypeStruct(  # noqa: E731
            s, jnp.float32, sharding=one_chip)
        vm, vn, sc = sd((m,)), sd((n,)), sd(())

        def f(K, Ka, b, c, lb, ub, T, S, x, xp, xb, y, tau, sig):
            return mega.fused_dense_steps(
                K, Ka, b, c, lb, ub, T, S, x, xp, xb, y, tau, sig,
                n_steps=8, gamma=0.0, interpret=False)
        return _compile(f, sd((m, n)), sd((n, m)), vm, vn, vn, vn, vn, vm,
                        vn, vn, vn, vm, sc, sc)

    assert mega.vmem_estimate(512, 1024, np.float32) <= mega.VMEM_BUDGET
    assert "tpu_custom_call" in fused(512, 1024).as_text()
    with pytest.raises(ValueError, match="VMEM"):
        fused(1024, 1024)

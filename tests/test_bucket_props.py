"""Property tests for the ``runtime.batch`` bucketing invariants.

Runs under real ``hypothesis`` when installed (the CI distributed job)
and under the deterministic stand-in of ``tests/_hypothesis_compat``
otherwise, so the sweeps always execute.

Invariants:
  * ``bucket_dims(tile=...)`` snaps UP to the smallest whole-tile
    multiple — never below the logical dims, never skipping a tile.
  * default ``bucket_dims`` is the enclosing power of two (floored at
    ``min_size``), idempotent on its own outputs.
  * ``ell_width_bucket`` is a monotone power-of-two step function.
  * sparse and dense buckets can never share an executable-cache key,
    whatever their dims.
"""
import numpy as np

from _hypothesis_compat import given, settings, st

from repro.core import PDHGOptions
from repro.kernels.sparse_mvm import MIN_ELL_WIDTH, ell_width_bucket
from repro.runtime.batch import (
    MIN_BUCKET,
    BatchSolver,
    bucket_dims,
)

DIMS = st.integers(min_value=1, max_value=4096)
TILES = st.integers(min_value=1, max_value=512)
WIDTHS = st.integers(min_value=0, max_value=1 << 16)


def _is_pow2(v: int) -> bool:
    return v >= 1 and (v & (v - 1)) == 0


@settings(max_examples=200)
@given(m=DIMS, n=DIMS, tr=TILES, tc=TILES)
def test_tile_mode_snaps_up_to_tile_multiples(m, n, tr, tc):
    mb, nb = bucket_dims(m, n, tile=(tr, tc))
    # whole tiles only
    assert mb % tr == 0 and nb % tc == 0
    # never below the logical dims
    assert mb >= m and nb >= n
    # minimal: one tile less would not fit
    assert mb - tr < m and nb - tc < n
    # idempotent: a bucket is its own bucket
    assert bucket_dims(mb, nb, tile=(tr, tc)) == (mb, nb)


@settings(max_examples=200)
@given(m=DIMS, n=DIMS)
def test_default_mode_is_minimal_enclosing_power_of_two(m, n):
    mb, nb = bucket_dims(m, n)
    assert _is_pow2(mb) and _is_pow2(nb)
    assert mb >= max(m, MIN_BUCKET) and nb >= max(n, MIN_BUCKET)
    # minimal: halving drops below the dim (or the floor)
    assert mb // 2 < m or mb == MIN_BUCKET
    assert nb // 2 < n or nb == MIN_BUCKET
    assert bucket_dims(mb, nb) == (mb, nb)


@settings(max_examples=200)
@given(a=WIDTHS, b=WIDTHS)
def test_ell_width_bucket_monotone_power_of_two(a, b):
    ba, bb = ell_width_bucket(a), ell_width_bucket(b)
    assert _is_pow2(ba) and _is_pow2(bb)
    assert ba >= max(a, MIN_ELL_WIDTH) and ba // 2 < max(a, MIN_ELL_WIDTH)
    if a <= b:                       # monotone step function
        assert ba <= bb
    assert ell_width_bucket(ba) == ba    # idempotent on bucket values


@settings(max_examples=100)
@given(m=DIMS, n=DIMS, wf=WIDTHS, wa=WIDTHS,
       B=st.integers(min_value=1, max_value=64))
def test_sparse_and_dense_buckets_never_share_cache_keys(m, n, wf, wa, B):
    """Whatever the dims, an ELL signature can never collide with a
    dense one (the executables take different argument layouts)."""
    solver = BatchSolver(PDHGOptions())
    mb, nb = bucket_dims(m, n)
    kd = solver._cache_key(("dense", mb, nb), B, np.float64, False)
    ks = solver._cache_key(("ell", mb, nb, ell_width_bucket(wf),
                            ell_width_bucket(wa)), B, np.float64, False)
    assert kd != ks
    # and the tags stay distinct even if the widths equal the dims
    ks2 = solver._cache_key(("ell", mb, nb, mb, nb), B, np.float64, False)
    assert kd != ks2


@settings(max_examples=50)
@given(m=DIMS, n=DIMS, tr=TILES, tc=TILES)
def test_tile_and_pow2_buckets_agree_when_tile_is_pow2_multiple(m, n, tr,
                                                                tc):
    """Sanity cross-check: tile mode with a (1, 1) tile is the identity
    ceiling (no padding at all)."""
    assert bucket_dims(m, n, tile=(1, 1)) == (max(m, 1), max(n, 1))
    mb, nb = bucket_dims(m, n, tile=(tr, tc))
    assert (mb // tr) == -(-m // tr) and (nb // tc) == -(-n // tc)

"""Pallas kernel validation: shape/dtype sweeps against the ref.py oracles,
executed in interpret mode on CPU (TPU is the compile target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:
    from _propcheck import given, settings, st

from repro.kernels import ref
from repro.kernels.fl_gains import fl_gains_pallas
from repro.kernels.similarity_kernel import similarity_pallas

SHAPES = [
    (8, 8, 8),  # far below one tile
    (50, 70, 33),  # ragged, sub-tile
    (128, 128, 512),  # exactly one tile
    (130, 257, 600),  # ragged, multi-tile
    (256, 384, 1024),  # multiple tiles each dim
]
METRICS = ["dot", "cosine", "euclidean", "rbf"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("metric", METRICS)
def test_similarity_matches_ref_fp32(shape, metric, rng):
    n, m, d = shape
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.normal(size=(m, d)).astype(np.float32)
    got = np.asarray(similarity_pallas(x, y, metric=metric, interpret=True))
    want = np.asarray(ref.similarity_ref(jnp.asarray(x), jnp.asarray(y), metric))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("metric", ["dot", "rbf"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_similarity_dtypes(metric, dtype, rng):
    x = jnp.asarray(rng.normal(size=(96, 200)).astype(np.float32), dtype)
    y = jnp.asarray(rng.normal(size=(64, 200)).astype(np.float32), dtype)
    got = np.asarray(similarity_pallas(x, y, metric=metric, interpret=True))
    want = np.asarray(ref.similarity_ref(x, y, metric))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("block", [(64, 128), (256, 512), (128, 256)])
def test_similarity_block_shapes(block, rng):
    bn, bk = block
    x = rng.normal(size=(100, 300)).astype(np.float32)
    y = rng.normal(size=(90, 300)).astype(np.float32)
    got = np.asarray(
        similarity_pallas(x, y, metric="dot", interpret=True, bn=bn, bm=bn, bk=bk)
    )
    want = np.asarray(ref.similarity_ref(jnp.asarray(x), jnp.asarray(y), "dot"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


FL_SHAPES = [(8, 8), (40, 60), (256, 512), (300, 700), (513, 1025)]


@pytest.mark.parametrize("shape", FL_SHAPES)
def test_fl_gains_matches_ref(shape, rng):
    u, n = shape
    s = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
    cm = rng.uniform(0, 1, size=(u,)).astype(np.float32)
    got = np.asarray(fl_gains_pallas(s, cm, interpret=True))
    want = np.asarray(ref.fl_gains_ref(jnp.asarray(s), jnp.asarray(cm)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fl_gains_dtypes(dtype, rng):
    s = jnp.asarray(rng.uniform(0, 1, size=(300, 400)).astype(np.float32), dtype)
    cm = jnp.asarray(rng.uniform(0, 1, size=(300,)).astype(np.float32), jnp.float32)
    got = np.asarray(fl_gains_pallas(s, cm, interpret=True))
    want = np.asarray(ref.fl_gains_ref(s, cm))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)


@settings(max_examples=10, deadline=None)
@given(
    u=st.integers(3, 200),
    n=st.integers(3, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_fl_gains_property(u, n, seed):
    rng = np.random.default_rng(seed)
    s = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
    cm = rng.uniform(0, 1, size=(u,)).astype(np.float32)
    got = np.asarray(fl_gains_pallas(s, cm, interpret=True, bu=64, bn=128))
    want = np.asarray(ref.fl_gains_ref(jnp.asarray(s), jnp.asarray(cm)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert (got >= -1e-6).all()  # gains of a monotone function


@pytest.mark.parametrize("u", [1, 1003, 5000])
def test_fl_gains_ragged_rows_sum_in_the_xla_order(u, rng):
    """A row count that is no multiple of the row tile or of BLOCK is read
    without padding: the kernel masks the last tile's rows past u (which
    interpret mode fills with NaN), and the gains keep the XLA sweep's bits,
    in the full sweep and the gathered subset."""
    from repro.common import fl_gain_sums
    from repro.kernels.fl_gains import fl_gains_at_pallas

    n = 40
    s = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
    cm = rng.uniform(0, 0.8, size=(u,)).astype(np.float32)
    got = np.asarray(fl_gains_pallas(s, cm, interpret=True))
    want = np.asarray(fl_gain_sums(jnp.asarray(s), jnp.asarray(cm)))
    assert got.tobytes() == want.tobytes()
    np.testing.assert_allclose(
        got, np.asarray(ref.fl_gains_ref(jnp.asarray(s), jnp.asarray(cm))),
        rtol=1e-5, atol=1e-4,
    )
    idx = np.array([n - 1, -1, 0, 7], np.int32)
    at = np.asarray(fl_gains_at_pallas(s, cm, idx, interpret=True))
    assert at[idx >= 0].tobytes() == got[idx[idx >= 0]].tobytes()


def test_fl_function_kernel_path_matches_plain(rng):
    """FacilityLocation(use_kernel=True) routes gains through the Pallas op
    and must select the identical greedy set."""
    from repro.core import FacilityLocation, create_kernel, naive_greedy

    x = rng.normal(size=(80, 16)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    plain = FacilityLocation.from_kernel(S, use_kernel=False)
    fused = FacilityLocation.from_kernel(S, use_kernel=True)
    r1 = naive_greedy(plain, 10)
    r2 = naive_greedy(fused, 10)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-5, atol=1e-5
    )


FUSED_SHAPES = [(40, 60, 16), (300, 700, 128), (256, 512, 300), (513, 1025, 80)]


@pytest.mark.parametrize("shape", FUSED_SHAPES)
def test_fused_fl_sweep_matches_ref(shape, rng):
    """Beyond-paper fused similarity+gain kernel (EXPERIMENTS §Perf-3/C3):
    the O(n^2) kernel matrix never exists; gains come straight from the
    embeddings through a VMEM tile accumulator."""
    from repro.kernels.fused_fl_sweep import (
        fused_fl_sweep_pallas,
        fused_fl_sweep_ref,
    )

    u, n, d = shape
    x = rng.normal(size=(u, d)).astype(np.float32)
    y = rng.normal(size=(n, d)).astype(np.float32)
    cm = rng.uniform(0, 3, size=(u,)).astype(np.float32)
    got = np.asarray(
        fused_fl_sweep_pallas(x, y, cm, interpret=True, bu=128, bn=128, bk=64)
    )
    want = np.asarray(
        fused_fl_sweep_ref(jnp.asarray(x), jnp.asarray(y), jnp.asarray(cm))
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# -- graph-cut gain sweep (backend-layer kernel) -----------------------------

GC_SHAPES = [
    (8,),  # far below one tile
    (100,),  # ragged, sub-tile
    (128,),  # exactly one tile (bj=bk=64 -> multi-tile, aligned)
    (257,),  # ragged, multi-tile
]


@pytest.mark.parametrize("shape", GC_SHAPES)
def test_gc_gains_matches_ref(shape, rng):
    from repro.kernels.gc_gains import gc_gains_pallas

    (n,) = shape
    s = rng.uniform(0, 1, size=(n, n)).astype(np.float32)
    s = (s + s.T) / 2
    m = (rng.uniform(size=n) < 0.3).astype(np.float32)
    tot = s.sum(axis=0).astype(np.float32)
    got = np.asarray(gc_gains_pallas(s, m, tot, 0.4, interpret=True, bj=64, bk=64))
    want = np.asarray(
        ref.gc_gains_ref(jnp.asarray(s), jnp.asarray(m), jnp.asarray(tot), 0.4)
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gc_gains_dtypes(dtype, rng):
    from repro.kernels.gc_gains import gc_gains_pallas

    n = 150
    s = jnp.asarray(rng.uniform(0, 1, size=(n, n)).astype(np.float32), dtype)
    m = jnp.asarray((rng.uniform(size=n) < 0.5).astype(np.float32))
    tot = jnp.asarray(rng.uniform(0, n, size=n).astype(np.float32))
    got = np.asarray(gc_gains_pallas(s, m, tot, 0.25, interpret=True, bj=64, bk=64))
    want = np.asarray(ref.gc_gains_ref(s, m, tot, 0.25))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=5e-2)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(3, 200), seed=st.integers(0, 2**31 - 1))
def test_gc_gains_property(n, seed):
    from repro.kernels.gc_gains import gc_gains_pallas

    rng = np.random.default_rng(seed)
    s = rng.uniform(0, 1, size=(n, n)).astype(np.float32)
    m = (rng.uniform(size=n) < 0.4).astype(np.float32)
    tot = s.sum(axis=0).astype(np.float32)
    lam = float(rng.uniform(0.0, 1.0))
    got = np.asarray(gc_gains_pallas(s, m, tot, lam, interpret=True, bj=64, bk=64))
    want = np.asarray(
        ref.gc_gains_ref(jnp.asarray(s), jnp.asarray(m), jnp.asarray(tot), lam)
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gc_function_kernel_path_matches_plain(rng):
    """GraphCut(use_kernel=True) routes full sweeps through the Pallas gain
    backend and must select the identical greedy set."""
    from repro.core import GraphCut, create_kernel, naive_greedy

    x = rng.normal(size=(70, 12)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="cosine"))
    plain = GraphCut.from_kernel(S, lam=0.3)
    fused = GraphCut.from_kernel(S, lam=0.3, use_kernel=True)
    r1 = naive_greedy(plain, 10, False, False)
    r2 = naive_greedy(fused, 10, False, False)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-4, atol=1e-4
    )


# -- feature-based concave-over-modular sweep ---------------------------------

FB_SHAPES = [(8, 5), (128, 128), (130, 70), (300, 33)]


@pytest.mark.parametrize("shape", FB_SHAPES)
@pytest.mark.parametrize("concave", ["sqrt", "log", "inverse"])
def test_fb_gains_matches_ref(shape, concave, rng):
    from repro.kernels.fb_gains import fb_gains_pallas

    n, F = shape
    feats = rng.uniform(0, 1, size=(n, F)).astype(np.float32)
    acc = rng.uniform(0, 2, size=(F,)).astype(np.float32)
    w = rng.uniform(0.5, 1.5, size=(F,)).astype(np.float32)
    got = np.asarray(
        fb_gains_pallas(feats, acc, w, concave=concave, interpret=True, bn=64, bf=64)
    )
    want = np.asarray(
        ref.fb_gains_ref(jnp.asarray(feats), jnp.asarray(acc), jnp.asarray(w), concave)
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fb_gains_dtypes(dtype, rng):
    from repro.kernels.fb_gains import fb_gains_pallas

    feats = jnp.asarray(rng.uniform(0, 1, size=(90, 40)).astype(np.float32), dtype)
    acc = jnp.asarray(rng.uniform(0, 2, size=(40,)).astype(np.float32))
    w = jnp.asarray(rng.uniform(0.5, 1.5, size=(40,)).astype(np.float32))
    got = np.asarray(fb_gains_pallas(feats, acc, w, interpret=True))
    want = np.asarray(ref.fb_gains_ref(feats, acc, w))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=5e-2)


def test_fb_function_kernel_path_matches_plain(rng):
    from repro.core import FeatureBased, naive_greedy

    feats = rng.uniform(0, 1, size=(60, 20)).astype(np.float32)
    plain = FeatureBased.from_features(feats, concave="log")
    fused = FeatureBased.from_features(feats, concave="log", use_kernel=True)
    r1 = naive_greedy(plain, 10, False, False)
    r2 = naive_greedy(fused, 10, False, False)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-4, atol=1e-4
    )


# -- set-cover family sweeps (backend-layer kernels) --------------------------

SC_SHAPES = [(8, 5), (100, 33), (128, 128), (257, 70), (300, 130)]


@pytest.mark.parametrize("shape", SC_SHAPES)
def test_sc_gains_matches_ref(shape, rng):
    from repro.kernels.sc_gains import sc_gains_pallas

    n, m = shape
    cover = rng.integers(0, 2, size=(n, m)).astype(np.float32)
    covered = (rng.uniform(size=m) < 0.4).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=m).astype(np.float32)
    got = np.asarray(
        sc_gains_pallas(cover, covered, w, interpret=True, bn=64, bm=64)
    )
    want = np.asarray(
        ref.sc_gains_ref(jnp.asarray(cover), jnp.asarray(covered), jnp.asarray(w))
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (got >= -1e-6).all()  # gains of a monotone function


@pytest.mark.parametrize("shape", SC_SHAPES)
def test_psc_gains_matches_ref(shape, rng):
    from repro.kernels.sc_gains import psc_gains_pallas

    n, m = shape
    probs = rng.uniform(0, 0.9, size=(n, m)).astype(np.float32)
    miss = rng.uniform(0, 1, size=m).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=m).astype(np.float32)
    got = np.asarray(
        psc_gains_pallas(probs, miss, w, interpret=True, bn=64, bm=64)
    )
    want = np.asarray(
        ref.psc_gains_ref(jnp.asarray(probs), jnp.asarray(miss), jnp.asarray(w))
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(3, 200), m=st.integers(1, 80), seed=st.integers(0, 2**31 - 1))
def test_sc_gains_property(n, m, seed):
    from repro.kernels.sc_gains import sc_gains_pallas

    rng = np.random.default_rng(seed)
    cover = rng.integers(0, 2, size=(n, m)).astype(np.float32)
    covered = (rng.uniform(size=m) < 0.5).astype(np.float32)
    w = rng.uniform(0.0, 2.0, size=m).astype(np.float32)
    got = np.asarray(sc_gains_pallas(cover, covered, w, interpret=True, bn=64, bm=64))
    want = np.asarray(
        ref.sc_gains_ref(jnp.asarray(cover), jnp.asarray(covered), jnp.asarray(w))
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_sc_function_kernel_path_matches_plain(rng):
    """SetCover(use_kernel=True) routes full sweeps through the Pallas gain
    backend and must select the identical greedy set."""
    from repro.core import SetCover, naive_greedy

    cover = rng.integers(0, 2, size=(70, 25)).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=25).astype(np.float32)
    plain = SetCover.from_cover(cover, w)
    fused = SetCover.from_cover(cover, w, use_kernel=True)
    r1 = naive_greedy(plain, 10)
    r2 = naive_greedy(fused, 10)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-5, atol=1e-5
    )


def test_psc_function_kernel_path_matches_plain(rng):
    from repro.core import ProbabilisticSetCover, naive_greedy

    probs = rng.uniform(0, 0.9, size=(60, 20)).astype(np.float32)
    plain = ProbabilisticSetCover.from_probs(probs)
    fused = ProbabilisticSetCover.from_probs(probs, use_kernel=True)
    r1 = naive_greedy(plain, 10)
    r2 = naive_greedy(fused, 10)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-5, atol=1e-5
    )


# -- disparity sweeps (stateless, from the selection mask) --------------------

DISP_SHAPES = [(8,), (100,), (128,), (257,)]


@pytest.mark.parametrize("shape", DISP_SHAPES)
def test_dsum_gains_matches_ref(shape, rng):
    from repro.kernels.disp_gains import dsum_gains_pallas

    (n,) = shape
    d = rng.uniform(0, 2, size=(n, n)).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    m = (rng.uniform(size=n) < 0.3).astype(np.float32)
    got = np.asarray(dsum_gains_pallas(d, m, interpret=True, bj=64, bk=64))
    want = np.asarray(ref.dsum_gains_ref(jnp.asarray(d), jnp.asarray(m)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", DISP_SHAPES)
def test_dmin_gains_matches_ref(shape, rng):
    from repro.kernels.disp_gains import dmin_gains_pallas

    (n,) = shape
    d = rng.uniform(0.1, 2, size=(n, n)).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    m = (rng.uniform(size=n) < 0.3).astype(np.float32)
    count = int(m.sum())
    curmin = float(rng.uniform(0, 1)) if count else 0.0
    got = np.asarray(
        dmin_gains_pallas(d, m, count, curmin, interpret=True, bj=64, bk=64)
    )
    want = np.asarray(
        ref.dmin_gains_ref(jnp.asarray(d), jnp.asarray(m), count, curmin)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dmin_gains_empty_selection_is_zero(rng):
    """|A| = 0: the surrogate collapses to 0 - f({}) = 0 for every candidate
    (the kernel's SMEM count conditional, not the masked min, must win)."""
    from repro.kernels.disp_gains import dmin_gains_pallas

    d = rng.uniform(0, 2, size=(40, 40)).astype(np.float32)
    got = np.asarray(
        dmin_gains_pallas(
            d, np.zeros(40, np.float32), 0, 0.0, interpret=True, bj=64, bk=64
        )
    )
    np.testing.assert_array_equal(got, np.zeros(40, np.float32))


def test_dsum_function_kernel_path_matches_plain(rng):
    from repro.core import DisparitySum, naive_greedy

    d = rng.uniform(0, 2, size=(60, 60)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    plain = DisparitySum.from_distance(d)
    fused = DisparitySum.from_distance(d, use_kernel=True)
    r1 = naive_greedy(plain, 8, False, False)
    r2 = naive_greedy(fused, 8, False, False)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_allclose(
        np.asarray(r1.gains), np.asarray(r2.gains), rtol=1e-5, atol=1e-5
    )


def test_dmin_function_kernel_path_matches_plain(rng):
    """DisparityMin's masked min is order-independent, so the stateless
    Pallas sweep reproduces the memoized path bit-for-bit."""
    from repro.core import DisparityMin, naive_greedy

    d = rng.uniform(0.1, 2, size=(60, 60)).astype(np.float32)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    plain = DisparityMin.from_distance(d)
    fused = DisparityMin.from_distance(d, use_kernel=True)
    r1 = naive_greedy(plain, 8, False, False)
    r2 = naive_greedy(fused, 8, False, False)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))
    np.testing.assert_array_equal(np.asarray(r1.gains), np.asarray(r2.gains))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_fl_sweep_dtypes(dtype, rng):
    from repro.kernels.fused_fl_sweep import (
        fused_fl_sweep_pallas,
        fused_fl_sweep_ref,
    )

    x = jnp.asarray(rng.normal(size=(100, 96)).astype(np.float32), dtype)
    y = jnp.asarray(rng.normal(size=(90, 96)).astype(np.float32), dtype)
    cm = jnp.asarray(rng.uniform(0, 2, size=(100,)).astype(np.float32))
    got = np.asarray(fused_fl_sweep_pallas(x, y, cm, interpret=True))
    want = np.asarray(fused_fl_sweep_ref(x, y, cm))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=5e-2)


# -- masked-subset (gather-sweep) entry points --------------------------------
#
# The partial_sweep contract behind the bucketed lazy engines: gains for a
# gathered candidate subset only, idx < 0 slots padded to NEG_INF.  Validated
# two ways per family: allclose against the jnp subset oracle, and EXACT
# equality against the full-sweep kernel gathered at the same indices (the
# per-candidate accumulation order is identical by construction, which is
# what lets lazy screens mix stale full-sweep bounds with subset refreshes).

from repro.common import NEG_INF
from repro.kernels.fb_gains import fb_gains_at_pallas, fb_gains_pallas
from repro.kernels.fl_gains import fl_gains_at_pallas
from repro.kernels.gc_gains import gc_gains_at_pallas, gc_gains_pallas

SUBSET_IDX = [
    np.array([0], np.int32),
    np.array([5, 3, 3, 17], np.int32),  # duplicates allowed
    np.array([2, -1, 40, -1, 7, 0], np.int32),  # padded slots
    np.arange(48, dtype=np.int32)[::-1].copy(),  # everything, reversed
]


@pytest.mark.parametrize("idx", SUBSET_IDX)
def test_fl_gains_at_matches_ref_and_full(idx):
    rng = np.random.default_rng(11)
    u, n = 70, 48
    sim = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
    cm = rng.uniform(0, 0.8, size=(u,)).astype(np.float32)
    got = np.asarray(fl_gains_at_pallas(sim, cm, idx, interpret=True))
    want = np.asarray(ref.fl_gains_at_ref(sim, cm, jnp.asarray(idx)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    full = np.asarray(fl_gains_pallas(sim, cm, interpret=True))
    mask = idx >= 0
    np.testing.assert_array_equal(got[mask], full[idx[mask]])
    assert (got[~mask] == NEG_INF).all()


@pytest.mark.parametrize("idx", SUBSET_IDX)
def test_gc_gains_at_matches_ref_and_full(idx):
    rng = np.random.default_rng(11)
    n = 48
    sim = rng.uniform(0, 1, size=(n, n)).astype(np.float32)
    sim = (sim + sim.T) / 2
    total = sim.sum(axis=0).astype(np.float32)
    selmask = (rng.uniform(size=n) < 0.3).astype(np.float32)
    lam = jnp.float32(0.4)
    got = np.asarray(
        gc_gains_at_pallas(sim, selmask, total, lam, idx, interpret=True)
    )
    want = np.asarray(
        ref.gc_gains_at_ref(sim, selmask, total, lam, jnp.asarray(idx))
    )
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    full = np.asarray(gc_gains_pallas(sim, selmask, total, lam, interpret=True))
    mask = idx >= 0
    np.testing.assert_array_equal(got[mask], full[idx[mask]])
    assert (got[~mask] == NEG_INF).all()


@pytest.mark.parametrize("idx", SUBSET_IDX)
@pytest.mark.parametrize("concave", ["sqrt", "log"])
def test_fb_gains_at_matches_ref_and_full(idx, concave):
    rng = np.random.default_rng(11)
    n, F = 48, 33
    feats = rng.uniform(0, 1, size=(n, F)).astype(np.float32)
    acc = rng.uniform(0, 3, size=(F,)).astype(np.float32)
    w = rng.uniform(0.2, 1.5, size=(F,)).astype(np.float32)
    got = np.asarray(
        fb_gains_at_pallas(feats, acc, w, idx, concave=concave, interpret=True)
    )
    want = np.asarray(
        ref.fb_gains_at_ref(feats, acc, w, jnp.asarray(idx), concave=concave)
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    full = np.asarray(
        fb_gains_pallas(feats, acc, w, concave=concave, interpret=True)
    )
    mask = idx >= 0
    np.testing.assert_array_equal(got[mask], full[idx[mask]])
    assert (got[~mask] == NEG_INF).all()


def test_partial_sweep_routes_through_kernel_backends():
    """backends.partial_sweep uses the family's Pallas subset kernel when
    use_kernel=True and the jnp gains_at reference otherwise — and the lazy
    screens agree between the two, which the batched lazy engine relies on."""
    from repro.core import FacilityLocation, create_kernel, lazy_greedy
    from repro.core.optimizers.backends import partial_sweep

    rng = np.random.default_rng(11)
    x = rng.normal(size=(40, 6)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    plain = FacilityLocation.from_kernel(S)
    fused = FacilityLocation.from_kernel(S, use_kernel=True)
    st = plain.init_state()
    idx = jnp.asarray([3, 11, 0, 25], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(partial_sweep(plain, st, idx)),
        np.asarray(partial_sweep(fused, st, idx)),
        rtol=1e-5,
        atol=1e-5,
    )
    r1 = lazy_greedy(plain, 6)
    r2 = lazy_greedy(fused, 6)
    assert list(np.asarray(r1.order)) == list(np.asarray(r2.order))


@pytest.mark.parametrize("family", ["fl", "fb"])
@pytest.mark.parametrize("shape", [(8, 40), (33, 300), (300, 600), (513, 1025)])
def test_pallas_sweep_equals_xla_sweep_bit_for_bit(family, shape, rng):
    """The XLA gain sweep and its Pallas kernel sum in one fixed order
    (repro.common.tiled_sum), so a breaker's Pallas -> XLA reroute returns
    the Pallas route's bits: full sweep and gathered subset alike."""
    from repro.core import FacilityLocation, FeatureBased

    u, n = shape
    idx = jnp.asarray([n - 1, -1, 0, n // 2], jnp.int32)
    if family == "fl":
        S = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
        fns = [FacilityLocation.from_kernel(S, use_kernel=k) for k in (True, False)]
        state = fns[0].init_state()
        state = fns[0].update(state, jnp.int32(n // 3))
    else:  # u candidates, n features
        X = rng.uniform(0, 1, size=(u, n)).astype(np.float32)
        fns = [FeatureBased.from_features(X, use_kernel=k) for k in (True, False)]
        state = fns[0].update(fns[0].init_state(), jnp.int32(u // 3))
        idx = jnp.clip(idx, -1, u - 1)
    backend = fns[0].gain_backend()
    assert backend is not None and fns[1].gain_backend() is None
    full = (backend.full_sweep(fns[0], state), fns[1].gains(state))
    at = (backend.partial_sweep(fns[0], state, idx), fns[1].gains_at(state, idx))
    assert np.asarray(full[0]).tobytes() == np.asarray(full[1]).tobytes()
    live = np.asarray(idx) >= 0
    assert (
        np.asarray(at[0])[live].tobytes() == np.asarray(at[1])[live].tobytes()
    )

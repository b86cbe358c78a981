"""Clustered mixtures (paper §8): block-masked kernel == sum of per-cluster
objectives, dense and matrix-free.

``clustered(base_from_kernel, S, labels)`` evaluates the base function on
the block-masked kernel; the §8 claim is that this EQUALS the mixture
f(A) = sum_l f_{C_l}(A ∩ C_l) of independent per-cluster functions.  The
matrix-free form (``clustered_matrix_free``) must agree without ever
materializing the kernel or the mask.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _points import make_points
from repro.core import (
    FacilityLocation,
    FacilityLocationMF,
    GraphCut,
    GraphCutMF,
    SelectionSpec,
    cluster_mask,
    clustered,
    clustered_matrix_free,
    create_kernel,
    solve,
)
from repro.core.optimizers.backends import full_sweep


def _setup(rng, n=30, n_clusters=3):
    x = make_points(rng, n)
    labels = rng.integers(0, n_clusters, size=n).astype(np.int32)
    S = np.asarray(create_kernel(x, metric="rbf"))
    mask = np.zeros(n, bool)
    mask[rng.choice(n, size=9, replace=False)] = True
    return x, labels, S, mask


def _per_cluster_sum(base_from_kernel, S, labels, mask, **kw):
    """sum_l f_{C_l}(A ∩ C_l), each cluster's function built independently."""
    total = 0.0
    for c in np.unique(labels):
        sel = labels == c
        fn_c = base_from_kernel(jnp.asarray(S[np.ix_(sel, sel)]), **kw)
        total += float(fn_c.evaluate(jnp.asarray(mask[sel])))
    return total


def test_clustered_fl_equals_per_cluster_sum(rng):
    _, labels, S, mask = _setup(rng)
    fn = clustered(FacilityLocation.from_kernel, S, labels)
    want = _per_cluster_sum(FacilityLocation.from_kernel, S, labels, mask)
    np.testing.assert_allclose(float(fn.evaluate(jnp.asarray(mask))), want,
                               rtol=1e-5, atol=1e-5)


def test_clustered_gc_equals_per_cluster_sum(rng):
    _, labels, S, mask = _setup(rng)
    fn = clustered(GraphCut.from_kernel, S, labels, lam=0.4)
    want = _per_cluster_sum(GraphCut.from_kernel, S, labels, mask, lam=0.4)
    np.testing.assert_allclose(float(fn.evaluate(jnp.asarray(mask))), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("metric", ("dot", "cosine", "rbf"))
def test_clustered_matrix_free_fl_matches_dense(rng, metric):
    x, labels, _, mask = _setup(rng)
    S = np.asarray(create_kernel(x, metric=metric))
    dense = clustered(FacilityLocation.from_kernel, S, labels)
    mf = clustered_matrix_free(
        FacilityLocationMF.from_features, x, labels, metric=metric
    )
    st_d, st_m = dense.init_state(), mf.init_state()
    np.testing.assert_allclose(
        np.asarray(full_sweep(mf, st_m)), np.asarray(full_sweep(dense, st_d)),
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_allclose(
        float(mf.evaluate(jnp.asarray(mask))),
        float(dense.evaluate(jnp.asarray(mask))),
        rtol=2e-5, atol=2e-5,
    )
    r_d, r_m = solve(SelectionSpec(dense, 5)), solve(SelectionSpec(mf, 5))
    assert list(np.asarray(r_d.order)) == list(np.asarray(r_m.order))
    np.testing.assert_allclose(np.asarray(r_d.gains), np.asarray(r_m.gains),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("metric", ("dot", "rbf"))
def test_clustered_matrix_free_gc_matches_dense(rng, metric):
    x, labels, _, mask = _setup(rng)
    S = np.asarray(create_kernel(x, metric=metric))
    dense = clustered(GraphCut.from_kernel, S, labels, lam=0.4)
    mf = clustered_matrix_free(
        GraphCutMF.from_features, x, labels, metric=metric, lam=0.4
    )
    np.testing.assert_allclose(
        np.asarray(full_sweep(mf, mf.init_state())),
        np.asarray(full_sweep(dense, dense.init_state())),
        rtol=2e-5, atol=2e-5,
    )
    np.testing.assert_allclose(
        float(mf.evaluate(jnp.asarray(mask))),
        float(dense.evaluate(jnp.asarray(mask))),
        rtol=2e-5, atol=2e-5,
    )
    r_d, r_m = solve(SelectionSpec(dense, 4)), solve(SelectionSpec(mf, 4))
    assert list(np.asarray(r_d.order)) == list(np.asarray(r_m.order))


def test_clustered_matrix_free_solve_modes_bit_identical(rng):
    """Labeled sources ride the same serving contract as unlabeled ones."""
    x, labels, _, _ = _setup(rng, n=37)
    mf = clustered_matrix_free(
        FacilityLocationMF.from_features, x, labels, metric="rbf"
    )
    spec = SelectionSpec(mf, 5)
    seq = solve(spec)
    for got in (
        solve([spec, spec], mode="batched")[0],
        solve([spec], mode="served")[0],
    ):
        assert list(np.asarray(seq.order)) == list(np.asarray(got.order))
        np.testing.assert_array_equal(np.asarray(seq.gains), np.asarray(got.gains))
        assert int(seq.n_evals) == int(got.n_evals)


def test_cluster_mask_is_block_indicator(rng):
    labels = np.asarray([0, 1, 0, 2, 1])
    m = np.asarray(cluster_mask(labels))
    want = (labels[:, None] == labels[None, :]).astype(np.float32)
    np.testing.assert_array_equal(m, want)

"""Matrix-free similarity sources: dense parity, solve-mode exactness, and
the memory ceiling.

The tentpole contract: a :class:`FeatureSource` / :class:`KnnSource` backed
function must (a) agree with the dense-kernel path within float tolerance
on every sweep the backends issue, (b) return bit-identical ids / gains /
``n_evals`` through ``solve()`` sequential vs batched vs served — the same
serving contract the dense families carry — and (c) never materialize an
(n, n) intermediate, which is what lets selection reach n >= 10^6 on one
host (the ``@slow`` smoke below runs it).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _points import make_points
from repro.core import (
    FacilityLocation,
    FacilityLocationMF,
    GraphCut,
    GraphCutMF,
    SelectionSpec,
    create_kernel,
    knn_from_features,
    solve,
    sparsify_topk,
)
from repro.core.optimizers.backends import full_sweep, partial_sweep
from repro.core.sources import TILE, feature_source
from repro.kernels import ops

METRICS = ("dot", "cosine", "rbf")


def _tricky_points(rng, n=37, d=8):
    """Non-multiple-of-TILE n, a duplicate row, and a zero-norm row."""
    assert n % TILE != 0
    x = make_points(rng, n, d)
    x[5] = x[3]
    x[7] = 0.0
    return x


def _pairs(rng, metric, lam=0.4):
    x = _tricky_points(rng)
    S = create_kernel(x, metric=metric)
    return (
        (FacilityLocationMF.from_features(x, metric=metric),
         FacilityLocation.from_kernel(S)),
        (GraphCutMF.from_features(x, metric=metric, lam=lam),
         GraphCut.from_kernel(S, lam=lam)),
    )


def _close(a, b, tol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol, atol=tol)


def _same(a, b, n_evals=True):
    assert list(np.asarray(a.order)) == list(np.asarray(b.order))
    np.testing.assert_array_equal(np.asarray(a.gains), np.asarray(b.gains))
    if n_evals:
        assert int(a.n_evals) == int(b.n_evals)


# -- dense-kernel parity (full_sweep / partial_sweep / evaluate) --------------


@pytest.mark.parametrize("metric", METRICS + ("euclidean",))
def test_sweeps_match_dense_path(rng, metric):
    # the duplicate row puts d2 ~ 0 under catastrophic cancellation, and
    # euclidean's 1/(1 + sqrt(d2)) amplifies it — same formula both paths,
    # different (valid) accumulation orders, so euclidean gets a looser bar
    tol = 2e-3 if metric == "euclidean" else 2e-5
    for mf, dense in _pairs(rng, metric):
        st_mf, st_d = mf.init_state(), dense.init_state()
        _close(full_sweep(mf, st_mf), full_sweep(dense, st_d), tol)
        # advance both one greedy step and compare the updated sweep
        j = int(jnp.argmax(full_sweep(dense, st_d)))
        st_mf, st_d = mf.update(st_mf, j), dense.update(st_d, j)
        _close(full_sweep(mf, st_mf), full_sweep(dense, st_d), tol)
        idx = jnp.asarray([0, 3, 5, 7, 36, 12], jnp.int32)
        _close(partial_sweep(mf, st_mf, idx), partial_sweep(dense, st_d, idx), tol)
        mask = jnp.zeros((mf.n,), bool).at[jnp.asarray([j, 2, 7])].set(True)
        _close(mf.evaluate(mask), dense.evaluate(mask), tol)


@pytest.mark.parametrize("metric", METRICS)
def test_fl_gains_at_padding_is_neg_inf(rng, metric):
    """Source-level subset sweeps mask idx < 0 pad slots to NEG_INF (the
    engines' partial-sweep padding contract); live slots are bit-identical
    to the full sweep at the same indices."""
    x = _tricky_points(rng)
    for mf in (
        FacilityLocationMF.from_features(x, metric=metric),
        FacilityLocationMF.from_knn(
            *(lambda s: (s.indices, s.weights))(
                knn_from_features(x, 6, metric=metric)
            )
        ),
    ):
        st = mf.init_state()
        idx = jnp.asarray([4, -1, 9, -1], jnp.int32)
        g = np.asarray(mf.gains_at(st, idx))
        ref = np.asarray(full_sweep(mf, st))
        assert g[1] < -1e29 and g[3] < -1e29
        np.testing.assert_array_equal(g[[0, 2]], ref[[4, 9]])


@pytest.mark.parametrize("metric", METRICS)
def test_selection_matches_dense_path(rng, metric):
    """Acceptance: identical ids, gains within tolerance, dense vs MF."""
    for mf, dense in _pairs(rng, metric):
        r_mf = solve(SelectionSpec(mf, 5))
        r_d = solve(SelectionSpec(dense, 5))
        assert list(np.asarray(r_mf.order)) == list(np.asarray(r_d.order))
        _close(r_mf.gains, r_d.gains)
        assert int(r_mf.n_evals) == int(r_d.n_evals)


# -- solve-mode exactness: sequential vs batched vs served --------------------


@pytest.mark.parametrize("optimizer", ("NaiveGreedy", "LazyGreedy"))
@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("family", ("fl", "gc"))
def test_solve_modes_bit_identical(rng, family, metric, optimizer):
    x = _tricky_points(rng)
    if family == "fl":
        fn = FacilityLocationMF.from_features(x, metric=metric)
    else:
        fn = GraphCutMF.from_features(x, metric=metric, lam=0.4)
    kw = {"screen_k": 8} if optimizer == "LazyGreedy" else {}
    spec = SelectionSpec(fn, 5, optimizer=optimizer, **kw)
    seq = solve(spec)
    _same(seq, solve([spec, spec], mode="batched")[0])
    _same(seq, solve([spec], mode="served")[0])  # pads n to its bucket


@pytest.mark.parametrize("family", ("fl", "gc"))
def test_knn_solve_modes_bit_identical(rng, family):
    x = make_points(rng, 41)
    src = knn_from_features(x, 6, metric="rbf")
    if family == "fl":
        fn = FacilityLocationMF.from_knn(src.indices, src.weights)
    else:
        fn = GraphCutMF.from_knn(src.indices, src.weights, lam=0.4)
    spec = SelectionSpec(fn, 4)
    seq = solve(spec)
    _same(seq, solve([spec, spec], mode="batched")[0])
    _same(seq, solve([spec], mode="served")[0])


# -- the sparse k-NN source ---------------------------------------------------


def test_knn_source_is_the_sparsified_dense_matrix(rng):
    x = make_points(rng, 29)
    S = sparsify_topk(create_kernel(x, metric="rbf"), 5)
    src = knn_from_features(x, 5, metric="rbf")
    _close(src.to_dense(), S)
    fl_knn = FacilityLocationMF.from_knn(src.indices, src.weights)
    fl_dense = FacilityLocation.from_kernel(src.to_dense())
    st_k, st_d = fl_knn.init_state(), fl_dense.init_state()
    _close(full_sweep(fl_knn, st_k), full_sweep(fl_dense, st_d))
    st_k, st_d = fl_knn.update(st_k, 11), fl_dense.update(st_d, 11)
    _close(full_sweep(fl_knn, st_k), full_sweep(fl_dense, st_d))
    gc_knn = GraphCutMF.from_knn(src.indices, src.weights, lam=0.3)
    gc_dense = GraphCut.from_kernel(src.to_dense(), lam=0.3)
    _close(full_sweep(gc_knn, gc_knn.init_state()),
           full_sweep(gc_dense, gc_dense.init_state()))
    mask = jnp.zeros((29,), bool).at[jnp.asarray([1, 11, 20])].set(True)
    _close(gc_knn.evaluate(mask), gc_dense.evaluate(mask))
    _close(fl_knn.evaluate(mask), fl_dense.evaluate(mask))


# -- fused Pallas sweeps vs jnp oracles (interpret mode off-TPU) --------------


@pytest.mark.parametrize("metric", METRICS + ("euclidean",))
def test_flmf_pallas_matches_ref(rng, metric):
    u, n, d = 45, 70, 12  # nothing tile-aligned
    x, y = make_points(rng, u, d), make_points(rng, n, d)
    if metric == "cosine":  # kernel contract: cosine rows arrive normalized
        x = x / np.linalg.norm(x, axis=1, keepdims=True)
        y = y / np.linalg.norm(y, axis=1, keepdims=True)
    xx, yy = (x * x).sum(1), (y * y).sum(1)
    curmax = np.abs(make_points(rng, u, 1))[:, 0]
    got = ops.flmf_gains(x, y, xx, yy, curmax, metric=metric)
    want = ops.flmf_gains_ref(x, y, curmax, metric=metric)
    _close(got, want)
    idx = jnp.asarray([3, 69, -1, 17], jnp.int32)
    got_at = np.asarray(
        ops.flmf_gains_at(x, y, xx, yy, curmax, idx, metric=metric)
    )
    assert got_at[2] < -1e29
    _close(got_at[[0, 1, 3]], np.asarray(want)[[3, 69, 17]])


@pytest.mark.parametrize("metric", ("euclidean", "rbf"))
def test_self_distance_is_exactly_zero(rng, metric):
    """A point against itself is at distance 0 on every path — dense
    kernel (XLA and Pallas), streamed column, fused sweep — even where
    xx + yy - 2 x.y leaves a large float32 residue (|x|^2 ~ 10^3)."""
    x = 10.0 + make_points(rng, 45, 12)  # common offset: |x|^2 ~ 10^3
    for use_pallas in (False, True):
        S = create_kernel(x, metric=metric, use_pallas=use_pallas)
        np.testing.assert_array_equal(np.diagonal(np.asarray(S)), 1.0)
    src = feature_source(x, metric=metric)
    for j in (0, 7, 44):
        assert float(src.col(jnp.asarray(j))[j]) == 1.0
    # the fused sweep at an empty state: each candidate's gain is its column
    # sum, self term included, so it must match the exact dense kernel
    S = np.asarray(create_kernel(x, metric=metric))
    curmax = np.zeros(45, np.float32)
    want = S.sum(axis=0)
    got = ops.flmf_gains(
        src.x, src.y, src.xx, src.yy, curmax, metric=metric, col_ids=src.col_ids
    )
    _close(got, want)
    _close(src.fl_gains(jnp.asarray(curmax)), want)
    idx = jnp.asarray([3, -1, 44], jnp.int32)
    got_at = np.asarray(
        ops.flmf_gains_at(
            src.x, src.y, src.xx, src.yy, curmax, idx,
            metric=metric, col_ids=src.col_ids,
        )
    )
    _close(got_at[[0, 2]], want[[3, 44]])


@pytest.mark.parametrize("metric", METRICS + ("euclidean",))
def test_gcmf_pallas_matches_ref(rng, metric):
    n, d = 70, 12
    y = make_points(rng, n, d)
    if metric == "cosine":
        y = y / np.linalg.norm(y, axis=1, keepdims=True)
    yy = (y * y).sum(1)
    src = feature_source(y, metric=metric)
    total, diag = src.col_sums(), src.diag()
    selmask = np.zeros(n, np.float32)
    selmask[[4, 31, 66]] = 1.0
    lam = jnp.asarray(0.4, jnp.float32)
    got = ops.gcmf_gains(y, yy, selmask, total, diag, lam, metric=metric)
    want = ops.gcmf_gains_ref(y, selmask, total, lam, metric=metric, diag=diag)
    _close(got, want)
    idx = jnp.asarray([0, -1, 42], jnp.int32)
    got_at = np.asarray(
        ops.gcmf_gains_at(y, yy, selmask, total, diag, lam, idx, metric=metric)
    )
    assert got_at[1] < -1e29
    _close(got_at[[0, 2]], np.asarray(want)[[0, 42]])


# -- the memory ceiling: no (n, n) intermediate -------------------------------


def _assert_no_square(traced, n):
    """The jaxpr walk now lives in the lint package (the JAXPR rule runs
    it over a whole manifest of cells); this keeps the test suite and the
    lint gate on one implementation."""
    import sys

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve().parents[1]))
    from tools.lint.jaxpr_audit import square_intermediates

    problems = square_intermediates(traced.jaxpr, n, TILE)
    assert not problems, problems


def test_full_sweep_has_no_square_intermediate(rng):
    n, d = 50_000, 8
    x = make_points(rng, 64, d)
    y = make_points(rng, n, d)
    fn = FacilityLocationMF.from_features(x, y=y, metric="rbf")
    traced = jax.make_jaxpr(lambda f: full_sweep(f, f.init_state()))(fn)
    _assert_no_square(traced, n)


def test_greedy_has_no_square_intermediate(rng):
    from repro.core.optimizers.greedy import naive_greedy

    n, d = 50_000, 8
    x = make_points(rng, 64, d)
    y = make_points(rng, n, d)
    fn = FacilityLocationMF.from_features(x, y=y, metric="dot")
    traced = jax.make_jaxpr(lambda f: naive_greedy(f, 3))(fn)
    _assert_no_square(traced, n)


# -- million-point smoke (slow tier) ------------------------------------------


@pytest.mark.slow
def test_million_point_fl_feature_source(rng):
    """FL selection over n = 10^6 candidates on one host: the represented
    set is a small sample (the summarization shape), candidates stream in
    feature tiles — peak bytes O(n * d), never n^2 (the jaxpr walk pins
    the ceiling; this runs the real thing)."""
    n, d, u = 1_000_000, 8, 512
    y = make_points(rng, n, d)
    x = y[rng.choice(n, size=u, replace=False)]
    fn = FacilityLocationMF.from_features(x, y=y, metric="dot")
    traced = jax.make_jaxpr(lambda f: full_sweep(f, f.init_state()))(fn)
    _assert_no_square(traced, n)
    res = solve(SelectionSpec(fn, 3))
    order = [i for i in np.asarray(res.order) if i >= 0]
    assert len(order) == 3 and len(set(order)) == 3
    assert all(0 <= i < n for i in order)
    gains = np.asarray(res.gains)[:3]
    assert np.all(np.diff(gains) <= 1e-3)  # greedy gains are non-increasing


@pytest.mark.slow
def test_million_point_fl_knn_source(rng):
    """The sparse k-NN source rides the same backend contract at n = 10^6:
    O(n * k) scatter sweeps, no similarity matrix."""
    n, k = 1_000_000, 8
    indices = rng.integers(0, n, size=(n, k)).astype(np.int32)
    weights = rng.random(size=(n, k)).astype(np.float32)
    fn = FacilityLocationMF.from_knn(indices, weights, n_cols=n)
    res = solve(SelectionSpec(fn, 4))
    order = [i for i in np.asarray(res.order) if i >= 0]
    assert len(order) == 4 and len(set(order)) == 4

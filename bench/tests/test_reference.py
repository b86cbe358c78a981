"""The plain reference at a small size on the CPU: the library's ``solve``
agrees with it within each cell's limits, its comparison flags a wrong
answer, its controls (a lower precision put in the program's place) fail
each cell's limits, and its blocks of rows compute what the whole (n, n)
similarity does."""
import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import data
import reference
from conftest import BENCH

CELLS = {"craig-cifar10.naive": "craig-cifar10"}


def _cell(workload):
    config = json.loads((BENCH / "configs" / f"{CELLS[workload]}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{workload}.json").read_text())
    return config, limits["limits"], limits["controls"]


def _passes(numbers, limits):
    return all(numbers[k] <= v for k, v in limits.items() if k in numbers)


def _groups(config, seed, n, groups=2):
    """Embeddings at the configuration's width, with fewer points."""
    return data.for_config({**config, "n": n}, seed, groups=groups)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_solve_agrees_with_the_reference(workload):
    from repro.core import FacilityLocation, SelectionSpec, create_kernel, solve

    config, limits, _ = _cell(workload)
    budget = min(config["budget"], 24)
    for x in _groups(config, 3, 192):
        fn = FacilityLocation.from_kernel(create_kernel(x, metric=config["metric"]))
        res = jax.device_get(solve(SelectionSpec(fn, budget, "NaiveGreedy")))
        numbers = reference.compare(x, config["metric"], res.order, res.gains, budget)
        assert numbers["invalid"] == 0
        assert _passes(numbers, limits), numbers


@pytest.mark.parametrize(
    "workload, precision",
    [(w, p) for w in sorted(CELLS) for p in _cell(w)[2]],
)
def test_the_control_is_not_correct(workload, precision):
    """The reference in a lower precision that the cell names as its
    control, held to itself at highest, fails a limit on every seed tried,
    at the cell's own size (one group of each seed)."""
    config, limits, _ = _cell(workload)
    budget = config["budget"]
    for seed in (1, 2, 3):
        x = _groups(config, seed, config["n"], groups=1)[0]
        picks, gains = jax.device_get(reference.greedy(x, config["metric"], budget, precision))
        numbers = reference.compare(x, config["metric"], picks, gains, budget)
        assert not _passes(numbers, limits), (seed, numbers)


def test_compare_flags_wrong_answers():
    x = data.embeddings(data.key(0), groups=1, n=64, dim=8)[0]
    picks, gains = (np.array(a) for a in jax.device_get(reference.greedy(x, "euclidean", 6)))
    compare = partial(reference.compare, x, "euclidean")
    ok = compare(picks, gains, 6)
    assert ok["invalid"] == 0 and ok["shortfall"] == 0 and ok["gain_err"] < 1e-6
    worse = picks.copy()
    worse[3] = np.setdiff1d(np.arange(64), picks)[0]
    assert compare(worse, gains, 6)["shortfall"] > 1e-3
    assert compare(picks, gains * 1.001, 6)["gain_err"] >= 1e-3
    assert compare(picks[:5], gains[:5], 6)["invalid"] == 1
    repeated = picks.copy()
    repeated[5] = repeated[0]
    assert compare(repeated, gains, 6)["invalid"] == 1


# the whole-matrix reference the blocks replaced, kept as their oracle


@partial(jax.jit, static_argnames=("metric", "precision"))
def whole_kernel(x, metric, precision="highest"):
    if metric == "euclidean":
        sq = jnp.sum(x * x, 1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * reference._dot(x, x, precision)
        d2 = jnp.where(jnp.eye(x.shape[0], dtype=bool), 0.0, jnp.maximum(d2, 0.0))
        s = 1.0 / (1.0 + jnp.sqrt(d2))
    else:
        xn = x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, 1, keepdims=True)), 1e-12)
        s = 0.5 * (1.0 + reference._dot(xn, xn, precision))
    return reference._bf16(s) if precision == "bfloat16" else s


def _whole_gains(S, curmax):
    return jnp.sum(jnp.maximum(S - curmax[:, None], 0.0), axis=0)


@jax.jit
def whole_replay(S, picks):
    def step(curmax, j):
        g = _whole_gains(S, curmax)
        return jnp.maximum(curmax, S[:, j]), (jnp.max(g), g[j])

    curmax, (top, picked) = jax.lax.scan(step, jnp.zeros((S.shape[0],), jnp.float32), picks)
    return top, picked, jnp.sum(curmax)


@partial(jax.jit, static_argnames="k")
def whole_greedy(S, k):
    def step(curmax, _):
        g = _whole_gains(S, curmax)
        j = jnp.argmax(g)
        return jnp.maximum(curmax, S[:, j]), (j, g[j])

    return jax.lax.scan(step, jnp.zeros((S.shape[0],), jnp.float32), None, length=k)[1]


METRICS = ("euclidean", "cosine")


def _points(n, dim=10, seed=4):
    return data.embeddings(data.key(seed), groups=1, n=n, dim=dim, scale=0.3, activation="softmax")[0]


@pytest.mark.parametrize("metric, n, budget", [("euclidean", 5000, 500), ("cosine", 700, 60)])
def test_one_block_replays_as_the_whole_matrix_bit_for_bit(metric, n, budget):
    """A group that fits one block (every group of ``craig-cifar10``, at its
    own size in the first case) reads what the whole matrix read."""
    x = _points(n)
    assert reference.block_rows(n) == n
    picks = whole_greedy(whole_kernel(x, metric), budget)[0]
    want = jax.device_get(whole_replay(whole_kernel(x, metric), picks))
    got = jax.device_get(reference.replay(x, metric, picks))
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("precision", reference.PRECISIONS)
def test_one_block_greedy_is_the_whole_matrix_greedy_bit_for_bit(metric, precision):
    x = _points(600)
    want = jax.device_get(whole_greedy(whole_kernel(x, metric, precision), 40))
    got = jax.device_get(reference.greedy(x, metric, 40, precision))
    for w, g in zip(want, got):
        assert np.array_equal(w, g)


def test_block_rows():
    assert reference.block_rows(5000) == 5000  # craig-cifar10: one block per class
    assert reference.block_rows(16384) == 16384
    assert reference.block_rows(16385) == 16376
    assert reference.block_rows(50000) == 5368


@pytest.fixture
def blocks_of_96(monkeypatch):
    """Blocks of 96 rows at n 1,000: ten whole ones and a ragged last."""
    monkeypatch.setattr(reference, "BLOCK_BYTES", 96 * 1000 * 4)
    assert reference.block_rows(1000) == 96


@pytest.mark.parametrize("metric", METRICS)
def test_ragged_blocks_replay_as_the_whole_matrix(metric, blocks_of_96):
    x = _points(1000, dim=32)
    S = whole_kernel(x, metric)
    picks = whole_greedy(S, 50)[0]
    want = [np.asarray(a, np.float64) for a in jax.device_get(whole_replay(S, picks))]
    got = [np.asarray(a, np.float64) for a in jax.device_get(reference.replay(x, metric, picks))]
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=0)


@pytest.mark.parametrize("metric", METRICS)
def test_ragged_blocks_greedy_follows_the_whole_matrix(metric, blocks_of_96):
    """Each pick of the blocked greedy is the whole matrix's best within
    float32 rounding, with its gain."""
    x = _points(1000, dim=32)
    picks, gains = jax.device_get(reference.greedy(x, metric, 50))
    top, picked, _ = (np.asarray(a, np.float64) for a in jax.device_get(whole_replay(whole_kernel(x, metric), picks)))
    assert len(set(picks.tolist())) == 50
    assert np.max((top - picked) / top) <= 1e-6
    np.testing.assert_allclose(gains, picked, rtol=1e-6, atol=0)


def test_every_row_is_in_one_block_with_its_self_distance_zero(blocks_of_96):
    """Each row is live in exactly one block, where it matches the whole
    matrix's row, and a point's euclidean similarity to itself is exactly
    1 at every block boundary."""
    n = 1000
    x = _points(n, dim=32)
    S = np.asarray(whole_kernel(x, "euclidean"))
    rows = reference.block_rows(n)
    seen = np.zeros(n, int)
    for start, first in zip(*reference._starts(n, rows)):
        block = np.asarray(reference._block(x, start, first, rows, "euclidean", "highest"))
        live = np.arange(start, start + rows) >= first
        assert not block[~live].any()
        ids = np.arange(start, start + rows)[live]
        seen[ids] += 1
        assert np.all(block[live][np.arange(len(ids)), ids] == 1.0)
        np.testing.assert_allclose(block[live], S[ids], rtol=1e-6, atol=0)
    assert np.all(seen == 1)


def test_seeds_of_any_size():
    a, b, c = (np.asarray(data.embeddings(data.key(s), groups=1, n=8, dim=4)) for s in (7, 7 + 2**32, 2**40))
    assert not np.array_equal(a, b) and not np.array_equal(b, c)
    again = np.asarray(data.embeddings(data.key(7 + 2**32), groups=1, n=8, dim=4))
    assert np.array_equal(b, again)

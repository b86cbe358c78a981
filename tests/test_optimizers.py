"""Optimizer correctness + the paper's approximation-quality claims (§2, §5.3).

- naive greedy >= (1 - 1/e) of the exhaustive optimum on small instances
  (paper: in practice ~0.98 — we assert the guarantee and report the ratio)
- lazy greedy (bound-screened) returns the identical set to naive greedy
- host Minoux heap returns the identical set with fewer evaluations
- stochastic / lazier-than-lazy reach >= 95% of the greedy value
- cover greedy reaches the requested coverage
- sharded solve (a unit mesh, and 4x2 in a subprocess) == serial greedy
"""
import itertools
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import mask_from_indices
from repro.core import (
    FacilityLocation,
    GraphCut,
    LogDet,
    SetCover,
    cover_greedy,
    SelectionSpec,
    create_kernel,
    host_lazy_greedy,
    knapsack_greedy,
    lazier_than_lazy_greedy,
    lazy_greedy,
    maximize,
    naive_greedy,
    solve,
    stochastic_greedy,
)


def _clustered_points(rng, n=60, d=5, k=6):
    centers = rng.normal(scale=4.0, size=(k, d))
    return (
        centers[rng.integers(0, k, n)] + rng.normal(scale=0.7, size=(n, d))
    ).astype(np.float32)


def _fns(rng, n=16):
    x = _clustered_points(rng, n=n)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    return {
        "fl": FacilityLocation.from_kernel(S),
        "gc": GraphCut.from_kernel(S, lam=0.3),
        "logdet": LogDet.from_kernel(
            0.5 * S + 0.75 * np.eye(n, dtype=np.float32), max_select=6
        ),
        "sc": SetCover.from_cover(
            rng.integers(0, 2, size=(n, 12)).astype(np.float32)
        ),
    }


@pytest.mark.parametrize("name", ["fl", "gc", "logdet", "sc"])
def test_greedy_within_bound_of_optimum(name, rng):
    fn = _fns(rng, n=14)[name]
    budget = 4
    res = naive_greedy(fn, budget, False, False)
    best = -np.inf
    for combo in itertools.combinations(range(14), budget):
        mask = mask_from_indices(jnp.asarray(combo, jnp.int32), 14)
        best = max(best, float(fn.evaluate(mask)))
    got = float(fn.evaluate(mask_from_indices(res.order, fn.n)))
    ratio = got / best if best > 0 else 1.0
    assert ratio >= 1 - 1 / np.e - 1e-6, f"{name}: ratio {ratio:.4f}"
    # the paper observes ~0.98 in practice; these instances should be close
    assert ratio >= 0.9, f"{name}: ratio {ratio:.4f} unexpectedly low"


@pytest.mark.parametrize("name", ["fl", "gc", "logdet", "sc"])
def test_lazy_equals_naive(name, rng):
    fn = _fns(rng, n=40)[name]
    r_naive = naive_greedy(fn, 8, False, False)
    r_lazy = lazy_greedy(fn, 8, 8, False, False)
    assert r_naive.as_list() == r_lazy.as_list()
    assert int(r_lazy.n_evals) <= int(r_naive.n_evals)


@pytest.mark.parametrize("name", ["fl", "gc", "sc"])
def test_host_lazy_equals_naive(name, rng):
    fn = _fns(rng, n=40)[name]
    r_naive = naive_greedy(fn, 8)
    order, gains, n_evals = host_lazy_greedy(fn, 8)
    # ULP-level reduction-order noise can flip exact ties between the heap
    # path (single-column gains) and the vectorized sweep; the objective
    # value must agree to float precision regardless
    got = float(fn.evaluate(mask_from_indices(jnp.asarray(order), fn.n)))
    want = float(r_naive.value)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert n_evals <= int(r_naive.n_evals)


def test_stochastic_and_ltl_quality(rng):
    fn = _fns(rng, n=60)["fl"]
    ref = float(naive_greedy(fn, 10).value)
    st = float(stochastic_greedy(fn, 10, jax.random.PRNGKey(0), 0.01).value)
    ltl = float(
        lazier_than_lazy_greedy(fn, 10, jax.random.PRNGKey(0), 0.01).value
    )
    assert st >= 0.95 * ref
    assert ltl >= 0.95 * ref


def test_eval_count_ordering(rng):
    """Hardware-independent reproduction of the paper's Table 2 ordering:
    evaluations(naive) > evaluations(stochastic) > evaluations(lazy-family)."""
    fn = _fns(rng, n=60)["fl"]
    ev_naive = int(naive_greedy(fn, 10).n_evals)
    ev_st = int(stochastic_greedy(fn, 10, jax.random.PRNGKey(0), 0.01).n_evals)
    ev_lazy = int(lazy_greedy(fn, 10).n_evals)
    ev_ltl = int(
        lazier_than_lazy_greedy(fn, 10, jax.random.PRNGKey(0), 0.01).n_evals
    )
    assert ev_naive > ev_st
    assert ev_naive > ev_lazy
    assert ev_ltl <= ev_st + 60  # ltl adds one initial full sweep

def test_maximize_api(rng):
    fn = _fns(rng, n=30)["fl"]
    out = maximize(fn, budget=5, optimizer="NaiveGreedy")
    assert len(out) == 5 and all(isinstance(i, int) for i, _ in out)
    with pytest.raises(ValueError):
        maximize(fn, budget=5, optimizer="Nope")


def test_cover_greedy_reaches_coverage(rng):
    fn = _fns(rng, n=30)["sc"]
    total = float(fn.evaluate(jnp.ones(30, bool)))
    res = cover_greedy(fn, coverage=0.8 * total, max_steps=30)
    assert float(res.value) >= 0.8 * total


def test_knapsack_respects_budget(rng):
    fn = _fns(rng, n=30)["fl"]
    costs = rng.uniform(0.5, 2.0, 30).astype(np.float32)
    res = knapsack_greedy(fn, budget=4.0, max_steps=30, costs=costs)
    chosen = [i for i, _ in res.as_list()]
    assert sum(costs[i] for i in chosen) <= 4.0 + 1e-5


def test_distributed_matches_serial(rng):
    x = _clustered_points(rng, n=64)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    fn = FacilityLocation.from_kernel(S)
    ref = naive_greedy(fn, 12)
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    (got,) = solve([SelectionSpec(fn, 12)], mesh=mesh)
    assert got.as_list() == ref.as_list()
    np.testing.assert_array_equal(np.asarray(got.gains), np.asarray(ref.gains))
    assert int(got.n_evals) == int(ref.n_evals)


_MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax
    from repro.core import (
        FacilityLocation, SelectionSpec, create_kernel, naive_greedy, solve,
    )

    rng = np.random.default_rng(0)
    fns = [
        FacilityLocation.from_kernel(np.asarray(create_kernel(
            rng.normal(size=(64, 8)).astype(np.float32), metric="euclidean"
        )))
        for _ in range(4)
    ]
    mesh = jax.make_mesh((4, 2), ("batch", "data"))
    got = solve([SelectionSpec(fn, 10) for fn in fns], mesh=mesh)
    for fn, res in zip(fns, got):
        ref = naive_greedy(fn, 10)
        assert res.as_list() == ref.as_list(), (res.as_list(), ref.as_list())
    print("MULTIDEV_OK")
    """
)


def test_distributed_greedy_eight_devices():
    """Real 8-device (4x2 mesh) run in a subprocess — proves the sharded
    engine's collectives are correct, not just its single-device lowering.

    (Historically @slow: without JAX_PLATFORMS=cpu the clean-env subprocess
    spent minutes probing for non-CPU backends before compiling.)"""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=pathlib.Path(__file__).resolve().parent.parent,
    )
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr


def test_greedy_respects_stop_flags(rng):
    # a modular function with some negative gains
    n = 12
    w = rng.normal(size=n).astype(np.float32)
    cover = np.eye(n, dtype=np.float32)
    fn = SetCover.from_cover(cover, w)
    res = naive_greedy(fn, n, True, True)
    chosen = [i for i, _ in res.as_list()]
    assert all(w[i] > 0 for i in chosen)
    assert len(chosen) == int((w > 0).sum())

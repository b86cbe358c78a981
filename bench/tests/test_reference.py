"""The plain reference at a small size on the CPU: the library's ``solve``
agrees with it within each cell's limits, its comparison flags a wrong
answer, and its controls (a lower precision put in the program's place)
fail each cell's limits."""
import json

import jax
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
        numbers = reference.compare(reference.kernel(x, config["metric"]), res.order, res.gains, budget)
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
        low = reference.kernel(x, config["metric"], precision)
        picks, gains = jax.device_get(reference.greedy(low, budget))
        numbers = reference.compare(reference.kernel(x, config["metric"]), picks, gains, budget)
        assert not _passes(numbers, limits), (seed, numbers)


def test_compare_flags_wrong_answers():
    x = data.embeddings(data.key(0), groups=1, n=64, dim=8)[0]
    S = reference.kernel(x, "euclidean")
    picks, gains = (np.array(a) for a in jax.device_get(reference.greedy(S, 6)))
    ok = reference.compare(S, picks, gains, 6)
    assert ok["invalid"] == 0 and ok["shortfall"] == 0 and ok["gain_err"] < 1e-6
    worse = picks.copy()
    worse[3] = np.setdiff1d(np.arange(64), picks)[0]
    assert reference.compare(S, worse, gains, 6)["shortfall"] > 1e-3
    assert reference.compare(S, picks, gains * 1.001, 6)["gain_err"] >= 1e-3
    assert reference.compare(S, picks[:5], gains[:5], 6)["invalid"] == 1
    repeated = picks.copy()
    repeated[5] = repeated[0]
    assert reference.compare(S, repeated, gains, 6)["invalid"] == 1


def test_seeds_of_any_size():
    a, b, c = (np.asarray(data.embeddings(data.key(s), groups=1, n=8, dim=4)) for s in (7, 7 + 2**32, 2**40))
    assert not np.array_equal(a, b) and not np.array_equal(b, c)
    again = np.asarray(data.embeddings(data.key(7 + 2**32), groups=1, n=8, dim=4))
    assert np.array_equal(b, again)

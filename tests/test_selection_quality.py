"""Selection quality on a seeded 8-mode mixture, through ``solve``.

The headline behavioural test: FacilityLocation coreset selection over a
multi-modal pool covers the latent modes far better than a prefix of the
same budget — the paper's 'efficient training' premise made measurable.
The targeted test is the paper's targeted-learning application: FLQMI with
queries from one mode picks pool items of that mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FLCG,
    FLQMI,
    DisparitySum,
    FacilityLocation,
    SelectionSpec,
    create_kernel,
    solve,
)

N_MODES = 8


@pytest.fixture(scope="module")
def pool():
    """64 points of 16 features, each drawn around one of 8 seeded centres;
    the mode of every point is drawn at random, so a prefix of the pool is
    an unbalanced sample of the modes."""
    rng = np.random.default_rng(0)
    centers = rng.normal(scale=4.0, size=(N_MODES, 16))
    modes = rng.integers(0, N_MODES, 64)
    emb = centers[modes] + rng.normal(size=(64, 16))
    return jnp.asarray(emb.astype(np.float32)), modes


def _kernel(x, y=None):
    return create_kernel(x, y, metric="euclidean")


def _select(fn, budget):
    spec = SelectionSpec(
        fn, budget, "LazyGreedy", screen_k=8,
        stopIfZeroGain=False, stopIfNegativeGain=False,
    )
    order = np.asarray(solve(spec).order)
    return order[order >= 0]


def test_coreset_covers_modes_better_than_prefix(pool):
    emb, modes = pool
    chosen = _select(FacilityLocation.from_kernel(_kernel(emb)), 8)
    modes_chosen = {int(modes[i]) for i in chosen}
    modes_prefix = {int(m) for m in modes[:8]}
    assert len(modes_chosen) >= len(modes_prefix)
    assert len(modes_chosen) >= 7  # 8 picks should cover >= 7 of 8 modes


def test_selector_objectives_run(pool):
    """Representative (FL), targeted (FLQMI), diverse (DisparitySum) and
    privacy-preserving (FLCG) selection all return distinct picks."""
    emb, _ = pool
    q, p = emb[:4], emb[4:8]
    sim = _kernel(emb)
    dist = 1.0 / jnp.maximum(sim, 1e-6) - 1.0  # invert 1/(1+d)
    for fn in (
        FacilityLocation.from_kernel(sim),
        FLQMI.build(_kernel(q, emb)),
        DisparitySum.from_distance(dist),
        FLCG.build(sim, _kernel(emb, p)),
    ):
        chosen = _select(fn, 6)
        assert len(chosen) == 6 and len(set(chosen.tolist())) == 6


def test_targeted_selection_prefers_query_mode(pool):
    """FLQMI with queries from one mode must pick pool items of that mode
    (the paper's targeted-learning application)."""
    emb, modes = pool
    target_mode = 3
    q_idx = np.flatnonzero(modes == target_mode)[:4]
    chosen = _select(FLQMI.build(_kernel(emb[q_idx], emb), eta=1.0), 6)
    hit = sum(1 for i in chosen if modes[i] == target_mode)
    assert hit >= 4, f"only {hit}/6 picks in the target mode"

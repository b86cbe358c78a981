"""Optimizer-equivalence + batched-engine correctness.

- lazy_greedy must match naive_greedy element-for-element on every function
  class (the lazy bound screen is exact under submodularity)
- batched_maximize must match a Python loop of single maximize calls per
  instance — orders, gains, AND the n_evals accounting, exactly
- padding masks: a zero-padded instance with a valid mask selects the same
  set as the unpadded instance
- _should_stop edge cases: the stopIfZeroGain / stopIfNegativeGain semantics
  are pinned (zero-gain stops iff stopIfZeroGain; stopIfZeroGain subsumes
  negative gains; with both off the budget is always exhausted)
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BatchedEngine,
    FacilityLocation,
    FeatureBased,
    GraphCut,
    LogDet,
    ProbabilisticSetCover,
    SetCover,
    batched_maximize,
    create_kernel,
    lazy_greedy,
    naive_greedy,
)
from repro.core.optimizers.greedy import _should_stop

N = 32


def _build(name, rng):
    x = rng.normal(size=(N, 6)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="cosine"))
    if name == "fl":
        return FacilityLocation.from_kernel(S)
    if name == "fl_kernel":
        return FacilityLocation.from_kernel(S, use_kernel=True)
    if name == "gc":
        return GraphCut.from_kernel(S, lam=0.3)
    if name == "gc_kernel":
        return GraphCut.from_kernel(S, lam=0.3, use_kernel=True)
    if name == "logdet":
        return LogDet.from_kernel(S + 0.5 * np.eye(N, dtype=np.float32))
    if name == "sc":
        return SetCover.from_cover(
            rng.integers(0, 2, size=(N, 12)).astype(np.float32)
        )
    if name == "psc":
        return ProbabilisticSetCover.from_probs(
            rng.uniform(0, 0.9, size=(N, 10)).astype(np.float32)
        )
    if name == "fb":
        return FeatureBased.from_features(
            rng.uniform(0, 1, size=(N, 9)).astype(np.float32), concave="sqrt"
        )
    if name == "fb_kernel":
        return FeatureBased.from_features(
            rng.uniform(0, 1, size=(N, 9)).astype(np.float32),
            concave="sqrt",
            use_kernel=True,
        )
    raise KeyError(name)


# every submodular function class (disparity functions are excluded: they are
# not submodular, so the lazy bound screen carries no guarantee there)
ALL_CLASSES = [
    "fl",
    "fl_kernel",
    "gc",
    "gc_kernel",
    "logdet",
    "sc",
    "psc",
    "fb",
    "fb_kernel",
]


@pytest.mark.parametrize("name", ALL_CLASSES)
def test_lazy_equals_naive_every_class(name, rng):
    fn = _build(name, rng)
    r_naive = naive_greedy(fn, 8, False, False)
    r_lazy = lazy_greedy(fn, 8, 8, False, False)
    assert list(np.asarray(r_naive.order)) == list(np.asarray(r_lazy.order))
    np.testing.assert_allclose(
        np.asarray(r_naive.gains), np.asarray(r_lazy.gains), rtol=1e-5, atol=1e-5
    )
    # NOTE: no n_evals <= naive assertion here — on flat gain distributions
    # (e.g. probabilistic set cover) the bound screen's fallback sweeps can
    # cost slightly more than naive; identical OUTPUT is the guarantee.
    assert int(r_lazy.n_evals) >= fn.n  # at least the initial bound sweep


def _fl_instances(rng, B, n=24):
    fns = []
    for _ in range(B):
        x = rng.normal(size=(n, 5)).astype(np.float32)
        S = np.asarray(create_kernel(x, metric="euclidean"))
        fns.append(FacilityLocation.from_kernel(S))
    return fns


@pytest.mark.parametrize("optimizer", ["NaiveGreedy", "LazyGreedy"])
def test_batched_matches_sequential_loop(optimizer, rng):
    """B=8 instances, mixed budgets: per-instance results must be identical
    to a Python loop of single maximize calls — including n_evals."""
    B = 8
    fns = _fl_instances(rng, B)
    budgets = [5, 3, 7, 5, 2, 6, 4, 5]
    single = {"NaiveGreedy": naive_greedy, "LazyGreedy": lazy_greedy}[optimizer]
    batched = batched_maximize(fns, budgets, optimizer=optimizer, return_result=True)
    assert len(batched) == B
    for i, (fn, b) in enumerate(zip(fns, budgets)):
        seq = single(fn, b)
        assert list(np.asarray(seq.order)) == list(np.asarray(batched[i].order)), i
        np.testing.assert_allclose(
            np.asarray(seq.gains), np.asarray(batched[i].gains), rtol=1e-6
        )
        assert int(seq.n_evals) == int(batched[i].n_evals), i
        np.testing.assert_allclose(
            float(seq.value), float(batched[i].value), rtol=1e-5
        )


def test_batched_naive_eval_accounting_exact(rng):
    """n_evals must be exactly (steps taken) * n for the naive engine."""
    B = 4
    n = 24
    fns = _fl_instances(rng, B, n=n)
    budgets = [3, 5, 1, 4]
    res = batched_maximize(fns, budgets, return_result=True)
    for r, b in zip(res, budgets):
        steps = int((np.asarray(r.order) >= 0).sum())
        assert steps == b  # monotone fn, budget < n: never stops early
        assert int(r.n_evals) == steps * n


def test_batched_valid_mask_padding(rng):
    """Zero-padded instances + valid mask == the unpadded instance."""
    n_small, n_pad = 20, 30
    x = rng.normal(size=(n_small, 6)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    Sp = np.zeros((n_pad, n_pad), np.float32)
    Sp[:n_small, :n_small] = S
    fn_small = FacilityLocation.from_kernel(S)
    fn_pad = FacilityLocation.from_kernel(Sp)
    valid = np.zeros((4, n_pad), bool)
    valid[:, :n_small] = True
    res = batched_maximize(
        [fn_pad] * 4, 5, valid=jnp.asarray(valid), return_result=True
    )
    seq = naive_greedy(fn_small, 5)
    for r in res:
        assert list(np.asarray(seq.order)) == list(np.asarray(r.order))
        np.testing.assert_allclose(
            np.asarray(seq.gains), np.asarray(r.gains), rtol=1e-6
        )


def test_batched_lazy_never_selects_padding(rng):
    """Exhaustion edge case: with fewer valid candidates than screen_k and
    stopping disabled, the lazy screen's top-k spills into padded indices —
    they must be masked out, never selected."""
    n_valid, n_pad = 4, 16
    x = rng.normal(size=(n_valid, 4)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    Sp = np.zeros((n_pad, n_pad), np.float32)
    Sp[:n_valid, :n_valid] = S
    valid = np.zeros((2, n_pad), bool)
    valid[:, :n_valid] = True
    res = batched_maximize(
        [FacilityLocation.from_kernel(Sp)] * 2,
        10,  # budget far beyond the valid count
        optimizer="LazyGreedy",
        valid=jnp.asarray(valid),
        return_result=True,
        stopIfZeroGain=False,
        stopIfNegativeGain=False,
    )
    for r in res:
        order = np.asarray(r.order)
        chosen = order[order >= 0]
        # padded candidates must never appear (pre-fix, top_k spill let
        # their unmasked 0-gains win over the NEG_INF-masked valid set)
        assert (chosen < n_valid).all(), order
        # the real selection (first n_valid picks) is unique; past
        # exhaustion with stopping disabled the argmax degenerately repeats
        # — same as the sequential optimizers, so not asserted against
        assert len(set(chosen[:n_valid].tolist())) == n_valid, order


def test_batched_engine_reuse(rng):
    """A resident BatchedEngine answers repeated queries consistently and
    supports per-call budgets."""
    fns = _fl_instances(rng, 3)
    engine = BatchedEngine(fns)
    first = engine.maximize(4, return_result=True)
    again = engine.maximize(4, return_result=True)
    for a, b in zip(first, again):
        assert list(np.asarray(a.order)) == list(np.asarray(b.order))
    shorter = engine.maximize(2, return_result=True)
    for a, s in zip(first, shorter):
        assert list(np.asarray(a.order))[:2] == list(np.asarray(s.order))


def test_batched_rejects_mixed_families(rng):
    fl = _fl_instances(rng, 1, n=N)[0]
    gc = _build("gc", rng)
    with pytest.raises(ValueError):
        batched_maximize([fl, gc], 3)
    with pytest.raises(ValueError):
        batched_maximize(_fl_instances(rng, 2), [3, 4, 5])  # budget len mismatch


# -- eval-sparsity property: batched lazy across the servable matrix ----------


def _servable(kind, rng, n=64):
    """One instance per servable family, shaped so the gain distribution has
    a clear head (the regime lazy greedy targets): wide concept axes keep
    SetCover from exhausting inside the budget, and PSC rows get decaying
    scales — a uniformly-flat PSC is the known worst case where bound
    screens always miss (see test_lazy_equals_naive_every_class NOTE)."""
    from repro.core import FLVMI
    from repro.launch.serve import _random_function

    if kind == "fl_kernel":
        fn = _random_function("fl", n, rng)
        return FacilityLocation.from_kernel(np.asarray(fn.sim), use_kernel=True)
    if kind == "sc":
        cover = rng.integers(0, 2, size=(n, 96)).astype(np.float32)
        w = rng.uniform(0.5, 2.0, 96).astype(np.float32)
        scale = (0.8 ** np.arange(n))[rng.permutation(n)].astype(np.float32)
        return SetCover.from_cover(cover * scale[:, None], w)
    if kind == "psc":
        probs = rng.uniform(0, 0.9, size=(n, 24)).astype(np.float32)
        scale = (0.75 ** np.arange(n))[rng.permutation(n)].astype(np.float32)
        return ProbabilisticSetCover.from_probs(probs * scale[:, None])
    if kind == "flvmi":
        from repro.core import create_kernel as ck

        x = rng.normal(size=(n, 8)).astype(np.float32)
        q = rng.normal(size=(5, 8)).astype(np.float32)
        S = np.asarray(ck(x, metric="euclidean"))
        return FLVMI.build(S, np.asarray(ck(x, q, metric="euclidean")))
    return _random_function(kind, n, rng)


SERVABLE_FAMILIES = [
    "fl", "fl_kernel", "gc", "fb", "sc", "psc", "dsum", "dmin",
    "flqmi", "flvmi", "gcmi", "logdet",
]


@pytest.mark.parametrize("kind", SERVABLE_FAMILIES)
def test_batched_lazy_property_every_servable_family(kind):
    """The tentpole contract, per servable family: (a) batched LazyGreedy is
    bit-identical to sequential lazy_greedy — ids, gains AND n_evals; (b) on
    head-heavy gain distributions its eval count never exceeds batched
    NaiveGreedy's (the Minoux '78 savings, recovered in the batched path)."""
    from repro.core import maximize

    # local generator: the session `rng` fixture's draw sequence feeds the
    # data-sensitive equivalence tests in later files
    rng = np.random.default_rng(7)
    stop = kind not in ("dsum", "dmin")  # dispersion: empty-set gain is 0
    fns = [_servable(kind, rng) for _ in range(3)]
    budgets = [12, 8, 10]
    kw = dict(stopIfZeroGain=stop, stopIfNegativeGain=stop)
    lazy = batched_maximize(
        fns, budgets, optimizer="LazyGreedy", return_result=True, **kw
    )
    naive = batched_maximize(
        fns, budgets, optimizer="NaiveGreedy", return_result=True, **kw
    )
    for fn, b, rl, rn in zip(fns, budgets, lazy, naive):
        for optimizer, got in (("LazyGreedy", rl), ("NaiveGreedy", rn)):
            ref = maximize(fn, b, optimizer=optimizer, return_result=True, **kw)
            assert list(np.asarray(ref.order)) == list(np.asarray(got.order)), kind
            np.testing.assert_array_equal(
                np.asarray(ref.gains), np.asarray(got.gains)
            )
            assert int(ref.n_evals) == int(got.n_evals), (kind, optimizer)
        assert int(rl.n_evals) <= int(rn.n_evals), kind


# -- _should_stop semantics ---------------------------------------------------


def test_should_stop_truth_table():
    """Pin the stopping rule: stop_if_zero uses gj <= 0 (so it subsumes
    negatives), stop_if_negative uses gj < 0, both off never stops."""
    cases = [
        # (gain, stop_if_zero, stop_if_negative, expected)
        (1.0, True, True, False),
        (0.0, True, True, True),
        (-1.0, True, True, True),
        (0.0, False, True, False),  # zero gain allowed when only negatives stop
        (-1e-6, False, True, True),
        (0.0, True, False, True),
        (-1.0, True, False, True),  # stop_if_zero alone still stops negatives
        (0.0, False, False, False),
        (-5.0, False, False, False),
    ]
    for g, sz, sn, want in cases:
        got = bool(_should_stop(jnp.asarray(g, jnp.float32), sz, sn))
        assert got == want, (g, sz, sn)


def test_stop_flag_behaviour_on_modular_function(rng):
    """Behavioural pin: modular SetCover with positive / zero / negative
    element weights under each flag combination."""
    n = 9
    w = np.asarray([2.0, 1.5, 1.0, 0.0, 0.0, -0.5, -1.0, 3.0, 0.5], np.float32)
    fn = SetCover.from_cover(np.eye(n, dtype=np.float32), w)

    both = naive_greedy(fn, n, True, True)
    assert sorted(i for i, _ in both.as_list()) == sorted(
        int(i) for i in np.flatnonzero(w > 0)
    )

    neg_only = naive_greedy(fn, n, False, True)
    chosen = [i for i, _ in neg_only.as_list()]
    assert sorted(chosen) == sorted(int(i) for i in np.flatnonzero(w >= 0))

    never = naive_greedy(fn, n, False, False)
    assert len(never.as_list()) == n  # budget exhausted, negatives included
    np.testing.assert_allclose(float(never.value), w.sum(), rtol=1e-6)


@pytest.mark.parametrize("concave", ["sqrt", "log", "inverse"])
def test_feature_based_batch_width_bit_stable(concave, rng):
    """FeatureBased served in a wave of one and a wave of two (rows padded
    to the n-bucket) equals sequential solve bit for bit: ids, gains and
    n_evals.  Its gain's sum over features is an add tree, not a reduce
    whose order XLA picks per program."""
    from repro.core import SelectionSpec, solve
    from repro.launch.serve import SelectionServer

    specs = [
        SelectionSpec(
            FeatureBased.from_features(
                rng.uniform(0, 1, size=(n, 24)).astype(np.float32), concave=concave
            ),
            budget,
        )
        for n, budget in ((40, 6), (56, 4))
    ]
    sequential = [solve(s) for s in specs]
    one = SelectionServer().select(specs[:1])
    two = SelectionServer().select(specs)
    assert two[0].wave_size == 2
    for got, want in ((one[0], sequential[0]), (two[0], sequential[0]),
                      (two[1], sequential[1])):
        for field in ("order", "gains", "n_evals"):
            assert (
                np.asarray(getattr(got.result, field)).tobytes()
                == np.asarray(getattr(want, field)).tobytes()
            ), field


# -- the naive loop stops at the wave's largest live budget -------------------


def _sized(family, n, rng):
    x = rng.normal(size=(n, 6)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    if family == "fl":
        return FacilityLocation.from_kernel(S)
    return GraphCut.from_kernel(S, lam=0.3)


@pytest.mark.parametrize("n", [24, 5], ids=["budgets", "stops-early"])
@pytest.mark.parametrize("family", ["fl", "gc"])
def test_naive_wave_under_a_budget_bucket_equals_sequential(family, n):
    """A naive wave whose budgets differ inside one bucket (3 and 7 under
    max_budget 8, plus a budget-0 batch pad) returns, instance for
    instance, the ids, gains and n_evals of sequential naive_greedy.  At
    n 5 the budget-7 instance stops on a zero gain before its budget, so
    the wave's last steps run for nobody."""
    rng = np.random.default_rng(19)
    fns = [_sized(family, n, rng) for _ in range(2)]
    fns.append(fns[0])  # batch pad
    budgets = [3, 7, 0]
    engine = BatchedEngine(fns)
    launched = engine.enqueue(budgets, "NaiveGreedy", max_budget=8)
    assert engine.steps_run == 7
    res = launched[0]
    order, gains, evals = (np.asarray(a) for a in (res.order, res.gains, res.n_evals))
    for i, (fn, b) in enumerate(zip(fns[:2], budgets)):
        want = naive_greedy(fn, b)
        assert order[i, :b].tobytes() == np.asarray(want.order).tobytes(), b
        assert gains[i, :b].tobytes() == np.asarray(want.gains).tobytes(), b
        assert int(evals[i]) == int(want.n_evals), b
    # past each budget, the batch pad's whole row included: the initial -1 / 0
    for i, b in enumerate(budgets):
        assert (order[i, b:] == -1).all() and (gains[i, b:] == 0).all(), b
    assert int(evals[2]) == 0
    if n == 5:
        assert (np.asarray(naive_greedy(fns[1], 7).order) >= 0).sum() < 7


def test_served_naive_wave_runs_to_its_largest_budget(monkeypatch):
    """Budgets 3 and 7 bucket to 8; the wave's loop sweeps 7 times, and its
    serve.wave span and the server's counters say 7 steps of a bucket of 8."""
    import jax

    from repro.core import SelectionSpec
    from repro.core.optimizers import backends
    from repro.launch.metrics import spans
    from repro.launch.serve import SelectionServer

    sweeps = []

    class Counting:
        name = "xla"

        def full_sweep(self, fn, state):
            jax.debug.callback(lambda: sweeps.append(1))  # unbatched: once a step
            return fn.gains(state)

    monkeypatch.setitem(backends._REGISTRY, FacilityLocation, lambda fn: Counting())
    jax.clear_caches()  # trace the wave afresh, through the counting backend
    try:
        rng = np.random.default_rng(7)
        server = SelectionServer()
        out = server.select(
            [SelectionSpec(_sized("fl", 20, rng), b) for b in (3, 7)]
        )
    finally:
        jax.clear_caches()  # no later test reuses the counting program
    assert [len(r.selection) for r in out] == [3, 7]
    assert out[0].wave_size == 2
    assert len(sweeps) == 7
    (wave,) = [s for s in spans() if s.name == "serve.wave"][-1:]
    assert (wave.attrs["steps_run"], wave.attrs["steps_bucket"]) == (7, 8)
    c = server.metrics.snapshot()["counters"]
    assert (c["steps_run"], c["steps_bucket"]) == (7, 8)

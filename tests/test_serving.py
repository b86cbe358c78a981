"""Distributed batched selection serving (launch/serve.py + coalesce.py +
the sharded batched engine in optimizers/distributed.py).

The load-bearing contract: every serving layer — padding, wave coalescing,
budget bucketing, the vmap x shard_map engine — returns selections
BIT-IDENTICAL to a Python loop of single ``maximize`` calls (ids, gains,
and, where the sweep width is unchanged, ``n_evals``).  A subprocess test
pins this on a real 4-device (2x2 batch x data) host-platform mesh.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FacilityLocation,
    FeatureBased,
    GraphCut,
    SelectionSpec,
    batched_maximize,
    create_kernel,
    maximize,
    naive_greedy,
)
from repro.launch.coalesce import (
    SelectionRequest,
    bucket_size,
    coalesce,
    next_pow2,
    pad_function,
)
from repro.launch.serve import SelectionServer, _random_requests


def _build(kind, rng, n):
    from repro.core import (
        GCMI,
        FLQMI,
        FLVMI,
        DisparityMin,
        DisparitySum,
        LogDet,
        ProbabilisticSetCover,
        SetCover,
    )

    x = rng.normal(size=(n, 8)).astype(np.float32)
    S = np.asarray(create_kernel(x, metric="euclidean"))
    if kind == "fl":
        return FacilityLocation.from_kernel(S)
    if kind == "fl_kernel":
        return FacilityLocation.from_kernel(S, use_kernel=True)
    if kind == "gc":
        return GraphCut.from_kernel(S, lam=0.3)
    if kind == "fb":
        return FeatureBased.from_features(
            rng.uniform(0, 1, size=(n, 12)).astype(np.float32), concave="sqrt"
        )
    if kind == "sc":
        return SetCover.from_cover(
            rng.integers(0, 2, size=(n, 12)).astype(np.float32),
            rng.uniform(0.5, 2.0, 12).astype(np.float32),
        )
    if kind == "sc_kernel":
        return SetCover.from_cover(
            rng.integers(0, 2, size=(n, 12)).astype(np.float32), use_kernel=True
        )
    if kind == "psc":
        return ProbabilisticSetCover.from_probs(
            rng.uniform(0, 0.9, size=(n, 12)).astype(np.float32)
        )
    if kind == "dsum":
        return DisparitySum.from_distance(1.0 - S)
    if kind == "dmin":
        return DisparityMin.from_distance(1.0 - S)
    if kind == "flqmi":
        q = rng.normal(size=(5, 8)).astype(np.float32)
        return FLQMI.build(np.asarray(create_kernel(q, x, metric="euclidean")))
    if kind == "flvmi":
        q = rng.normal(size=(5, 8)).astype(np.float32)
        return FLVMI.build(S, np.asarray(create_kernel(x, q, metric="euclidean")))
    if kind == "gcmi":
        q = rng.normal(size=(5, 8)).astype(np.float32)
        return GCMI.build(
            np.asarray(create_kernel(x, q, metric="euclidean")), lam=0.4
        )
    if kind == "logdet":
        return LogDet.from_kernel(
            S + 0.5 * np.eye(n, dtype=np.float32), max_select=10
        )
    raise KeyError(kind)


# the empty-set gain is 0 for the dispersion functions, so their requests run
# with stopping disabled (see core/functions/disparity.py)
_NOSTOP = {"dsum", "dmin"}


def _stop_args(kind):
    return (False, False) if kind in _NOSTOP else (True, True)


# -- coalescing ---------------------------------------------------------------


def test_bucket_size():
    assert next_pow2(1) == 1 and next_pow2(5) == 8 and next_pow2(64) == 64
    assert bucket_size(33) == 64
    assert bucket_size(3, multiple=4) == 4
    assert bucket_size(5, multiple=4) == 8  # pow2 already divisible
    assert bucket_size(2, multiple=3) == 3  # non-pow2 mesh axis
    # the ladder: eight steps per octave, at least one lane width
    assert bucket_size(5_000) == 5_120  # a CIFAR-10 class: 10 tiles of 512
    assert bucket_size(50_000) == 53_248  # the CIFAR-10 pool: 104 tiles
    assert bucket_size(1_100) == 1_152
    assert bucket_size(300) == 384


@pytest.mark.parametrize("multiple", [1, 2, 3, 4, 8])
def test_bucket_size_ladder_properties(multiple):
    """Over a range of n: a bucket never exceeds the power-of-two bucket
    (rounded to the multiple), is a multiple of ``multiple``, pads under
    1/8 from n = 1,024 up, takes at most eight values per octave, never
    falls as n grows, and from n = 4,096 up is a whole number of the
    Pallas sweeps' 512-candidate tiles (for a power-of-two multiple)."""
    octaves = {}
    prev = 0
    for n in [*range(1, 20_000), *range(20_000, 140_000, 37)]:
        b = bucket_size(n, multiple)
        assert n <= b <= -(-next_pow2(n) // multiple) * multiple, n
        assert b % multiple == 0, n
        if n >= 1_024 and multiple == 1:
            assert (b - n) / n < 1 / 8, n
        if n >= 4_096 and 512 % multiple == 0:
            assert b % 512 == 0, n
        assert b >= prev, n
        prev = b
        octaves.setdefault((n - 1).bit_length(), set()).add(b)  # n in (2^k, 2^(k+1)]
    assert max(len(bs) for bs in octaves.values()) <= 8, octaves


@pytest.mark.parametrize(
    "kind",
    ["fl", "gc", "fb", "sc", "psc", "dsum", "dmin", "flqmi", "flvmi", "gcmi",
     "logdet"],
)
def test_pad_function_preserves_selection_exactly(kind, rng):
    """Zero-padding the candidate axis + a valid mask is bit-invisible."""
    fn = _build(kind, rng, 23)
    stop_zero, stop_neg = _stop_args(kind)
    padded = pad_function(fn, 32)
    assert padded.n == 32
    valid = np.zeros((1, 32), bool)
    valid[:, :23] = True
    got = batched_maximize(
        [padded],
        6,
        valid=jnp.asarray(valid),
        return_result=True,
        stopIfZeroGain=stop_zero,
        stopIfNegativeGain=stop_neg,
    )[0]
    ref = naive_greedy(fn, 6, stop_zero, stop_neg)
    assert list(np.asarray(ref.order)) == list(np.asarray(got.order))
    np.testing.assert_array_equal(np.asarray(ref.gains), np.asarray(got.gains))


@pytest.mark.parametrize("kind", ["fl", "flmf", "gc"])
def test_served_off_power_of_two_bucket_bit_identical(kind):
    """A request whose n lands in a bucket that is not a power of two (1,100
    candidates padded to 1,152) is served bit for bit as unpadded sequential
    solve: ids, gains and n_evals.  FacilityLocation pads columns only,
    FacilityLocationMF pads its feature source's candidate rows, and
    GraphCut pads its kernel square."""
    from repro.core import FacilityLocationMF, solve

    n, budget = 1_100, 8
    rng = np.random.default_rng(17)
    x = rng.normal(size=(n, 8)).astype(np.float32)
    if kind == "flmf":
        fn = FacilityLocationMF.from_features(x, metric="euclidean")
    else:
        S = np.asarray(create_kernel(x, metric="euclidean"))
        fn = (
            FacilityLocation.from_kernel(S)
            if kind == "fl"
            else GraphCut.from_kernel(S, lam=0.3)
        )
    spec = SelectionSpec(fn, budget)
    (served,) = SelectionServer().select([spec])
    assert served.n_bucket == bucket_size(n) == 1_152
    want = solve(spec)
    for field in ("order", "gains", "n_evals"):
        assert (
            np.asarray(getattr(served.result, field)).tobytes()
            == np.asarray(getattr(want, field)).tobytes()
        ), field


def test_coalesce_groups_and_pads(rng):
    """Mixed families/sizes coalesce into per-(family, shape) waves; the
    batch pads carry budget 0 and demux drops them."""
    reqs = [
        SelectionRequest(rid="a", spec=SelectionSpec(_build("fl", rng, 24), 4)),
        SelectionRequest(rid="b", spec=SelectionSpec(_build("fl", rng, 24), 7)),
        SelectionRequest(rid="c", spec=SelectionSpec(_build("gc", rng, 24), 3)),
        SelectionRequest(rid="d", spec=SelectionSpec(_build("fl", rng, 40), 4)),
    ]
    waves = coalesce(reqs, n_multiple=4, b_multiple=4)
    by_rids = {tuple(sorted(r.rid for r in w.requests)): w for w in waves}
    assert set(by_rids) == {("a", "b"), ("c",), ("d",)}

    w_ab = by_rids[("a", "b")]
    assert w_ab.n_bucket == 32 and w_ab.batch_size == 4
    assert w_ab.budgets == [4, 7, 0, 0]  # two batch pads, budget 0
    assert w_ab.max_budget == 8  # pow2 bucket of 7
    assert w_ab.n_padded_slots == 2
    assert w_ab.valid.shape == (4, 32) and w_ab.valid[:, :24].all()
    assert not w_ab.valid[:, 24:].any()
    assert by_rids[("d",)].n_bucket == 64

    demuxed = w_ab.demux(["r0", "r1", "r2", "r3"])
    assert demuxed == {"a": "r0", "b": "r1"}


def test_coalesce_splits_at_max_wave(rng):
    fn = _build("fl", rng, 16)
    reqs = [
        SelectionRequest(rid=i, spec=SelectionSpec(fn, 3)) for i in range(5)
    ]
    waves = coalesce(reqs, max_wave=2)
    assert sorted(len(w.requests) for w in waves) == [1, 2, 2]


def _unsupported_family(rng):
    """DisparityMinSum deliberately registers no padder/ShardRule: its gains
    reduce over ALL rows of the distance matrix, so zero row-padding would
    shift them by ulps (see core/functions/disparity.py)."""
    from repro.core import DisparityMinSum

    d = rng.uniform(0, 2, size=(8, 8)).astype(np.float32)
    np.fill_diagonal(d, 0.0)
    return DisparityMinSum.from_distance(d)


def test_coalesce_rejects_unknown_family(rng):
    fn = _unsupported_family(rng)
    with pytest.raises(NotImplementedError, match="register_padder"):
        coalesce(
            [SelectionRequest(rid=0, spec=SelectionSpec(fn, 2))], n_multiple=16
        )


def test_server_rejects_unknown_family_with_clear_error(rng):
    """An unsupported family submitted to the SelectionServer must surface a
    NotImplementedError naming register_padder — not an opaque shape error
    from deep inside the engine — AT SUBMIT TIME, and must not poison
    co-pending valid requests."""
    server = SelectionServer()
    fn_ok = _build("fl", rng, 16)
    rid_ok = server.submit(fn_ok, 3)
    with pytest.raises(NotImplementedError, match="register_padder"):
        server.submit(_unsupported_family(rng), 3)
    out = server.flush()  # the valid request is unaffected by the rejection
    assert out[rid_ok].selection == maximize(fn_ok, 3)


def test_shard_rule_error_names_register_shard_rule(rng):
    """The mesh path's unknown-family error must name register_shard_rule."""
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    fn = _unsupported_family(rng)
    with pytest.raises(NotImplementedError, match="register_shard_rule"):
        batched_maximize([fn], 2, mesh=mesh)


# -- the server, single device ------------------------------------------------


def test_server_bit_identical_to_maximize_loop(rng):
    """A mixed FL/GC/FB workload with heterogeneous n and budgets: every
    served selection equals its single `maximize` call, ids AND gains."""
    server = SelectionServer()
    requests = _random_requests(12, seed=3)
    responses = server.select(requests)
    assert len(responses) == len(requests)
    for (fn, budget), resp in zip(requests, responses):
        ref = maximize(fn, budget)
        assert [i for i, _ in ref] == [i for i, _ in resp.selection]
        assert [g for _, g in ref] == [g for _, g in resp.selection]
    s = server.stats.summary()
    assert s["requests"] == 12 and s["waves"] >= 3 and s["total_s"] > 0


def test_server_coalesces_same_shape_requests(rng):
    """Same-family same-bucket requests ride one wave (the serving win)."""
    server = SelectionServer(max_wave=8)
    fns = [_build("fl", rng, 24) for _ in range(6)]
    responses = server.select([(f, 4) for f in fns])
    assert server.stats.waves == 1
    assert all(r.wave_size == 6 for r in responses)
    for f, r in zip(fns, responses):
        assert r.selection == maximize(f, 4)


def test_server_lazy_greedy_single_device(rng):
    server = SelectionServer()
    fn = _build("fl", rng, 24)
    rid = server.submit(fn, 5, optimizer="LazyGreedy")
    out = server.flush()
    assert out[rid].selection == maximize(fn, 5, optimizer="LazyGreedy")


def test_server_screen_k_reaches_engine(rng):
    """A non-default screen_k must be honored (n_evals proves it ran)."""
    server = SelectionServer()
    fn = _build("fl", rng, 32)
    rid = server.submit(fn, 5, optimizer="LazyGreedy", screen_k=3)
    out = server.flush()
    ref = maximize(fn, 5, optimizer="LazyGreedy", screen_k=3, return_result=True)
    assert out[rid].selection == [
        (int(i), float(g)) for i, g in zip(ref.order, ref.gains) if i >= 0
    ]
    assert int(out[rid].result.n_evals) == int(ref.n_evals)


def test_server_rejects_unknown_submit_options(rng):
    server = SelectionServer()
    with pytest.raises(TypeError, match="unknown option"):
        server.submit(_build("fl", rng, 16), 3, stopIfZeroGains=False)  # typo


def test_server_never_drops_submitted_requests(rng):
    """select() must not swallow responses to requests enqueued earlier via
    submit(): they ride the same flush and surface on the next flush()."""
    server = SelectionServer()
    fn_a, fn_b = _build("fl", rng, 16), _build("fl", rng, 24)
    rid_a = server.submit(fn_a, 3)
    resp_b = server.select([(fn_b, 4)])
    assert resp_b[0].selection == maximize(fn_b, 4)
    out = server.flush()  # nothing pending, but rid_a's answer is held here
    assert out[rid_a].selection == maximize(fn_a, 3)


def test_server_stop_flags_ride_the_wave_key(rng):
    """stopIfZeroGain/stopIfNegativeGain are part of the wave key and reach
    the engine: the same function served under different flags matches the
    corresponding single `maximize` calls (including the degenerate
    exhausted-budget tail when stopping is disabled)."""
    fn = _build("fl", rng, 8)
    server = SelectionServer()
    rid_stop = server.submit(fn, 8)
    rid_nostop = server.submit(fn, 8, stopIfZeroGain=False, stopIfNegativeGain=False)
    out = server.flush()
    assert server.stats.waves == 2  # different flags -> different waves
    assert out[rid_stop].selection == maximize(fn, 8)
    assert out[rid_nostop].selection == maximize(
        fn, 8, stopIfZeroGain=False, stopIfNegativeGain=False
    )


def test_server_disparity_stop_default():
    """Regression for the Disparity* serving footgun: the empty-set gain is
    0, so the library-wide stopIfZeroGain=True default used to silently
    serve EMPTY selections for dsum/dmin requests.  submit() now defaults
    stopIfZeroGain=False for those families — and an explicit flag wins."""
    rng = np.random.default_rng(13)  # local: keep the session rng sequence
    server = SelectionServer()
    fns = {k: _build(k, rng, 24) for k in ("dsum", "dmin")}
    rids = {k: server.submit(f, 5) for k, f in fns.items()}
    explicit = server.submit(fns["dsum"], 5, stopIfZeroGain=True)
    out = server.flush()
    for k, f in fns.items():
        resp = out[rids[k]]
        assert resp.selection, k  # non-empty: the footgun is closed
        ref = maximize(f, 5, stopIfZeroGain=False)
        assert resp.selection == ref, k
    assert out[explicit].selection == []  # manual flag still wins


# -- the sharded engine, in-process (1,1) mesh --------------------------------


@pytest.mark.parametrize("kind", ["fl", "fl_kernel", "gc", "fb"])
def test_sharded_engine_unit_mesh_bit_identical(kind, rng):
    """mesh=(1,1): the full shard_map+vmap program, collectives degenerate.
    Ids, gains, n_evals and value all equal the sequential loop."""
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    fns = [_build(kind, rng, 32) for _ in range(3)]
    budgets = [5, 3, 6]
    res = batched_maximize(fns, budgets, mesh=mesh, return_result=True)
    for fn, b, r in zip(fns, budgets, res):
        ref = naive_greedy(fn, b)
        assert list(np.asarray(ref.order)) == list(np.asarray(r.order))
        np.testing.assert_array_equal(np.asarray(ref.gains), np.asarray(r.gains))
        assert int(ref.n_evals) == int(r.n_evals)
        assert float(ref.value) == float(r.value)


@pytest.mark.parametrize(
    "kind", ["sc", "sc_kernel", "psc", "dsum", "dmin", "flqmi", "flvmi",
             "gcmi", "logdet"]
)
def test_sharded_engine_unit_mesh_new_families(kind, rng):
    """The serving-breadth families (SetCover family, Disparity, MI
    combinators, LogDet) through the full shard_map+vmap program on a (1,1)
    mesh: ids, gains, n_evals and value all equal the sequential loop."""
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    stop_zero, stop_neg = _stop_args(kind)
    fns = [_build(kind, rng, 32) for _ in range(3)]
    budgets = [5, 3, 6]
    res = batched_maximize(
        fns,
        budgets,
        mesh=mesh,
        return_result=True,
        stopIfZeroGain=stop_zero,
        stopIfNegativeGain=stop_neg,
    )
    for fn, b, r in zip(fns, budgets, res):
        ref = naive_greedy(fn, b, stop_zero, stop_neg)
        assert list(np.asarray(ref.order)) == list(np.asarray(r.order))
        np.testing.assert_array_equal(np.asarray(ref.gains), np.asarray(r.gains))
        assert int(ref.n_evals) == int(r.n_evals)
        assert float(ref.value) == float(r.value)


@pytest.mark.parametrize(
    "kind", ["fl", "fl_kernel", "gc", "fb", "sc", "psc", "dsum", "dmin",
             "flqmi", "flvmi", "gcmi", "logdet"]
)
def test_sharded_engine_unit_mesh_lazy_bit_identical(kind):
    """LazyGreedy on a (1,1) mesh: the full bucketed sharded-lazy program
    (sorted-prefix merge + gathered partial sweeps + level conds, collectives
    degenerate) is bit-identical to sequential lazy_greedy — ids, gains,
    n_evals, value — for every servable family."""
    from repro.core import lazy_greedy

    rng = np.random.default_rng(13)  # local: keep the session rng sequence
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    stop_zero, stop_neg = _stop_args(kind)
    fns = [_build(kind, rng, 32) for _ in range(3)]
    budgets = [5, 3, 6]
    res = batched_maximize(
        fns,
        budgets,
        mesh=mesh,
        optimizer="LazyGreedy",
        return_result=True,
        screen_k=6,
        stopIfZeroGain=stop_zero,
        stopIfNegativeGain=stop_neg,
    )
    for fn, b, r in zip(fns, budgets, res):
        ref = lazy_greedy(fn, b, 6, stop_zero, stop_neg)
        assert list(np.asarray(ref.order)) == list(np.asarray(r.order))
        np.testing.assert_array_equal(np.asarray(ref.gains), np.asarray(r.gains))
        assert int(ref.n_evals) == int(r.n_evals)
        assert float(ref.value) == float(r.value)


def test_server_lazy_on_mesh_bit_identical():
    """optimizer="LazyGreedy" through the whole serving stack on a mesh
    (formerly the pinned NaiveGreedy-only error path).  Padded waves keep
    ids/gains bit-identical; the n=32 requests sit at their bucket, so their
    n_evals compare exactly too."""
    from repro.core import lazy_greedy

    rng = np.random.default_rng(13)  # local: keep the session rng sequence
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    server = SelectionServer(mesh=mesh)
    fns = [_build("fl", rng, 32) for _ in range(3)] + [_build("gc", rng, 24)]
    rids = [server.submit(fn, 5, optimizer="LazyGreedy") for fn in fns]
    out = server.flush()
    for fn, rid in zip(fns, rids):
        ref = maximize(fn, 5, optimizer="LazyGreedy", return_result=True)
        assert out[rid].selection == [
            (int(i), float(g)) for i, g in zip(ref.order, ref.gains) if i >= 0
        ]
        if fn.n == 32:
            assert int(out[rid].result.n_evals) == int(ref.n_evals)


def test_server_new_families_bit_identical(rng):
    """Mixed SC / PSC / FLQMI / GCMI / LogDet workload through the server:
    every served selection equals its single `maximize` call."""
    from repro.launch.serve import _random_requests as rr

    server = SelectionServer()
    requests = rr(10, seed=11, families=("sc", "psc", "flqmi", "gcmi", "logdet"))
    responses = server.select(requests)
    for (fn, budget), resp in zip(requests, responses):
        ref = maximize(fn, budget)
        assert [i for i, _ in ref] == [i for i, _ in resp.selection]
        assert [g for _, g in ref] == [g for _, g in resp.selection]


def test_server_disparity_bit_identical(rng):
    """Disparity requests need stopIfZeroGain=False (empty-set gain is 0);
    with it they serve bit-identically, including coalesced same-shape
    waves."""
    server = SelectionServer()
    fns = [_build(k, rng, 24) for k in ("dsum", "dsum", "dmin")]
    rids = [
        server.submit(f, 5, stopIfZeroGain=False, stopIfNegativeGain=False)
        for f in fns
    ]
    out = server.flush()
    for f, rid in zip(fns, rids):
        ref = maximize(f, 5, stopIfZeroGain=False, stopIfNegativeGain=False)
        assert out[rid].selection == ref


def test_sharded_engine_rejects_disparity_use_kernel(rng):
    """Disparity*(use_kernel=True) keeps the GraphCut policy: the stateless
    Pallas sweep cannot be reconciled with the memoized shard rule
    bit-identically, so the mesh path must refuse loudly."""
    from repro.core import DisparityMin, DisparitySum

    d = np.asarray(_build("dsum", rng, 32).dist)
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    for cls in (DisparitySum, DisparityMin):
        fn = cls.from_distance(d, use_kernel=True)
        with pytest.raises(ValueError, match="use_kernel"):
            batched_maximize([fn], 3, mesh=mesh)
        # single-device serving of the same instance stays bit-identical
        r = batched_maximize(
            [fn], 3, return_result=True,
            stopIfZeroGain=False, stopIfNegativeGain=False,
        )[0]
        ref = naive_greedy(fn, 3, False, False)
        assert list(np.asarray(ref.order)) == list(np.asarray(r.order))


def test_sharded_engine_rejects_bad_mesh_axes(rng):
    fns = [_build("fl", rng, 32) for _ in range(3)]
    with pytest.raises(ValueError, match="no axis"):
        batched_maximize(fns, 3, mesh=jax.make_mesh((1, 1), ("x", "data")))


def test_sharded_engine_rejects_gc_use_kernel(rng):
    """GraphCut(use_kernel=True) cannot keep the bit-identical contract on a
    mesh (Pallas stateless vs memoized sweep); it must refuse loudly."""
    fns = [_build("gc", rng, 32)]
    fns_k = [
        GraphCut.from_kernel(np.asarray(f.sim_ground), lam=0.3, use_kernel=True)
        for f in fns
    ]
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    with pytest.raises(ValueError, match="use_kernel"):
        batched_maximize(fns_k, 3, mesh=mesh)
    # single-device serving of the same instance is fine (and bit-identical)
    r = batched_maximize(fns_k, 3, return_result=True)[0]
    ref = naive_greedy(fns_k[0], 3)
    assert list(np.asarray(ref.order)) == list(np.asarray(r.order))
    np.testing.assert_array_equal(np.asarray(ref.gains), np.asarray(r.gains))


def test_server_sharded_unit_mesh_bit_identical(rng):
    """The whole serving stack through the sharded engine on a (1,1) mesh."""
    mesh = jax.make_mesh((1, 1), ("batch", "data"))
    server = SelectionServer(mesh=mesh)
    requests = _random_requests(9, seed=5)
    responses = server.select(requests)
    for (fn, budget), resp in zip(requests, responses):
        ref = maximize(fn, budget)
        assert [i for i, _ in ref] == [i for i, _ in resp.selection]
        assert [g for _, g in ref] == [g for _, g in resp.selection]


# -- the real thing: 4 host devices, 2x2 batch x data mesh --------------------

_MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core import (FacilityLocation, GraphCut, FeatureBased,
                            create_kernel, naive_greedy, lazy_greedy,
                            batched_maximize, maximize)
    from repro.launch.serve import SelectionServer, _random_requests

    rng = np.random.default_rng(0)

    def build(kind, n):
        x = rng.normal(size=(n, 8)).astype(np.float32)
        S = np.asarray(create_kernel(x, metric="euclidean"))
        if kind == "fl": return FacilityLocation.from_kernel(S)
        if kind == "gc": return GraphCut.from_kernel(S, lam=0.3)
        return FeatureBased.from_features(
            rng.uniform(0, 1, (n, 12)).astype(np.float32))

    mesh = jax.make_mesh((2, 2), ("batch", "data"))
    assert len(jax.devices()) == 4

    # engine-level: ids, gains, n_evals, value all bit-identical, for both
    # the naive partition sweep and the bucketed sharded-lazy engine
    budgets = [6, 3, 5, 4]
    for kind in ["fl", "gc", "fb"]:
        fns = [build(kind, 32) for _ in range(4)]
        res = batched_maximize(fns, budgets, mesh=mesh, return_result=True)
        for fn, b, r in zip(fns, budgets, res):
            ref = naive_greedy(fn, b)
            assert list(np.asarray(ref.order)) == list(np.asarray(r.order)), kind
            assert np.array_equal(np.asarray(ref.gains), np.asarray(r.gains)), kind
            assert int(ref.n_evals) == int(r.n_evals), kind
            assert float(ref.value) == float(r.value), kind
    for kind in ["fl", "gc"]:
        fns = [build(kind, 32) for _ in range(4)]
        res = batched_maximize(fns, budgets, mesh=mesh, optimizer="LazyGreedy",
                               return_result=True, screen_k=6)
        for fn, b, r in zip(fns, budgets, res):
            ref = lazy_greedy(fn, b, 6)
            assert list(np.asarray(ref.order)) == list(np.asarray(r.order)), kind
            assert np.array_equal(np.asarray(ref.gains), np.asarray(r.gains)), kind
            assert int(ref.n_evals) == int(r.n_evals), kind

    # server-level: mixed workload, padding + batch pads on the mesh
    server = SelectionServer(mesh=mesh)
    requests = _random_requests(10, seed=1)
    for (fn, budget), resp in zip(requests, server.select(requests)):
        ref = maximize(fn, budget)
        assert [i for i, _ in ref] == [i for i, _ in resp.selection]
        assert [g for _, g in ref] == [g for _, g in resp.selection]
    assert server.stats.requests == 10

    # server-level LazyGreedy on the mesh (the former pinned error path)
    fns_lazy = [build("fl", 32) for _ in range(3)]
    rids = [server.submit(fn, b, optimizer="LazyGreedy")
            for fn, b in zip(fns_lazy, [4, 6, 5])]
    out = server.flush()
    for fn, b, rid in zip(fns_lazy, [4, 6, 5], rids):
        ref = maximize(fn, b, optimizer="LazyGreedy", return_result=True)
        assert out[rid].selection == [
            (int(i), float(g)) for i, g in zip(ref.order, ref.gains) if i >= 0]
        assert int(out[rid].result.n_evals) == int(ref.n_evals)
    print("SHARDED_SERVE_OK")
    """
)


def test_sharded_serving_four_devices():
    """Real 4-device (2x2 batch x data) subprocess run: the sharded batched
    engine AND the server return bit-identical results to sequential
    single-device maximize — ids, gains, n_evals — with live collectives."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        # JAX_PLATFORMS=cpu skips backend probing, which otherwise stalls a
        # clean-env subprocess for minutes before the first compile
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "SHARDED_SERVE_OK" in r.stdout, r.stdout + r.stderr


_MULTIDEV_BREADTH_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np, jax
    from repro.core import (SetCover, ProbabilisticSetCover, DisparitySum,
                            DisparityMin, FLQMI, FLVMI, GCMI, LogDet,
                            create_kernel, naive_greedy, batched_maximize,
                            maximize)
    from repro.launch.serve import SelectionServer, _random_requests

    rng = np.random.default_rng(0)

    def build(kind, n=32):
        x = rng.normal(size=(n, 8)).astype(np.float32)
        S = np.asarray(create_kernel(x, metric="euclidean"))
        if kind == "sc":
            return SetCover.from_cover(
                rng.integers(0, 2, size=(n, 12)).astype(np.float32))
        if kind == "sc_kernel":
            return SetCover.from_cover(
                rng.integers(0, 2, size=(n, 12)).astype(np.float32),
                use_kernel=True)
        if kind == "psc":
            return ProbabilisticSetCover.from_probs(
                rng.uniform(0, 0.9, size=(n, 12)).astype(np.float32))
        if kind == "dsum": return DisparitySum.from_distance(1.0 - S)
        if kind == "dmin": return DisparityMin.from_distance(1.0 - S)
        if kind == "flqmi":
            q = rng.normal(size=(5, 8)).astype(np.float32)
            return FLQMI.build(np.asarray(create_kernel(q, x, "euclidean")))
        if kind == "flvmi":
            q = rng.normal(size=(5, 8)).astype(np.float32)
            return FLVMI.build(S, np.asarray(create_kernel(x, q, "euclidean")))
        if kind == "gcmi":
            q = rng.normal(size=(5, 8)).astype(np.float32)
            return GCMI.build(
                np.asarray(create_kernel(x, q, "euclidean")), lam=0.4)
        return LogDet.from_kernel(
            S + 0.5 * np.eye(n, dtype=np.float32), max_select=10)

    mesh = jax.make_mesh((2, 2), ("batch", "data"))
    assert len(jax.devices()) == 4
    budgets = [6, 3, 5, 4]

    for kind in ["sc", "sc_kernel", "psc", "flqmi", "flvmi", "gcmi", "logdet"]:
        fns = [build(kind) for _ in range(4)]
        res = batched_maximize(fns, budgets, mesh=mesh, return_result=True)
        for fn, b, r in zip(fns, budgets, res):
            ref = naive_greedy(fn, b)
            assert list(np.asarray(ref.order)) == list(np.asarray(r.order)), kind
            assert np.array_equal(np.asarray(ref.gains), np.asarray(r.gains)), kind
            assert int(ref.n_evals) == int(r.n_evals), kind
            assert float(ref.value) == float(r.value), kind

    for kind in ["dsum", "dmin"]:  # empty-set gain is 0: stopping disabled
        fns = [build(kind) for _ in range(4)]
        res = batched_maximize(fns, budgets, mesh=mesh, return_result=True,
                               stopIfZeroGain=False, stopIfNegativeGain=False)
        for fn, b, r in zip(fns, budgets, res):
            ref = naive_greedy(fn, b, False, False)
            assert list(np.asarray(ref.order)) == list(np.asarray(r.order)), kind
            assert np.array_equal(np.asarray(ref.gains), np.asarray(r.gains)), kind

    server = SelectionServer(mesh=mesh)
    requests = _random_requests(
        12, seed=2, families=("sc", "psc", "flqmi", "gcmi", "logdet", "fl"))
    for (fn, budget), resp in zip(requests, server.select(requests)):
        ref = maximize(fn, budget)
        assert [i for i, _ in ref] == [i for i, _ in resp.selection]
        assert [g for _, g in ref] == [g for _, g in resp.selection]
    print("SHARDED_BREADTH_OK")
    """
)


@pytest.mark.slow
def test_sharded_serving_breadth_four_devices():
    """The full function x backend matrix on a real 2x2 mesh: SetCover family
    (incl. the per-shard Pallas sweep), Disparity, the FL/GC MI combinators
    and LogDet all serve bit-identically with live collectives.  @slow: ~9
    compiled programs; the fast tier covers the same families on the (1,1)
    in-process mesh."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_BREADTH_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo",
    )
    assert "SHARDED_BREADTH_OK" in r.stdout, r.stdout + r.stderr


def test_server_rejects_unknown_optimizer_at_submit():
    """A typo'd optimizer must fail at submit() — surfacing from the engine
    mid-flush would abort the flush after the pending queue was cleared,
    dropping every co-pending request."""
    rng = np.random.default_rng(13)  # local: keep the session rng sequence
    server = SelectionServer()
    fn = _build("fl", rng, 16)
    rid_ok = server.submit(fn, 3)
    with pytest.raises(ValueError, match="unknown optimizer"):
        server.submit(fn, 3, optimizer="lazygreedy")
    out = server.flush()  # the valid request is unaffected by the rejection
    assert out[rid_ok].selection == maximize(fn, 3)


# ---------------------------------------------------------------------------
# Per-group queues, failure discipline, backpressure, truthful latency.
# ---------------------------------------------------------------------------


def test_group_key_is_the_wave_identity(rng):
    """group_key (computed shape-only at submit time) partitions requests
    exactly as wave coalescing does: same family/bucket/optimizer share a
    key, different shapes or flags split — and budgets/deadlines never key."""
    from repro.launch.coalesce import group_key

    def req(fn, budget, **kw):
        return SelectionRequest(rid=0, spec=SelectionSpec(fn, budget, **kw))

    a = req(_build("fl", rng, 24), 3)
    b = req(_build("fl", rng, 24), 7, deadline_s=0.5)  # budget/deadline: no split
    c = req(_build("fl", rng, 48), 3)  # different bucket
    d = req(_build("gc", rng, 24), 3)  # different family
    e = req(_build("fl", rng, 24), 3, stopIfZeroGain=False)  # different flags
    f = req(_build("fl", rng, 24), 3, optimizer="LazyGreedy")  # different opt
    keys = [group_key(r) for r in (a, b, c, d, e, f)]
    assert keys[0] == keys[1]
    assert len({keys[0], keys[2], keys[3], keys[4], keys[5]}) == 5


def test_server_queues_per_group_and_group_states(rng):
    """Pending requests live in per-group queues; group_states() exposes the
    scheduling view (depth / oldest arrival / earliest deadline)."""
    server = SelectionServer()
    server.submit_spec(SelectionSpec(_build("fl", rng, 24), 3))
    server.submit_spec(SelectionSpec(_build("fl", rng, 24), 5, deadline_s=9.0))
    server.submit_spec(SelectionSpec(_build("gc", rng, 24), 3))
    states = server.group_states()
    assert sorted(depth for _, depth, _, _ in states) == [1, 2]
    assert server.pending_count == 3
    fl_state = next(s for s in states if s[1] == 2)
    assert fl_state[3] is not None  # the deadline_s=9.0 member surfaces
    gc_state = next(s for s in states if s[1] == 1)
    assert gc_state[3] is None
    out = server.flush()
    assert len(out) == 3 and server.pending_count == 0


def test_flush_error_loses_no_requests_or_responses(rng):
    """The poisoned-wave pin (the mid-flush drop bug): wave 2 of 3 fails —
    wave 1's computed responses are re-held, the failed wave AND the
    never-dispatched wave are re-enqueued, and the next flush answers
    everyone.  Zero requests, zero computed responses lost."""
    from repro.launch.serve import FlushError

    class Boom(RuntimeError):
        pass

    class PoisonServer(SelectionServer):
        armed = True

        def _dispatch(self, wave):
            if self.armed and wave.n_bucket == 64:
                raise Boom("engine on fire")
            return super()._dispatch(wave)

    server = PoisonServer()
    fn_good, fn_poison, fn_late = (
        _build("fl", rng, 32),
        _build("fl", rng, 64),
        _build("fl", rng, 16),
    )
    rid_good = server.submit_spec(SelectionSpec(fn_good, 4))
    rid_poison = server.submit_spec(SelectionSpec(fn_poison, 4))
    rid_late = server.submit_spec(SelectionSpec(fn_late, 3))

    with pytest.raises(FlushError) as excinfo:
        server.flush()
    e = excinfo.value
    assert isinstance(e.__cause__, Boom)
    assert e.failed_rids == [rid_poison]
    assert e.undispatched_rids == [rid_late]
    assert set(e.completed) == {rid_good}
    # unserved requests are back in their queues, arrival stamps intact
    assert server.pending_count == 2
    assert server.metrics.counters["flush_errors"] == 1
    assert server.metrics.counters["requeued"] == 2

    server.armed = False  # the engine recovers; nothing was lost
    out = server.flush()
    assert set(out) == {rid_good, rid_poison, rid_late}
    for fn, budget, rid in [(fn_good, 4, rid_good), (fn_poison, 4, rid_poison),
                            (fn_late, 3, rid_late)]:
        assert out[rid].selection == maximize(fn, budget)


def test_flush_error_cancel_escape_hatch(rng):
    """After a FlushError names a poisoned request, cancel(rid) removes it
    from its queue so the retry serves the survivors."""
    from repro.launch.serve import FlushError

    class PoisonServer(SelectionServer):
        def _dispatch(self, wave):
            if wave.n_bucket == 64:
                raise RuntimeError("this request always fails")
            return super()._dispatch(wave)

    server = PoisonServer()
    rid_ok = server.submit_spec(SelectionSpec(_build("fl", rng, 32), 4))
    rid_bad = server.submit_spec(SelectionSpec(_build("fl", rng, 64), 4))
    with pytest.raises(FlushError):
        server.flush()
    assert server.cancel(rid_bad)
    assert not server.cancel(rid_bad)  # already gone
    out = server.flush()  # survivors (and the held wave-1 response) surface
    assert set(out) == {rid_ok}


def test_latency_reports_queue_time_truthfully(rng):
    """The latency-lie fix: a request that waited in the queue reports that
    wait.  latency_s = queue_s + wave_s, and queue_s covers the dwell."""
    import time as _time

    server = SelectionServer()
    rid = server.submit_spec(SelectionSpec(_build("fl", rng, 24), 4))
    _time.sleep(0.25)
    resp = server.flush()[rid]
    assert resp.queue_s >= 0.25
    assert resp.wave_s > 0
    assert resp.latency_s == pytest.approx(resp.queue_s + resp.wave_s)
    assert resp.latency_s > resp.wave_s  # the old code reported only wave_s
    assert resp.deadline_missed is False
    m = server.metrics.snapshot()
    assert m["queue_s"]["count"] == 1 and m["queue_s"]["max"] >= 0.25


def test_server_backpressure_and_cancel_free_space(rng):
    """max_queue admission control: overflow raises ServerOverloaded and is
    counted; cancel() and flush() free space."""
    from repro.launch.serve import ServerOverloaded

    server = SelectionServer(max_queue=2)
    rid_a = server.submit_spec(SelectionSpec(_build("fl", rng, 24), 3))
    server.submit_spec(SelectionSpec(_build("gc", rng, 24), 3))
    with pytest.raises(ServerOverloaded, match="2/2"):
        server.submit_spec(SelectionSpec(_build("fl", rng, 24), 3))
    assert server.stats.rejections == 1
    assert server.cancel(rid_a)  # freeing a slot re-admits
    server.submit_spec(SelectionSpec(_build("fl", rng, 24), 3))
    out = server.flush()
    assert len(out) == 2
    with pytest.raises(ValueError, match="max_queue"):
        SelectionServer(max_queue=0)


def test_server_stats_bounded_with_stable_summary_keys(rng):
    """The unbounded wave_seconds fix: accounting memory is O(1) in flush
    count (fixed-size reservoir), while summary() keeps the historical keys
    and adds the latency/backpressure decomposition."""
    server = SelectionServer()
    fn = _build("fl", rng, 16)
    for _ in range(3):
        server.select([(fn, 3)])
    s = server.stats.summary()
    for key in ("requests", "waves", "slots", "padded_slots", "total_s"):
        assert key in s  # historical keys, stable
    for key in ("wave_p50_s", "wave_p99_s", "queue_p50_s", "queue_p99_s",
                "rejections", "deadline_misses"):
        assert key in s  # the new decomposition
    assert s["requests"] == 3 and s["waves"] == 3
    assert 0 < s["wave_p50_s"] <= s["wave_p99_s"] <= s["total_s"]
    # bounded: the reservoir never outgrows its capacity
    h = server.metrics.wave_s
    assert h.count == 3 and len(h._reservoir._sample) <= h._reservoir.capacity

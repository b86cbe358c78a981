"""Greedy maximizers (paper §5.3), jit-compatible.

All optimizers return a :class:`GreedyResult` with a fixed-size ``order``
buffer (-1 padded once stopping criteria fire), the per-step gains, and the
number of marginal-gain evaluations performed (the hardware-independent cost
metric used to reproduce the paper's Table 2 ordering; see DESIGN §8.1).

Tie-breaking matches the paper: the *first* best element is added.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.common import NEG_INF, pytree_dataclass
from repro.core.optimizers.backends import full_sweep, partial_sweep


@pytree_dataclass
class GreedyResult:
    order: jax.Array  # (budget,) int32 selected indices, -1 once stopped
    gains: jax.Array  # (budget,) float marginal gains (0 once stopped)
    n_evals: jax.Array  # int32 total marginal-gain evaluations
    value: jax.Array  # f(A) of the returned set (telescoped gains)

    def as_list(self):
        """[(index, gain), ...] like submodlib's maximize() return value."""
        order = jax.device_get(self.order)
        gains = jax.device_get(self.gains)
        return [(int(i), float(g)) for i, g in zip(order, gains) if i >= 0]


def _tree_where(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _should_stop(gj, stop_if_zero: bool, stop_if_negative: bool):
    stop = jnp.zeros((), bool)
    if stop_if_zero:
        stop |= gj <= 0.0
    if stop_if_negative:
        stop |= gj < 0.0
    return stop


def _naive_impl(
    fn,
    budget: int,
    stop_if_zero: bool,
    stop_if_negative: bool,
    budget_i=None,
    valid=None,
    steps=None,
) -> GreedyResult:
    """Single implementation behind :func:`naive_greedy` AND the batched
    engine: ``budget_i`` (dynamic per-instance budget), ``valid`` (padding
    mask) and ``steps`` (the loop's traced trip count, at most ``budget``)
    are None for the plain single-query path — all are trace-time
    decisions, so the None case lowers to exactly the unmasked program.

    ``budget`` is the width of ``order`` / ``gains``.  With ``steps`` the
    loop stops after that many steps; the slots past it keep their initial
    -1 / 0, which is what a step past ``budget_i`` writes anyway."""
    n = fn.n
    state = fn.init_state()
    # n_evals counts LOGICAL oracle evaluations: a padded instance (served
    # at a bucket size, or riding a batched wave) sweeps the padded width,
    # but only the live candidates are reported — so served == sequential.
    true_n = (
        jnp.asarray(n, jnp.int32)
        if valid is None
        else jnp.sum(valid, dtype=jnp.int32)
    )

    def body(i, carry):
        state, selected, order, gains, evals, done = carry
        blocked = selected if valid is None else selected | ~valid
        g = jnp.where(blocked, NEG_INF, full_sweep(fn, state))
        j = jnp.argmax(g)
        gj = g[j]
        past = jnp.zeros((), bool) if budget_i is None else i >= budget_i
        stop = done | past | _should_stop(gj, stop_if_zero, stop_if_negative)
        take = ~stop
        new_state = fn.update(state, j)
        state = _tree_where(take, new_state, state)
        selected = selected.at[j].set(selected[j] | take)
        order = order.at[i].set(jnp.where(take, j, -1))
        gains = gains.at[i].set(jnp.where(take, gj, 0.0))
        evals = evals + jnp.where(done | past, 0, true_n)
        return state, selected, order, gains, evals, stop

    carry = (
        state,
        jnp.zeros((n,), bool),
        jnp.full((budget,), -1, jnp.int32),
        jnp.zeros((budget,), jnp.float32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), bool),
    )
    state, selected, order, gains, evals, _ = jax.lax.fori_loop(
        0, budget if steps is None else steps, body, carry
    )
    return GreedyResult(order=order, gains=gains, n_evals=evals, value=gains.sum())


@partial(jax.jit, static_argnums=(1, 2, 3))
def naive_greedy(
    fn,
    budget: int,
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
) -> GreedyResult:
    """Standard greedy [Nemhauser et al. '78]: full gain sweep per step.

    On TPU the sweep is a single fused pass over the memoized statistics —
    the vectorized adaptation of the paper's per-element loop (DESIGN §2).
    """
    return _naive_impl(fn, budget, stop_if_zero, stop_if_negative)


def _screen_levels(n: int, screen_k: int) -> tuple[tuple[int, int], ...]:
    """Static (lo, hi) slices of the per-step stale-bound sort: cumulative
    screen widths screen_k, 2*screen_k, 4*screen_k, ..., capped at n.

    The last level always reaches n, so every step resolves within the
    schedule and each candidate is evaluated at most once per step — the
    per-step eval cost is <= n (a naive sweep) with equality only on a full
    bound-screen miss."""
    levels, lo = [], 0
    hi = min(max(int(screen_k), 1), n)
    while True:
        levels.append((lo, hi))
        if hi >= n:
            return tuple(levels)
        lo, hi = hi, min(2 * hi, n)


def _where_rows(pred, a, b):
    """Per-row select on (B, ...) pytrees: ``pred`` is (B,)."""
    return jax.tree.map(
        lambda x, y: jnp.where(pred.reshape(pred.shape + (1,) * (x.ndim - 1)), x, y),
        a,
        b,
    )


def _lazy_bucketed_impl(
    fns,
    max_budget: int,
    budgets,
    valid,
    screen_k: int,
    stop_if_zero: bool,
    stop_if_negative: bool,
) -> GreedyResult:
    """Bucketed lazy greedy over a B-stacked batch — the ONE implementation
    behind sequential :func:`lazy_greedy` (B = 1) and the batched engine's
    LazyGreedy path, so their ids/gains/``n_evals`` agree bit-for-bit by
    construction.

    Per step, candidates are sorted by stale upper bound (descending, ties
    broken by lowest index — exactly ``lax.top_k``'s order) and evaluated in
    doubling *levels* of that order (``_screen_levels``): every wave member
    re-evaluates its top-K stalest bounds through ONE gathered
    ``partial_sweep`` call, and a level only executes if some instance is
    still unresolved — a *scalar* ``lax.cond`` predicate, which is what the
    old vmap-of-``lax.cond`` formulation could not give us (under vmap cond
    lowers to select, so both branches ran and batched LazyGreedy paid the
    full O(B*n) sweep every step; see ROADMAP "Lazy batched engine
    efficiency").  An instance accepts once the best true gain seen beats
    every remaining stale bound; the last level spans all n, so a full miss
    degenerates to exactly one evaluation per candidate (cost n, all bounds
    refreshed) — per-step cost never exceeds the naive sweep.

    The winner is the first-index argmax over evaluated true gains
    (unevaluated entries held at NEG_INF), matching naive_greedy's tie rule.
    ``n_evals`` counts, per instance, the LIVE (non-padded) candidates in
    the levels that instance was still unresolved for (plus the initial
    bound sweep over its live candidates) — instances that accept early
    stop accruing even when the wave digs deeper for others, and a padded
    instance reports the same count it would sequentially.
    """
    B, n = valid.shape
    levels = _screen_levels(n, screen_k)
    rows = jnp.arange(B)
    state0 = jax.vmap(lambda f: f.init_state())(fns)
    ub0 = jax.vmap(full_sweep)(fns, state0)

    def body(i, carry):
        state, selected, ub, order, gains, evals, done = carry
        blocked = selected | ~valid
        ubm = jnp.where(blocked, NEG_INF, ub)
        # descending stale-bound order, ties by lowest index (lax.sort over
        # (-value, index) — identical on one device and in the sharded
        # engine's gathered merge, unlike raw top_k whose cross-shard merge
        # would reorder equal bounds)
        iota = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n))
        neg_sv, si = jax.lax.sort((-ubm, iota), dimension=-1, num_keys=2)
        sv = -neg_sv

        def level(lo, hi, c):
            resolved, geval, evaluated, cost = c
            idx = jax.lax.slice_in_dim(si, lo, hi, axis=1)  # (B, hi-lo)
            g = jax.vmap(partial_sweep)(fns, state, idx)
            blk = jnp.take_along_axis(blocked, idx, axis=1)
            g = jnp.where(blk, NEG_INF, g.astype(geval.dtype))
            live = ~resolved  # instances this level still works for
            geval = jnp.where(
                live[:, None], geval.at[rows[:, None], idx].set(g), geval
            )
            evaluated = jnp.where(
                live[:, None], evaluated.at[rows[:, None], idx].set(True), evaluated
            )
            # logical evaluations only: a padded instance's level still
            # spans hi - lo sorted slots, but the pad candidates in it are
            # not oracle calls — count the live ones so served == sequential
            w_valid = jnp.sum(
                jnp.take_along_axis(valid, idx, axis=1), axis=1, dtype=jnp.int32
            )
            cost = cost + jnp.where(live, w_valid, 0)
            best = jnp.max(geval, axis=1)
            rest = (
                sv[:, hi] if hi < n else jnp.full((B,), NEG_INF, sv.dtype)
            )  # largest stale bound not yet evaluated
            resolved = resolved | (best >= rest - 1e-6)
            return resolved, geval, evaluated, cost

        c = (
            jnp.zeros((B,), bool),
            jnp.full((B, n), NEG_INF, ubm.dtype),
            jnp.zeros((B, n), bool),
            jnp.zeros((B,), jnp.int32),
        )
        for lo, hi in levels:
            # scalar predicate: the whole wave skips the level once everyone
            # has resolved (level 0 always runs)
            c = jax.lax.cond(
                jnp.all(c[0]),
                lambda c: c,
                partial(level, lo, hi),
                c,
            )
        _, geval, evaluated, cost = c

        j = jnp.argmax(geval, axis=1)  # first-index tie-break, like naive
        gj = jnp.take_along_axis(geval, j[:, None], axis=1)[:, 0]
        past = i >= budgets
        stop = done | past | _should_stop(gj, stop_if_zero, stop_if_negative)
        take = ~stop
        new_state = jax.vmap(lambda f, s, jj: f.update(s, jj))(fns, state, j)
        state = _where_rows(take, new_state, state)
        selected = selected.at[rows, j].set(selected[rows, j] | take)
        ub = jnp.where(evaluated, geval, ubm)  # refreshed bounds stay valid
        order = order.at[:, i].set(jnp.where(take, j, -1))
        gains = gains.at[:, i].set(jnp.where(take, gj, 0.0))
        evals = evals + jnp.where(done | past, 0, cost)
        return state, selected, ub, order, gains, evals, stop

    carry = (
        state0,
        jnp.zeros((B, n), bool),
        ub0,
        jnp.full((B, max_budget), -1, jnp.int32),
        jnp.zeros((B, max_budget), jnp.float32),
        jnp.sum(valid, axis=1, dtype=jnp.int32),  # the initial bound sweep
        jnp.zeros((B,), bool),
    )
    out = jax.lax.fori_loop(0, max_budget, body, carry)
    state, selected, ub, order, gains, evals, _ = out
    return GreedyResult(
        order=order, gains=gains, n_evals=evals, value=gains.sum(axis=1)
    )


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def lazy_greedy(
    fn,
    budget: int,
    screen_k: int = 8,
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
) -> GreedyResult:
    """Bound-screened greedy — the TPU adaptation of Minoux's accelerated
    (lazy) greedy [paper §5.3.2; DESIGN §2].

    A dense vector ``ub`` of stale upper bounds replaces the priority queue
    (valid by submodularity: gains only shrink as A grows).  Each step
    re-evaluates true gains for the candidates with the largest stale bounds
    in doubling screen levels (screen_k, 2*screen_k, ... — see
    ``_lazy_bucketed_impl``), accepting as soon as the best evaluated gain
    beats every remaining stale bound; a full miss degenerates to one
    evaluation per candidate, so a step never costs more than a naive sweep.
    Identical output to naive_greedy, far fewer gain evaluations on peaked
    gain distributions.

    This is literally the B = 1 instantiation of the bucketed batched lazy
    engine, which is what makes batched/served LazyGreedy waves bit-identical
    to this function (ids, gains AND ``n_evals``).
    """
    fns = jax.tree.map(lambda x: jnp.asarray(x)[None], fn)
    res = _lazy_bucketed_impl(
        fns,
        budget,
        jnp.full((1,), budget, jnp.int32),
        jnp.ones((1, fn.n), bool),
        screen_k,
        stop_if_zero,
        stop_if_negative,
    )
    return GreedyResult(
        order=res.order[0],
        gains=res.gains[0],
        n_evals=res.n_evals[0],
        value=res.value[0],
    )


def _sample_unselected(key, selected, size):
    """Uniform random ``size``-subset of unselected indices (Gumbel top-k)."""
    z = jax.random.uniform(key, selected.shape)
    z = jnp.where(selected, -1.0, z)
    return jax.lax.top_k(z, size)[1]


@partial(jax.jit, static_argnums=(1, 3, 4, 5, 6))
def stochastic_greedy(
    fn,
    budget: int,
    key: jax.Array | None = None,
    epsilon: float = 0.01,
    sample_size: int | None = None,
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
) -> GreedyResult:
    """Stochastic greedy [Mirzasoleiman et al. '15] (paper §5.3.3): each step
    evaluates gains on a random (n/b) log(1/eps) subsample of the remaining
    ground set. Linear total running time independent of budget, 1-1/e-eps in
    expectation."""
    import math

    n = fn.n
    key = jax.random.PRNGKey(0) if key is None else key
    s = sample_size or max(1, min(n, int(math.ceil(n / budget * math.log(1.0 / epsilon)))))
    state = fn.init_state()

    def body(i, carry):
        state, selected, order, gains, evals, done = carry
        subkey = jax.random.fold_in(key, i)
        cand = _sample_unselected(subkey, selected, s)
        g = partial_sweep(fn, state, cand)
        # guard: sampled entries that are actually selected (when fewer than s
        # unselected remain) are masked out
        g = jnp.where(selected[cand], NEG_INF, g)
        bi = jnp.argmax(g)
        j, gj = cand[bi], g[bi]
        stop = done | _should_stop(gj, stop_if_zero, stop_if_negative)
        take = ~stop
        new_state = fn.update(state, j)
        state = _tree_where(take, new_state, state)
        selected = selected.at[j].set(selected[j] | take)
        order = order.at[i].set(jnp.where(take, j, -1))
        gains = gains.at[i].set(jnp.where(take, gj, 0.0))
        evals = evals + jnp.where(done, 0, s)
        return state, selected, order, gains, evals, stop

    carry = (
        state,
        jnp.zeros((n,), bool),
        jnp.full((budget,), -1, jnp.int32),
        jnp.zeros((budget,), jnp.float32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), bool),
    )
    state, selected, order, gains, evals, _ = jax.lax.fori_loop(0, budget, body, carry)
    return GreedyResult(order=order, gains=gains, n_evals=evals, value=gains.sum())


@partial(jax.jit, static_argnums=(1, 3, 4, 5, 6, 7))
def lazier_than_lazy_greedy(
    fn,
    budget: int,
    key: jax.Array | None = None,
    epsilon: float = 0.01,
    sample_size: int | None = None,
    screen_k: int = 8,
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
) -> GreedyResult:
    """Random sampling + lazy evaluation [Mirzasoleiman et al. '15]
    (paper §5.3.4): per step, draw the stochastic-greedy subsample, then apply
    the stale-bound screen *within the sample* — evaluating true gains only on
    the sample's top-``screen_k`` bounds, falling back to the whole sample on
    a bound violation."""
    import math

    n = fn.n
    key = jax.random.PRNGKey(0) if key is None else key
    s = sample_size or max(1, min(n, int(math.ceil(n / budget * math.log(1.0 / epsilon)))))
    k = min(screen_k, s)
    state = fn.init_state()
    ub0 = full_sweep(fn, state)

    def body(i, carry):
        state, selected, ub, order, gains, evals, done = carry
        subkey = jax.random.fold_in(key, i)
        cand = _sample_unselected(subkey, selected, s)  # (s,)
        ub_cand = jnp.where(selected[cand], NEG_INF, ub[cand])
        top_vals, top_pos = jax.lax.top_k(ub_cand, k)
        top_idx = cand[top_pos]
        true_g = partial_sweep(fn, state, top_idx)
        true_g = jnp.where(selected[top_idx], NEG_INF, true_g)
        bi = jnp.argmax(true_g)
        j_screen, g_screen = top_idx[bi], true_g[bi]
        rest_max = jnp.max(ub_cand.at[top_pos].set(NEG_INF))
        ok = g_screen >= rest_max - 1e-6

        def sample_sweep(_):
            g = partial_sweep(fn, state, cand)
            g = jnp.where(selected[cand], NEG_INF, g)
            b = jnp.argmax(g)
            return cand[b], g[b], g, jnp.int32(s)

        def accept(_):
            # refresh bounds only for the screened entries; the rest keep
            # their stale (still valid) bounds
            g = ub_cand.at[top_pos].set(true_g)
            return j_screen, g_screen, g, jnp.int32(k)

        j, gj, upd_g, cost = jax.lax.cond(ok, accept, sample_sweep, None)
        ub = ub.at[cand].set(upd_g)
        stop = done | _should_stop(gj, stop_if_zero, stop_if_negative)
        take = ~stop
        new_state = fn.update(state, j)
        state = _tree_where(take, new_state, state)
        selected = selected.at[j].set(selected[j] | take)
        order = order.at[i].set(jnp.where(take, j, -1))
        gains = gains.at[i].set(jnp.where(take, gj, 0.0))
        evals = evals + jnp.where(done, 0, cost)
        return state, selected, ub, order, gains, evals, stop

    carry = (
        state,
        jnp.zeros((n,), bool),
        ub0,
        jnp.full((budget,), -1, jnp.int32),
        jnp.zeros((budget,), jnp.float32),
        jnp.asarray(n, jnp.int32),
        jnp.zeros((), bool),
    )
    out = jax.lax.fori_loop(0, budget, body, carry)
    state, selected, ub, order, gains, evals, _ = out
    return GreedyResult(order=order, gains=gains, n_evals=evals, value=gains.sum())

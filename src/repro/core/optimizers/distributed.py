"""Sharded batched greedy: a B-query wave over a 2-D mesh (DESIGN §2, §5).

The batch axis is sharded over one mesh axis and every instance's candidate
axis over another — ``jax.vmap`` over the local batch slice composed with a
shard_map partition-greedy sweep.  Function families plug in through
:class:`ShardRule` adapters (registry mirrors
``backends.register_gain_backend``), and each shard's gain sweep routes
through ``backends.full_sweep`` / ``backends.partial_sweep`` on a
candidate-sliced local instance, so fused Pallas sweeps (full and
gathered-subset) are reused per shard.  Two step programs share the
adapters: :func:`sharded_batched_greedy` (naive full sweeps + O(1) winner
election) and :func:`sharded_batched_lazy` (the eval-sparse bucketed lazy
engine: merged stale-bound prefixes + sharded gathered subsets).

Each step of the naive program, per instance:

  1. local gains              — the family's sweep over the resident columns
  2. local argmax             — first-index tie-break inside the shard
  3. pmax + pmin(index)       — O(1)-payload global winner election
  4. masked psum of winner's  — one broadcast to update the memoized
     column over the col axes   statistic (FL curmax, FB acc, ...)

The per-step collective payload is O(stat) + O(1), independent of the
ground-set size.  ``col_axes`` may span several mesh axes; the public entry
is ``solve(..., mesh=jax.make_mesh((b, d), ("batch", "data")))``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common import NEG_INF

_INT_MAX = jnp.int32(2**31 - 1)


def _flat_axis_index(axes: Sequence[str]) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


# ---------------------------------------------------------------------------
# Sharded batched greedy: B queries x sharded ground set on a 2-D mesh.
# ---------------------------------------------------------------------------
#
# A ShardRule describes how one function family's pytree and greedy state
# decompose over the candidate axis, so ONE generic shard_map program serves
# every family.  Per instance (inside jax.vmap over the local batch slice):
#
#   parts  = the family's dynamic arrays, candidate axis sliced to V_loc
#   state  = the memoized statistic; replicated (FL curmax, FB acc) or
#            itself candidate-sharded (GC selsum)
#   sweep  = local marginal gains for the V_loc resident candidates
#   apply  = fold the globally elected winner into the state; at most one
#            O(stat) psum broadcast (the winner's column/row)
#
# Bit-identical contract: rows/features are never split, so each candidate's
# gain is the same float reduction as on one device; the first-index global
# argmax is recovered exactly by local argmax -> pmax(gain) -> pmin(index).

import dataclasses as _dataclasses


class ShardRule:
    """Family adapter for the generic sharded batched greedy.

    Implementations are frozen dataclasses holding only static meta (they are
    hashed into the jit cache key).  Methods run inside shard_map + vmap, so
    ``parts`` / ``state`` are the per-instance local slices.
    """

    def global_parts(self, fn) -> tuple:
        """Dynamic arrays of one instance, in a fixed order."""
        raise NotImplementedError

    def part_specs(self, batch_axes, col_axes) -> tuple:
        """PartitionSpec per part for the B-stacked arrays (batch dim first)."""
        raise NotImplementedError

    def init_state(self, parts):
        """Greedy state for A = {} from the local parts."""
        raise NotImplementedError

    def local_sweep(self, parts, state) -> jax.Array:
        """Marginal gains for the V_loc local candidates, shape (V_loc,)."""
        raise NotImplementedError

    def local_sweep_at(self, parts, state, idx) -> jax.Array:
        """Gains for the LOCAL candidate subset ``idx`` only (the sharded
        ``partial_sweep`` contract, feeding the bucketed lazy engine).

        The default gathers from a full local sweep — correct for every
        rule, but O(V_loc); rules override it with an O(k * stat) gathered
        form (most route through ``backends.partial_sweep`` on the local
        instance, so fused Pallas subset kernels serve per shard)."""
        return self.local_sweep(parts, state)[idx]

    def apply_winner(self, parts, state, take, is_mine, wl, winner, col_axes):
        """State after adding the elected ``winner`` (global index; ``wl`` is
        its local column on the owning shard).  Must be a no-op when ``take``
        is False and identical on every shard afterwards."""
        raise NotImplementedError


@_dataclasses.dataclass(frozen=True)
class FLShardRule(ShardRule):
    """FacilityLocation: columns sharded, rows (represented set) replicated;
    curmax is replicated and updated via a psum broadcast of the winner's
    column — an O(U) payload per step."""

    use_kernel: bool = False

    def global_parts(self, fn):
        return (fn.sim,)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, None, col_axes),)

    def init_state(self, parts):
        (sim,) = parts
        return jnp.zeros((sim.shape[0],), sim.dtype)

    def _local_fn(self, parts):
        from repro.core.functions.facility_location import FacilityLocation

        (sim,) = parts
        return FacilityLocation(
            sim=sim, n=int(sim.shape[1]), use_kernel=self.use_kernel
        )

    def local_sweep(self, parts, curmax):
        from repro.core.functions.facility_location import FLState
        from repro.core.optimizers.backends import full_sweep

        (sim,) = parts
        return full_sweep(
            self._local_fn(parts), FLState(curmax=curmax, n_rows=int(sim.shape[0]))
        )

    def local_sweep_at(self, parts, curmax, idx):
        from repro.core.functions.facility_location import FLState
        from repro.core.optimizers.backends import partial_sweep

        (sim,) = parts
        return partial_sweep(
            self._local_fn(parts),
            FLState(curmax=curmax, n_rows=int(sim.shape[0])),
            idx,
        )

    def apply_winner(self, parts, curmax, take, is_mine, wl, winner, col_axes):
        (sim,) = parts
        col = jnp.where(is_mine, sim[:, wl], 0.0)
        col = jax.lax.psum(col, col_axes)
        return jnp.where(take, jnp.maximum(curmax, col), curmax)


@_dataclasses.dataclass(frozen=True)
class GCShardRule(ShardRule):
    """GraphCut: ground-kernel ROWS are the candidate axis (each shard keeps
    the full row of its candidates), so selsum shards with the candidates and
    the winner update is collective-free — every shard already holds the
    winner's kernel value against its own candidates.

    The sweep is the memoized O(n)-per-step form (``total - lam * (2 selsum
    + diag)``); GraphCut's fused Pallas sweep is the *stateless* full-matrix
    recompute, a different float reduction than the memoized form, so a
    ``use_kernel=True`` GraphCut could not keep the bit-identical contract
    here — the factory rejects it (single-device serving handles it fine)."""

    def global_parts(self, fn):
        return (fn.sim_ground, fn.total, jnp.diagonal(fn.sim_ground), fn.lam)

    def part_specs(self, batch_axes, col_axes):
        return (
            P(batch_axes, col_axes, None),
            P(batch_axes, col_axes),
            P(batch_axes, col_axes),
            P(batch_axes),
        )

    def init_state(self, parts):
        block, total, diag, lam = parts
        return jnp.zeros((block.shape[0],), block.dtype)

    def local_sweep(self, parts, selsum):
        block, total, diag, lam = parts
        return total - lam * (2.0 * selsum + diag)

    def local_sweep_at(self, parts, selsum, idx):
        block, total, diag, lam = parts
        return total[idx] - lam * (2.0 * selsum[idx] + diag[idx])

    def apply_winner(self, parts, selsum, take, is_mine, wl, winner, col_axes):
        block, total, diag, lam = parts
        return jnp.where(take, selsum + block[:, winner], selsum)


@_dataclasses.dataclass(frozen=True)
class FBShardRule(ShardRule):
    """FeatureBased: feature rows sharded over candidates, the accumulated
    feature mass replicated; the winner's feature row is psum-broadcast."""

    concave: str = "sqrt"
    use_kernel: bool = False

    def global_parts(self, fn):
        return (fn.feats, fn.w)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None), P(batch_axes))

    def init_state(self, parts):
        feats, w = parts
        return jnp.zeros((feats.shape[1],), jnp.float32)

    def _local_fn(self, parts):
        from repro.core.functions.feature_based import FeatureBased

        feats, w = parts
        return FeatureBased(
            feats=feats,
            w=w,
            n=int(feats.shape[0]),
            concave=self.concave,
            use_kernel=self.use_kernel,
        )

    def local_sweep(self, parts, acc):
        from repro.core.functions.feature_based import FBState
        from repro.core.optimizers.backends import full_sweep

        return full_sweep(self._local_fn(parts), FBState(acc=acc))

    def local_sweep_at(self, parts, acc, idx):
        from repro.core.functions.feature_based import FBState
        from repro.core.optimizers.backends import partial_sweep

        return partial_sweep(self._local_fn(parts), FBState(acc=acc), idx)

    def apply_winner(self, parts, acc, take, is_mine, wl, winner, col_axes):
        feats, w = parts
        row = jnp.where(is_mine, feats[wl], 0.0)
        row = jax.lax.psum(row, col_axes)
        return jnp.where(take, acc + row, acc)


@_dataclasses.dataclass(frozen=True)
class SCShardRule(ShardRule):
    """SetCover: incidence rows sharded over candidates, the concept axis
    (and the covered indicator) replicated; the winner's incidence row is
    psum-broadcast — the FeatureBased shape over concepts."""

    use_kernel: bool = False

    def global_parts(self, fn):
        return (fn.cover, fn.w)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None), P(batch_axes))

    def init_state(self, parts):
        cover, w = parts
        return jnp.zeros((cover.shape[1],), cover.dtype)

    def _local_fn(self, parts):
        from repro.core.functions.set_cover import SetCover

        cover, w = parts
        return SetCover(
            cover=cover, w=w, n=int(cover.shape[0]), use_kernel=self.use_kernel
        )

    def local_sweep(self, parts, covered):
        from repro.core.functions.set_cover import SCState
        from repro.core.optimizers.backends import full_sweep

        return full_sweep(self._local_fn(parts), SCState(covered=covered))

    def local_sweep_at(self, parts, covered, idx):
        from repro.core.functions.set_cover import SCState
        from repro.core.optimizers.backends import partial_sweep

        return partial_sweep(self._local_fn(parts), SCState(covered=covered), idx)

    def apply_winner(self, parts, covered, take, is_mine, wl, winner, col_axes):
        cover, w = parts
        row = jnp.where(is_mine, cover[wl], 0.0)
        row = jax.lax.psum(row, col_axes)
        return jnp.where(take, jnp.maximum(covered, row), covered)


@_dataclasses.dataclass(frozen=True)
class PSCShardRule(ShardRule):
    """ProbabilisticSetCover: log-miss rows sharded over candidates, the
    memoized per-concept miss probability replicated; the winner's log-miss
    row is psum-broadcast and folded multiplicatively."""

    use_kernel: bool = False

    def global_parts(self, fn):
        return (fn.log_miss, fn.w)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None), P(batch_axes))

    def init_state(self, parts):
        log_miss, w = parts
        return jnp.ones((log_miss.shape[1],), jnp.float32)

    def _local_fn(self, parts):
        from repro.core.functions.set_cover import ProbabilisticSetCover

        log_miss, w = parts
        return ProbabilisticSetCover(
            log_miss=log_miss,
            w=w,
            n=int(log_miss.shape[0]),
            use_kernel=self.use_kernel,
        )

    def local_sweep(self, parts, miss):
        from repro.core.functions.set_cover import PSCState
        from repro.core.optimizers.backends import full_sweep

        return full_sweep(self._local_fn(parts), PSCState(miss=miss))

    def local_sweep_at(self, parts, miss, idx):
        from repro.core.functions.set_cover import PSCState
        from repro.core.optimizers.backends import partial_sweep

        return partial_sweep(self._local_fn(parts), PSCState(miss=miss), idx)

    def apply_winner(self, parts, miss, take, is_mine, wl, winner, col_axes):
        log_miss, w = parts
        row = jnp.where(is_mine, log_miss[wl], 0.0)
        row = jax.lax.psum(row, col_axes)
        return jnp.where(take, miss * jnp.exp(row), miss)


@_dataclasses.dataclass(frozen=True)
class DSumShardRule(ShardRule):
    """DisparitySum: distance-matrix ROWS are the candidate axis (each shard
    keeps the full row of its candidates), selsum shards with the candidates,
    and the winner update is collective-free — the GraphCut shape."""

    def global_parts(self, fn):
        return (fn.dist,)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None),)

    def init_state(self, parts):
        (dist,) = parts
        return jnp.zeros((dist.shape[0],), dist.dtype)

    def local_sweep(self, parts, selsum):
        return selsum

    def local_sweep_at(self, parts, selsum, idx):
        return selsum[idx]

    def apply_winner(self, parts, selsum, take, is_mine, wl, winner, col_axes):
        (dist,) = parts
        return jnp.where(take, selsum + dist[:, winner], selsum)


@_dataclasses.dataclass(frozen=True)
class DMinShardRule(ShardRule):
    """DisparityMin: ``mind`` shards with the candidate rows; the scalars
    f(A) and |A| are replicated, refreshed from a psum of the winner's
    ``mind`` entry (its owning shard contributes, the rest add exact zeros)."""

    def global_parts(self, fn):
        return (fn.dist,)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None),)

    def init_state(self, parts):
        (dist,) = parts
        big = jnp.asarray(1e30, dist.dtype)
        return (
            jnp.full((dist.shape[0],), big, dist.dtype),  # mind (local rows)
            jnp.zeros((), dist.dtype),  # curmin = f(A)
            jnp.zeros((), jnp.int32),  # count = |A|
        )

    def local_sweep(self, parts, state):
        mind, curmin, count = state
        # DisparityMin.gains on the local slice (scalars replicated)
        surrogate = jnp.where(count == 0, 0.0, mind)
        return jnp.minimum(surrogate, 1e30) - curmin

    def local_sweep_at(self, parts, state, idx):
        mind, curmin, count = state
        surrogate = jnp.where(count == 0, 0.0, mind[idx])
        return jnp.minimum(surrogate, 1e30) - curmin

    def apply_winner(self, parts, state, take, is_mine, wl, winner, col_axes):
        (dist,) = parts
        mind, curmin, count = state
        mind_w = jax.lax.psum(jnp.where(is_mine, mind[wl], 0.0), col_axes)
        newmin = jnp.where(
            count <= 0,
            curmin,
            jnp.where(count == 1, mind_w, jnp.minimum(curmin, mind_w)),
        )
        return (
            jnp.where(take, jnp.minimum(mind, dist[:, winner]), mind),
            jnp.where(take, newmin, curmin),
            count + jnp.where(take, 1, 0).astype(jnp.int32),
        )


@_dataclasses.dataclass(frozen=True)
class GCMIShardRule(ShardRule):
    """GCMI: a pure modular function — the query-sum vector shards with the
    candidates, the running value is replicated via a scalar psum."""

    def global_parts(self, fn):
        return (fn.qsum,)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes),)

    def init_state(self, parts):
        (qsum,) = parts
        return jnp.zeros((), qsum.dtype)

    def local_sweep(self, parts, value):
        (qsum,) = parts
        return qsum

    def local_sweep_at(self, parts, value, idx):
        (qsum,) = parts
        return qsum[idx]

    def apply_winner(self, parts, value, take, is_mine, wl, winner, col_axes):
        (qsum,) = parts
        qj = jax.lax.psum(jnp.where(is_mine, qsum[wl], 0.0), col_axes)
        return jnp.where(take, value + qj, value)


@_dataclasses.dataclass(frozen=True)
class LogDetShardRule(ShardRule):
    """LogDet: the candidate Cholesky rows C and pivots d2 shard with the
    candidates (kernel rows); the winner's Cholesky row + pivot are
    psum-broadcast and every shard applies the same rank-1 update.  The
    reduce-form inner product in ``LogDet.update`` is what keeps the local
    e_i floats identical to the single-device sweep."""

    max_select: int = 0

    def global_parts(self, fn):
        return (fn.L, jnp.diagonal(fn.L))

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, col_axes, None), P(batch_axes, col_axes))

    def init_state(self, parts):
        block, diag = parts
        return (
            jnp.zeros((block.shape[0], self.max_select), block.dtype),  # C
            diag,  # d2
            jnp.zeros((), jnp.int32),  # count
        )

    def _local_fn_state(self, parts, state):
        from repro.core.functions.log_det import LogDet, LogDetState

        block, diag = parts
        C, d2, count = state
        fn_loc = LogDet(L=block, n=int(block.shape[0]), max_select=self.max_select)
        st = LogDetState(C=C, d2=d2, count=count, value=jnp.zeros((), block.dtype))
        return fn_loc, st

    def local_sweep(self, parts, state):
        from repro.core.optimizers.backends import full_sweep

        return full_sweep(*self._local_fn_state(parts, state))

    def local_sweep_at(self, parts, state, idx):
        from repro.core.optimizers.backends import partial_sweep

        fn_loc, st = self._local_fn_state(parts, state)
        return partial_sweep(fn_loc, st, idx)

    def apply_winner(self, parts, state, take, is_mine, wl, winner, col_axes):
        from repro.core.functions.log_det import _EPS

        block, diag = parts
        C, d2, count = state
        cj = jax.lax.psum(jnp.where(is_mine, C[wl], jnp.zeros_like(C[wl])), col_axes)
        d2j = jax.lax.psum(jnp.where(is_mine, d2[wl], 0.0), col_axes)
        dj = jnp.sqrt(jnp.maximum(d2j, _EPS))
        e = (block[:, winner] - (C * cj[None, :]).sum(axis=1)) / dj
        C_new = C.at[:, count].set(e, mode="drop")
        return (
            jnp.where(take, C_new, C),
            jnp.where(take, d2 - e * e, d2),
            count + jnp.where(take, 1, 0).astype(jnp.int32),
        )


class _FLInfoShardRule(ShardRule):
    """Shared shape for the FL-family information measures: query-side rows
    replicated, candidate columns sharded, ``curmax`` replicated and updated
    by a psum broadcast of the winner's column (|Q| small, so the query
    kernel stays resident on every shard).  Subclasses
    rebuild the measure on the local column slice; the sweep then routes
    through ``backends.full_sweep`` so the class's own ``gains`` runs."""

    def _local_fn(self, parts):
        raise NotImplementedError

    def init_state(self, parts):
        sim = parts[0]
        return jnp.zeros((sim.shape[0],), sim.dtype)

    def local_sweep(self, parts, curmax):
        from repro.core.functions.facility_location import FLState
        from repro.core.optimizers.backends import full_sweep

        sim = parts[0]
        return full_sweep(
            self._local_fn(parts),
            FLState(curmax=curmax, n_rows=int(sim.shape[0])),
        )

    def local_sweep_at(self, parts, curmax, idx):
        from repro.core.functions.facility_location import FLState
        from repro.core.optimizers.backends import partial_sweep

        sim = parts[0]
        return partial_sweep(
            self._local_fn(parts),
            FLState(curmax=curmax, n_rows=int(sim.shape[0])),
            idx,
        )

    def apply_winner(self, parts, curmax, take, is_mine, wl, winner, col_axes):
        sim = parts[0]
        col = jax.lax.psum(jnp.where(is_mine, sim[:, wl], 0.0), col_axes)
        return jnp.where(take, jnp.maximum(curmax, col), curmax)


@_dataclasses.dataclass(frozen=True)
class FLQMIShardRule(_FLInfoShardRule):
    def global_parts(self, fn):
        return (fn.sim_qv, fn.modular)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, None, col_axes), P(batch_axes, col_axes))

    def _local_fn(self, parts):
        from repro.core.info.fl import FLQMI

        sim_qv, modular = parts
        return FLQMI(sim_qv=sim_qv, modular=modular, n=int(sim_qv.shape[1]))


@_dataclasses.dataclass(frozen=True)
class FLVMIShardRule(_FLInfoShardRule):
    def global_parts(self, fn):
        return (fn.sim, fn.qmax)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, None, col_axes), P(batch_axes, None))

    def _local_fn(self, parts):
        from repro.core.info.fl import FLVMI

        sim, qmax = parts
        return FLVMI(sim=sim, qmax=qmax, n=int(sim.shape[1]))


@_dataclasses.dataclass(frozen=True)
class FLCGShardRule(_FLInfoShardRule):
    def global_parts(self, fn):
        return (fn.sim, fn.pmax)

    def part_specs(self, batch_axes, col_axes):
        return (P(batch_axes, None, col_axes), P(batch_axes, None))

    def _local_fn(self, parts):
        from repro.core.info.fl import FLCG

        sim, pmax = parts
        return FLCG(sim=sim, pmax=pmax, n=int(sim.shape[1]))


@_dataclasses.dataclass(frozen=True)
class FLCMIShardRule(_FLInfoShardRule):
    def global_parts(self, fn):
        return (fn.sim, fn.qmax, fn.pmax)

    def part_specs(self, batch_axes, col_axes):
        return (
            P(batch_axes, None, col_axes),
            P(batch_axes, None),
            P(batch_axes, None),
        )

    def _local_fn(self, parts):
        from repro.core.info.fl import FLCMI

        sim, qmax, pmax = parts
        return FLCMI(sim=sim, qmax=qmax, pmax=pmax, n=int(sim.shape[1]))


# class -> factory(fn) -> ShardRule | None, resolved along the MRO (the same
# plug-in shape as backends.register_gain_backend)
_SHARD_RULES: dict[type, Any] = {}


def register_shard_rule(cls: type, factory) -> None:
    """Plug a :class:`ShardRule` factory in for ``cls`` (and subclasses)."""
    _SHARD_RULES[cls] = factory


def shard_rule(fn) -> ShardRule:
    """Resolve the shard rule serving ``fn``'s family, or raise."""
    for klass in type(fn).__mro__:
        factory = _SHARD_RULES.get(klass)
        if factory is not None:
            rule = factory(fn)
            if rule is not None:
                return rule
    raise NotImplementedError(
        f"{type(fn).__name__} has no registered ShardRule, so it cannot be "
        "mesh-sharded; plug one in via "
        "repro.core.optimizers.distributed.register_shard_rule (see "
        "docs/functions.md for the families served out of the box)"
    )


def _reject_kernel_on_mesh(name: str) -> None:
    raise ValueError(
        f"{name} with use_kernel=True cannot be mesh-sharded bit-identically: "
        "single-device maximize sweeps through the stateless Pallas recompute "
        "while the shard rule must use the memoized form, and their float "
        "reductions differ. Serve it single-device, or build the function "
        "with use_kernel=False."
    )


def _register_builtin_rules():
    from repro.core.functions.disparity import DisparityMin, DisparitySum
    from repro.core.functions.facility_location import FacilityLocation
    from repro.core.functions.feature_based import FeatureBased
    from repro.core.functions.graph_cut import GraphCut
    from repro.core.functions.log_det import LogDet
    from repro.core.functions.set_cover import ProbabilisticSetCover, SetCover
    from repro.core.info.fl import FLCG, FLCMI, FLQMI, FLVMI
    from repro.core.info.gc import GCMI
    from repro.core.optimizers.backends import kernel_enabled

    # use_kernel=None ("auto") is resolved HERE, against the GLOBAL ground-set
    # size, and the concrete bool is baked into the rule: the rules rebuild
    # candidate-sliced local instances whose n is V_loc, and letting the
    # heuristic re-resolve per shard could pick a different float path than
    # the sequential reference, breaking the bit-identical contract.

    def _gc_rule(fn):
        if kernel_enabled(fn.use_kernel, fn.n):
            _reject_kernel_on_mesh("GraphCut")
        return GCShardRule()

    def _dsum_rule(fn):
        if kernel_enabled(fn.use_kernel, fn.n):
            _reject_kernel_on_mesh("DisparitySum")
        return DSumShardRule()

    def _dmin_rule(fn):
        if kernel_enabled(fn.use_kernel, fn.n):
            _reject_kernel_on_mesh("DisparityMin")
        return DMinShardRule()

    register_shard_rule(
        FacilityLocation,
        lambda fn: FLShardRule(use_kernel=kernel_enabled(fn.use_kernel, fn.n)),
    )
    register_shard_rule(GraphCut, _gc_rule)
    register_shard_rule(
        FeatureBased,
        lambda fn: FBShardRule(
            concave=fn.concave, use_kernel=kernel_enabled(fn.use_kernel, fn.n)
        ),
    )
    register_shard_rule(
        SetCover,
        lambda fn: SCShardRule(use_kernel=kernel_enabled(fn.use_kernel, fn.n)),
    )
    register_shard_rule(
        ProbabilisticSetCover,
        lambda fn: PSCShardRule(use_kernel=kernel_enabled(fn.use_kernel, fn.n)),
    )
    register_shard_rule(DisparitySum, _dsum_rule)
    register_shard_rule(DisparityMin, _dmin_rule)
    register_shard_rule(GCMI, lambda fn: GCMIShardRule())
    register_shard_rule(
        LogDet, lambda fn: LogDetShardRule(max_select=fn.max_select)
    )
    register_shard_rule(FLQMI, lambda fn: FLQMIShardRule())
    register_shard_rule(FLVMI, lambda fn: FLVMIShardRule())
    register_shard_rule(FLCG, lambda fn: FLCGShardRule())
    register_shard_rule(FLCMI, lambda fn: FLCMIShardRule())


_register_builtin_rules()


def stack_parts(rule: ShardRule, fns: Sequence) -> tuple:
    """Stack each instance's ``rule.global_parts`` into (B, ...) arrays."""
    per = [rule.global_parts(f) for f in fns]
    return tuple(
        jnp.stack([p[k] for p in per]) for k in range(len(per[0]))
    )


@partial(
    jax.jit,
    static_argnames=(
        "rule",
        "max_budget",
        "mesh",
        "batch_axes",
        "col_axes",
        "stop_if_zero",
        "stop_if_negative",
    ),
)
def sharded_batched_greedy(
    rule: ShardRule,
    parts: tuple,
    budgets: jax.Array,
    valid: jax.Array,
    *,
    max_budget: int,
    mesh: jax.sharding.Mesh,
    batch_axes: Sequence[str] = ("batch",),
    col_axes: Sequence[str] = ("data",),
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
):
    """Run a B-query naive-greedy wave over a (batch x data) mesh.

    Args:
      rule: the family's :class:`ShardRule` (static — part of the jit key).
      parts: B-stacked dynamic arrays from :func:`stack_parts`.
      budgets: (B,) int32 per-instance budgets (instances freeze once spent).
      valid: (B, n) bool; False marks padded candidates.
      max_budget: static loop bound, >= max(budgets).
      mesh: mesh carrying ``batch_axes`` (batch sharding) + ``col_axes``
        (candidate sharding); B and n must be multiples of the respective
        axis sizes.

    Returns ``(order, gains, n_evals, value)`` with shapes ``(B, max_budget)``,
    ``(B, max_budget)``, ``(B,)``, ``(B,)`` — per instance bit-identical to
    ``naive_greedy`` on one device (same sweep -> argmax -> update ordering,
    same stopping rule, ``n_evals`` counting the LIVE candidates per step —
    pad columns sweep along but are not logical oracle calls).
    """
    from repro.core.optimizers.greedy import _should_stop

    batch_axes = tuple(batch_axes)
    col_axes = tuple(col_axes)
    B, n = valid.shape

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            rule.part_specs(batch_axes, col_axes),
            P(batch_axes),
            P(batch_axes, col_axes),
        ),
        out_specs=(
            P(batch_axes, None),
            P(batch_axes, None),
            P(batch_axes),
            P(batch_axes),
        ),
        check_vma=False,
    )
    def run(parts_l, budgets_l, valid_l):
        def one(parts_i, budget_i, valid_i):
            V_loc = valid_i.shape[0]
            col_off = _flat_axis_index(col_axes) * V_loc
            state0 = rule.init_state(parts_i)
            # logical sweep width: live candidates across every shard
            true_n = jax.lax.psum(
                jnp.sum(valid_i, dtype=jnp.int32), col_axes
            )

            def body(i, carry):
                state, selected, order, gains, evals, done = carry
                blocked = selected | ~valid_i
                g = jnp.where(blocked, NEG_INF, rule.local_sweep(parts_i, state))
                lbi = jnp.argmax(g)
                lbg = g[lbi]
                gbest = jax.lax.pmax(lbg, col_axes)
                cand = jnp.where(lbg >= gbest, col_off + lbi, _INT_MAX)
                winner = jax.lax.pmin(cand, col_axes)  # first global argmax
                past = i >= budget_i
                stop = done | past | _should_stop(
                    gbest, stop_if_zero, stop_if_negative
                )
                take = ~stop
                is_mine = (winner >= col_off) & (winner < col_off + V_loc)
                wl = jnp.clip(winner - col_off, 0, V_loc - 1)
                state = rule.apply_winner(
                    parts_i, state, take, is_mine, wl, winner, col_axes
                )
                selected = selected | (
                    take & is_mine & (jnp.arange(V_loc) == wl)
                )
                order = order.at[i].set(jnp.where(take, winner, -1))
                gains = gains.at[i].set(jnp.where(take, gbest, 0.0))
                evals = evals + jnp.where(done | past, 0, true_n)
                return state, selected, order, gains, evals, stop

            carry = (
                state0,
                jnp.zeros((V_loc,), bool),
                jnp.full((max_budget,), -1, jnp.int32),
                jnp.zeros((max_budget,), jnp.float32),
                jnp.zeros((), jnp.int32),
                jnp.zeros((), bool),
            )
            _, _, order, gains, evals, _ = jax.lax.fori_loop(
                0, max_budget, body, carry
            )
            return order, gains, evals, gains.sum()

        return jax.vmap(one)(parts_l, budgets_l, valid_l)

    return run(parts, budgets, valid)


def _all_gather_cols(x: jax.Array, col_axes: Sequence[str]) -> jax.Array:
    """Concatenate a (B_loc, k) array across the column shards along axis 1,
    ordered by the flat column-shard index (matches ``_flat_axis_index``)."""
    # gather the fastest-varying axis first so blocks land in flat-index order
    for a in reversed(tuple(col_axes)):
        x = jax.lax.all_gather(x, a, axis=1, tiled=True)
    return x


@partial(
    jax.jit,
    static_argnames=(
        "rule",
        "max_budget",
        "mesh",
        "batch_axes",
        "col_axes",
        "screen_k",
        "stop_if_zero",
        "stop_if_negative",
    ),
)
def sharded_batched_lazy(
    rule: ShardRule,
    parts: tuple,
    budgets: jax.Array,
    valid: jax.Array,
    *,
    max_budget: int,
    mesh: jax.sharding.Mesh,
    batch_axes: Sequence[str] = ("batch",),
    col_axes: Sequence[str] = ("data",),
    screen_k: int = 8,
    stop_if_zero: bool = True,
    stop_if_negative: bool = True,
):
    """Run a B-query **bucketed lazy** wave over a (batch x data) mesh — the
    eval-sparse counterpart of :func:`sharded_batched_greedy`.

    Same arguments plus ``screen_k``.  Per step, per level of the doubling
    screen schedule (``greedy._screen_levels``):

    1. every shard sorts its local stale bounds once per step (descending,
       ties by lowest GLOBAL index — the same ``lax.sort`` keys as the
       single-device engine, so cross-shard merges cannot reorder equal
       bounds the way raw top_k would);
    2. the level's prefix of each shard's sorted (bound, index) pairs is
       ``all_gather``-ed over the column shards and merge-sorted — an
       O(level width) payload, NOT O(n) — reproducing the global sort prefix
       exactly;
    3. **the gathered subset is sharded back for evaluation**: each shard
       computes true gains only for the screened candidates it owns
       (``rule.local_sweep_at`` — an O(k * stat) partial sweep, Pallas
       subset kernels per shard where the family has them) and a ``psum``
       assembles the replicated (B_loc, k) true-gain block;
    4. acceptance (best evaluated gain beats every remaining stale bound)
       is decided on replicated values, so every shard agrees; the level
       itself is skipped via a ``lax.cond`` whose predicate is uniform
       within each column group once the whole local wave has resolved.

    The winner is already replicated (no pmax/pmin election needed), and
    ``rule.apply_winner`` folds it in exactly as the naive engine does.
    Results are bit-identical to single-device ``lazy_greedy`` per instance
    — ids, gains, and the per-instance ``n_evals`` level accounting.
    """
    from repro.core.optimizers.greedy import _screen_levels, _should_stop

    batch_axes = tuple(batch_axes)
    col_axes = tuple(col_axes)
    B, n = valid.shape
    levels = _screen_levels(n, screen_k)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            rule.part_specs(batch_axes, col_axes),
            P(batch_axes),
            P(batch_axes, col_axes),
        ),
        out_specs=(
            P(batch_axes, None),
            P(batch_axes, None),
            P(batch_axes),
            P(batch_axes),
        ),
        check_vma=False,
    )
    def run(parts_l, budgets_l, valid_l):
        B_loc, V_loc = valid_l.shape
        col_off = _flat_axis_index(col_axes) * V_loc
        gidx = col_off + jnp.arange(V_loc, dtype=jnp.int32)  # global ids
        rows = jnp.arange(B_loc)
        state0 = jax.vmap(rule.init_state)(parts_l)
        ub0 = jax.vmap(rule.local_sweep)(parts_l, state0)  # (B_loc, V_loc)

        def body(i, carry):
            state, selected, ub, order, gains, evals, done = carry
            blocked = selected | ~valid_l
            ubm = jnp.where(blocked, NEG_INF, ub)
            # local descending stale-bound order, ties by global index (one
            # sort per step; levels slice its prefix)
            neg_lv, li = jax.lax.sort(
                (-ubm, jnp.broadcast_to(gidx, (B_loc, V_loc))),
                dimension=-1,
                num_keys=2,
            )

            def level(lo, hi, c):
                resolved, best_g, best_j, geval, evaluated, cost = c
                kl = min(hi + 1, V_loc)  # covers the global top-(hi+1)
                # merge the column shards' sorted prefixes: payload O(hi)
                gv = -_all_gather_cols(neg_lv[:, :kl], col_axes)
                gi = _all_gather_cols(li[:, :kl], col_axes)
                neg_sv, mi = jax.lax.sort((-gv, gi), dimension=-1, num_keys=2)
                sv = -neg_sv  # == the global stale-bound sort through hi
                idx = mi[:, lo:hi]  # (B_loc, w) global candidate ids
                own = (idx >= col_off) & (idx < col_off + V_loc)
                lread = jnp.clip(idx - col_off, 0, V_loc - 1)
                g_loc = jax.vmap(rule.local_sweep_at)(parts_l, state, lread)
                blk = jnp.take_along_axis(blocked, lread, axis=1)
                g_loc = jnp.where(blk, NEG_INF, g_loc.astype(ub.dtype))
                # each screened candidate's gain comes from its owning shard
                g = jax.lax.psum(jnp.where(own, g_loc, 0.0), col_axes)

                live = ~resolved
                # refresh the local shard of the bound vector (owned slots)
                lwrite = jnp.where(own, lread, V_loc)  # V_loc -> dropped
                geval = jnp.where(
                    live[:, None],
                    geval.at[rows[:, None], lwrite].set(g, mode="drop"),
                    geval,
                )
                evaluated = jnp.where(
                    live[:, None],
                    evaluated.at[rows[:, None], lwrite].set(True, mode="drop"),
                    evaluated,
                )
                # logical evaluations only: count the LIVE candidates in
                # the level, summed over the owning shards (matches the
                # single-device engine's padded-instance accounting)
                w_valid = jax.lax.psum(
                    jnp.sum(
                        jnp.take_along_axis(valid_l, lread, axis=1) & own,
                        axis=1,
                        dtype=jnp.int32,
                    ),
                    col_axes,
                )
                cost = cost + jnp.where(live, w_valid, 0)
                # running first-index argmax over everything evaluated so far
                lvl_best = jnp.max(g, axis=1)
                lvl_j = jnp.min(
                    jnp.where(g == lvl_best[:, None], idx, _INT_MAX), axis=1
                )
                better = lvl_best > best_g
                tie = (lvl_best == best_g) & (lvl_j < best_j)
                best_j = jnp.where(live & (better | tie), lvl_j, best_j)
                best_g = jnp.where(live & better, lvl_best, best_g)
                rest = (
                    sv[:, hi]
                    if hi < n
                    else jnp.full((B_loc,), NEG_INF, sv.dtype)
                )
                resolved = resolved | (best_g >= rest - 1e-6)
                return resolved, best_g, best_j, geval, evaluated, cost

            c = (
                jnp.zeros((B_loc,), bool),
                jnp.full((B_loc,), NEG_INF, ub.dtype),
                # matches the single-device argmax over an all-NEG_INF
                # buffer, which degenerates to index 0
                jnp.zeros((B_loc,), jnp.int32),
                jnp.full((B_loc, V_loc), NEG_INF, ub.dtype),
                jnp.zeros((B_loc, V_loc), bool),
                jnp.zeros((B_loc,), jnp.int32),
            )
            for lo, hi in levels:
                # predicate is replicated within each column group (inputs
                # all replicated), so the collectives inside stay uniform
                c = jax.lax.cond(
                    jnp.all(c[0]), lambda c: c, partial(level, lo, hi), c
                )
            _, best_g, best_j, geval, evaluated, cost = c

            gj = best_g
            past = i >= budgets_l
            stop = done | past | _should_stop(gj, stop_if_zero, stop_if_negative)
            take = ~stop
            is_mine = (best_j >= col_off) & (best_j < col_off + V_loc)
            wl = jnp.clip(best_j - col_off, 0, V_loc - 1)
            state = jax.vmap(
                lambda p, s, t, im, w_, wn: rule.apply_winner(
                    p, s, t, im, w_, wn, col_axes
                )
            )(parts_l, state, take, is_mine, wl, best_j)
            selected = selected | (
                take[:, None]
                & is_mine[:, None]
                & (jnp.arange(V_loc)[None, :] == wl[:, None])
            )
            ub = jnp.where(evaluated, geval, ubm)
            order = order.at[:, i].set(jnp.where(take, best_j, -1))
            gains = gains.at[:, i].set(jnp.where(take, gj, 0.0))
            evals = evals + jnp.where(done | past, 0, cost)
            return state, selected, ub, order, gains, evals, stop

        carry = (
            state0,
            jnp.zeros((B_loc, V_loc), bool),
            ub0,
            jnp.full((B_loc, max_budget), -1, jnp.int32),
            jnp.zeros((B_loc, max_budget), jnp.float32),
            jax.lax.psum(  # the initial bound sweep (live candidates)
                jnp.sum(valid_l, axis=1, dtype=jnp.int32), col_axes
            ),
            jnp.zeros((B_loc,), bool),
        )
        out = jax.lax.fori_loop(0, max_budget, body, carry)
        _, _, _, order, gains, evals, _ = out
        return order, gains, evals, gains.sum(axis=1)

    return run(parts, budgets, valid)

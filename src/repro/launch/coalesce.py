"""Request coalescing for the selection server (launch/serve.py).

Incoming selection requests are heterogeneous — different function families,
ground-set sizes, budgets — while the batched engines want homogeneous,
statically-shaped waves.  This module is the bridge:

1. **pad**: each request's function is zero-padded along the candidate axis
   to its n-bucket (:func:`bucket_size`: a ladder of eight steps per octave,
   capped at the next power of two; zero rows/columns have zero marginal
   gain for the supported families, so padding never changes the
   selection), with a per-request ``valid`` row masking the padding;
2. **group**: padded requests sharing (pytree structure, leaf shapes,
   optimizer, stop flags) coalesce into waves of at most ``max_wave``;
3. **bucket budgets**: a wave's ``max_budget`` is the power-of-two bucket
   of its largest budget: the width of its output and part of its compile
   key, so waves with different budget mixes reuse one compiled program.
   The naive loop runs only to the wave's largest live budget (a traced
   trip count); instances freeze once their own budget is spent;
4. **pad the batch**: when serving on a mesh, the wave's batch dimension is
   padded to a multiple of the batch-axis size with budget-0 copies of the
   first instance (they finish in zero steps and are dropped at demux).

The demultiplexing inverse lives on :class:`Wave`: results come back in wave
order and :meth:`Wave.demux` maps them to request ids, dropping batch pads.

Padding semantics are family-specific and registered in ``_PADDERS`` —
FacilityLocation (zero COLUMNS only: the represented-set rows are never
padded, because appending rows changes XLA's sum-reduction tree and shifts
gains by ulps — see ``_pad_fl``), GraphCut (zero rows+columns, zero modular
term — its gains are elementwise, so both axes pad exactly), FeatureBased
(zero feature rows; the feature axis is untouched), SetCover /
ProbabilisticSetCover (zero incidence rows; the concept axis is untouched),
DisparitySum / DisparityMin (zero rows+columns — padded candidates are
valid-masked and padded columns are never selected), LogDet (zero
rows+columns: a padded candidate's Cholesky pivot is 0, so its gain is
NEG_INF), GCMI (zero query-sum entries), and the FL-family information
measures (zero COLUMNS of the ground-side kernel only; the query-side row
axis is never padded, for the same reduction-tree reason as FL).  MI / CG
measures that are plain instances of a padded family — gccg, sc_mi/.../
psc_cmi, logdet_cg — resolve along the MRO and need no entry of their own.
The matrix-free families (FacilityLocationMF / GraphCutMF) pad their
similarity SOURCE instead of a matrix: feature sources pad zero feature
rows on the candidate axis, k-NN sources pad meta-only for FL (the scatter
target grows) and -1/-0 rows for GC, dense sources pad like their
materialized counterparts — so feature- and k-NN-backed requests serve
through ``solve()`` / ``SelectionServer`` unchanged.
``register_padder`` plugs in more families; unsupported ones raise a
``NotImplementedError`` naming it (see docs/functions.md for the coverage
matrix).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional, Sequence

import jax
import numpy as np

from repro.core.functions.disparity import DisparityMin, DisparitySum
from repro.core.functions.facility_location import FacilityLocation, FacilityLocationMF
from repro.core.functions.feature_based import FeatureBased
from repro.core.functions.graph_cut import GraphCut, GraphCutMF
from repro.core.functions.log_det import LogDet
from repro.core.functions.set_cover import ProbabilisticSetCover, SetCover
from repro.core.info.fl import FLCG, FLCMI, FLQMI, FLVMI
from repro.core.info.gc import GCMI
from repro.core.optimizers.spec import OptimizerSpec, SelectionSpec
from repro.core.sources import DenseSource, FeatureSource, KnnSource
from repro.launch import faults


@dataclasses.dataclass
class SelectionRequest:
    """One enqueued query: a request id plus its :class:`SelectionSpec`.

    The request IS the spec — serving adds only routing identity (``rid``)
    and arrival time (``enqueue_ns``, ``time.perf_counter_ns()`` stamped at
    construction: the clock of the serving spans, ``launch/metrics.py``),
    which is what lets the coalescer, the batched engines, and the async
    front end all consume the same validated object unchanged.  The arrival
    stamp is what makes latency accounting truthful: a response reports the
    time the *client* waited (queue + dispatch), not just its wave's
    dispatch wall time.
    """

    rid: int | str
    spec: SelectionSpec
    enqueue_ns: int = dataclasses.field(default_factory=time.perf_counter_ns)

    @property
    def enqueue_t(self) -> float:
        """Arrival in seconds on the ``time.perf_counter()`` clock."""
        return self.enqueue_ns * 1e-9

    @property
    def fn(self):
        """The function with the spec's backend choice applied."""
        return self.spec.resolved_fn()

    @property
    def budget(self) -> int:
        return self.spec.budget

    @property
    def deadline_t(self) -> Optional[float]:
        """Absolute deadline (``enqueue_t + spec.deadline_s``), or
        None when the request carries no deadline."""
        if self.spec.deadline_s is None:
            return None
        return self.enqueue_t + self.spec.deadline_s


def next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


# Steps per octave of the n-bucket ladder.  Eight keeps the pad a bucket
# adds under n / 8 (from n = 1,024 up, where the step is not held at one
# lane width) while letting an octave of mixed-n traffic split into at most
# eight group keys, hence at most eight compiled programs.
BUCKETS_PER_OCTAVE = 8
_LANES = 128  # the smallest step: one TPU lane width


def bucket_size(n: int, multiple: int = 1) -> int:
    """The n-bucket of a ground set of ``n`` candidates: ``n`` rounded up to
    a step of 1/``BUCKETS_PER_OCTAVE`` of its octave (at least one lane
    width), never above the next power of two, then up to a multiple of
    ``multiple`` (the mesh data-axis size, so sharded waves always divide
    evenly).  From n = 4,096 up every bucket is a multiple of 512, the
    candidate tile of the Pallas gain sweeps."""
    n = max(int(n), 1)
    octave = 1 << (n.bit_length() - 1)  # the largest power of two <= n
    step = max(octave // BUCKETS_PER_OCTAVE, _LANES)
    b = min(next_pow2(n), -(-n // step) * step)
    return -(-b // multiple) * multiple


# ---------------------------------------------------------------------------
# Family padders: fn, n_to -> equivalent instance over a padded ground set.
# ---------------------------------------------------------------------------

def _pad_fl(fn: FacilityLocation, n_to: int) -> FacilityLocation:
    import jax.numpy as jnp

    U, n = fn.sim.shape
    # zero columns only: a padded candidate's gain is max(0 - curmax, 0) = 0
    # and the valid mask blocks it.  The ROW (represented-set) axis is never
    # padded — a gain is a sum-reduction over rows, and appending even exact
    # zeros changes XLA's reduction tree and therefore the float result by
    # ulps; keeping rows intact is what makes served FL selections
    # bit-identical to unpadded `maximize`.  Requests with different row
    # counts simply land in different waves (the wave key includes shapes).
    sim = jnp.zeros((U, n_to), fn.sim.dtype).at[:, :n].set(fn.sim)
    return FacilityLocation(sim=sim, n=n_to, use_kernel=fn.use_kernel)


def _pad_gc(fn: GraphCut, n_to: int) -> GraphCut:
    import jax.numpy as jnp

    n = fn.n
    sim = jnp.zeros((n_to, n_to), fn.sim_ground.dtype).at[:n, :n].set(fn.sim_ground)
    total = jnp.zeros((n_to,), fn.total.dtype).at[:n].set(fn.total)
    return GraphCut(
        sim_ground=sim, total=total, lam=fn.lam, n=n_to, use_kernel=fn.use_kernel
    )


def _pad_fb(fn: FeatureBased, n_to: int) -> FeatureBased:
    import jax.numpy as jnp

    n = fn.n
    feats = jnp.zeros((n_to, fn.feats.shape[1]), fn.feats.dtype).at[:n].set(fn.feats)
    return FeatureBased(
        feats=feats, w=fn.w, n=n_to, concave=fn.concave, use_kernel=fn.use_kernel
    )


def _pad_sc(fn: SetCover, n_to: int) -> SetCover:
    import jax.numpy as jnp

    n, m = fn.cover.shape
    # zero incidence rows: a padded candidate covers nothing, so its gain is
    # exactly 0 and the valid mask blocks it; real candidates' gains are
    # per-row reductions over the untouched concept axis, so they are
    # bit-identical to the unpadded instance.
    cover = jnp.zeros((n_to, m), fn.cover.dtype).at[:n].set(fn.cover)
    return SetCover(cover=cover, w=fn.w, n=n_to, use_kernel=fn.use_kernel)


def _pad_psc(fn: ProbabilisticSetCover, n_to: int) -> ProbabilisticSetCover:
    import jax.numpy as jnp

    n, m = fn.log_miss.shape
    # log(1 - p) = 0 rows: a padded candidate has p = 0 everywhere -> gain 0.
    log_miss = jnp.zeros((n_to, m), fn.log_miss.dtype).at[:n].set(fn.log_miss)
    return ProbabilisticSetCover(
        log_miss=log_miss, w=fn.w, n=n_to, use_kernel=fn.use_kernel
    )


def _pad_square_dist(fn, n_to: int):
    import jax.numpy as jnp

    cls = type(fn)
    n = fn.n
    dist = jnp.zeros((n_to, n_to), fn.dist.dtype).at[:n, :n].set(fn.dist)
    return cls(dist=dist, n=n_to, use_kernel=fn.use_kernel)


def _pad_logdet(fn: LogDet, n_to: int) -> LogDet:
    import jax.numpy as jnp

    n = fn.n
    # zero rows+columns: a padded candidate's pivot d2 starts (and stays) 0,
    # so its gain is NEG_INF and it can never be selected even before the
    # valid mask; max_select is preserved (it is capacity, not ground size).
    L = jnp.zeros((n_to, n_to), fn.L.dtype).at[:n, :n].set(fn.L)
    return LogDet(L=L, n=n_to, max_select=fn.max_select)


def _pad_gcmi(fn: GCMI, n_to: int) -> GCMI:
    import jax.numpy as jnp

    qsum = jnp.zeros((n_to,), fn.qsum.dtype).at[: fn.n].set(fn.qsum)
    return GCMI(qsum=qsum, n=n_to)


def _pad_flqmi(fn: FLQMI, n_to: int) -> FLQMI:
    import jax.numpy as jnp

    nq, n = fn.sim_qv.shape
    # zero COLUMNS only, like FacilityLocation: the query-side row axis is a
    # sum-reduction axis and may never be padded (reduction-tree ulps).
    sim_qv = jnp.zeros((nq, n_to), fn.sim_qv.dtype).at[:, :n].set(fn.sim_qv)
    modular = jnp.zeros((n_to,), fn.modular.dtype).at[:n].set(fn.modular)
    return FLQMI(sim_qv=sim_qv, modular=modular, n=n_to)


def _pad_ground_cols(sim, n_to: int):
    import jax.numpy as jnp

    nv, n = sim.shape
    return jnp.zeros((nv, n_to), sim.dtype).at[:, :n].set(sim)


def _pad_flvmi(fn: FLVMI, n_to: int) -> FLVMI:
    return FLVMI(sim=_pad_ground_cols(fn.sim, n_to), qmax=fn.qmax, n=n_to)


def _pad_flcg(fn: FLCG, n_to: int) -> FLCG:
    return FLCG(sim=_pad_ground_cols(fn.sim, n_to), pmax=fn.pmax, n=n_to)


def _pad_flcmi(fn: FLCMI, n_to: int) -> FLCMI:
    return FLCMI(
        sim=_pad_ground_cols(fn.sim, n_to), qmax=fn.qmax, pmax=fn.pmax, n=n_to
    )


def _pad_source_cols(src, n_to: int):
    """Pad a similarity source's CANDIDATE (column) axis only — the row
    axis is a sum-reduction axis and is never padded (same reduction-tree
    argument as ``_pad_fl``)."""
    import dataclasses as _dc

    import jax.numpy as jnp

    if isinstance(src, FeatureSource):
        return src.pad_cols(n_to)
    if isinstance(src, KnnSource):
        # meta-only: the scatter target grows; indices/weights are untouched,
        # so real candidates' gains are bit-identical for free
        return _dc.replace(src, n_cols=n_to)
    if isinstance(src, DenseSource):
        n = src.n_cols
        sim = jnp.zeros((src.n_rows, n_to), src.sim.dtype).at[:, :n].set(src.sim)
        return _dc.replace(src, sim=sim, n_cols=n_to)
    raise NotImplementedError(
        f"no column padder for source type {type(src).__name__}"
    )


def _pad_source_square(src, n_to: int):
    """Pad a SQUARE ground-set source on both axes (Graph-Cut shape).

    Feature pad rows are zero-feature rows — their similarity to real
    points is generally nonzero (cosine midpoint, RBF at distance), but
    every read of those entries is blocked: pad candidates are
    valid-masked, pad columns carry selmask/total/diag 0, and ``col`` reads
    at pad rows only feed gains of pad candidates."""
    import dataclasses as _dc

    import jax.numpy as jnp

    if isinstance(src, FeatureSource):
        return src.square_pad(n_to)
    if isinstance(src, KnnSource):
        n = src.n_rows
        indices = jnp.full((n_to, src.k), -1, jnp.int32).at[:n].set(src.indices)
        weights = jnp.zeros((n_to, src.k), src.weights.dtype).at[:n].set(src.weights)
        return _dc.replace(
            src, indices=indices, weights=weights, n_rows=n_to, n_cols=n_to
        )
    if isinstance(src, DenseSource):
        n = src.n_cols
        sim = jnp.zeros((n_to, n_to), src.sim.dtype).at[:n, :n].set(src.sim)
        return _dc.replace(src, sim=sim, n_rows=n_to, n_cols=n_to)
    raise NotImplementedError(
        f"no square padder for source type {type(src).__name__}"
    )


def _pad_flmf(fn: FacilityLocationMF, n_to: int) -> FacilityLocationMF:
    return FacilityLocationMF(
        src=_pad_source_cols(fn.src, n_to), n=n_to, use_kernel=fn.use_kernel
    )


def _pad_gcmf(fn: GraphCutMF, n_to: int) -> GraphCutMF:
    import jax.numpy as jnp

    n = fn.n
    total = jnp.zeros((n_to,), fn.total.dtype).at[:n].set(fn.total)
    diag = jnp.zeros((n_to,), fn.diag.dtype).at[:n].set(fn.diag)
    return GraphCutMF(
        src=_pad_source_square(fn.src, n_to),
        total=total,
        diag=diag,
        lam=fn.lam,
        n=n_to,
        use_kernel=fn.use_kernel,
    )


_PADDERS: dict[type, Callable] = {
    FacilityLocation: _pad_fl,
    GraphCut: _pad_gc,
    FeatureBased: _pad_fb,
    SetCover: _pad_sc,
    ProbabilisticSetCover: _pad_psc,
    DisparitySum: _pad_square_dist,
    DisparityMin: _pad_square_dist,
    LogDet: _pad_logdet,
    GCMI: _pad_gcmi,
    FLQMI: _pad_flqmi,
    FLVMI: _pad_flvmi,
    FLCG: _pad_flcg,
    FLCMI: _pad_flcmi,
    FacilityLocationMF: _pad_flmf,
    GraphCutMF: _pad_gcmf,
}


def register_padder(cls: type, padder: Callable) -> None:
    """Plug in ``padder(fn, n_to) -> fn_padded`` for a function family."""
    _PADDERS[cls] = padder


def resolve_padder(cls: type) -> Callable:
    """The padder serving ``cls`` (resolved along the MRO), or a
    ``NotImplementedError`` naming :func:`register_padder`.  The serving
    front door calls this at submit time so an unsupported family is
    rejected before it can poison a flush."""
    for klass in cls.__mro__:
        padder = _PADDERS.get(klass)
        if padder is not None:
            return padder
    raise NotImplementedError(
        f"{cls.__name__} has no registered padder, so it cannot be "
        "coalesced into served waves; plug one in via "
        "repro.launch.coalesce.register_padder (see docs/functions.md for "
        "the families served out of the box)"
    )


def pad_function(fn, n_to: int):
    """Zero-pad ``fn``'s candidate axis to ``n_to`` (identity if equal).

    The registry is consulted even when no padding is needed: a family
    without a padder must fail the same way at every ground-set size, not
    only when its n misses its bucket.  This is also the
    "padder" fault-injection boundary (``launch/faults.py``) — it fires
    even at exact bucket sizes, for the same any-size consistency reason.
    Materialization happens at flush time, so a padder fault aborts a
    drain *before* any queue entry is removed (or, on the resilient drain,
    isolates just the failing group)."""
    padder = resolve_padder(type(fn))
    faults.check("padder", family=type(fn).__name__, n=fn.n, n_to=n_to)
    if fn.n == n_to:
        return fn
    if fn.n > n_to:
        raise ValueError(f"cannot pad n={fn.n} down to {n_to}")
    return padder(fn, n_to)


# ---------------------------------------------------------------------------
# Waves
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Wave:
    """A homogeneous, statically-shaped batch ready for a batched engine."""

    requests: list[SelectionRequest]  # the real requests, in batch order
    fns: list  # padded instances; len >= len(requests) (batch pads at tail)
    valid: np.ndarray  # (B, n_bucket) bool
    budgets: list[int]  # per-slot budgets; 0 for batch-pad slots
    max_budget: int  # output width and compile key (pow2 bucket of the largest budget)
    optimizer: OptimizerSpec  # shared by the wave (hyperparameters included)
    stop_if_zero: bool
    stop_if_negative: bool
    n_bucket: int
    bytes_stacked: int  # nbytes of the padded fns' leaves, batch pads included
    bytes_live: int  # nbytes of the real requests' own unpadded fns' leaves
    cols_stacked: int  # candidate columns swept: B x n_bucket, batch pads included
    cols_live: int  # the real requests' own candidates: sum of their n

    @property
    def batch_size(self) -> int:
        return len(self.fns)

    @property
    def n_padded_slots(self) -> int:
        return len(self.fns) - len(self.requests)

    @property
    def label(self) -> str:
        """Metrics label of the group that produced this wave — matches
        :func:`group_label` for every member request."""
        return (
            f"{type(self.requests[0].spec.fn).__name__}/n{self.n_bucket}"
            f"/{self.optimizer.name}"
        )

    def demux(self, results: Sequence) -> dict:
        """Map per-slot engine results back to {rid: result}, dropping the
        batch-pad slots.  ``results`` is whatever the engine returned, in
        slot order (GreedyResults or [(idx, gain), ...] lists)."""
        return {req.rid: results[i] for i, req in enumerate(self.requests)}


# -- group keys: wave identity, promoted to queue identity --------------------
#
# Requests sharing a group key can ride one engine dispatch, so the key is
# ALSO the right identity for the serving front door's pending queues
# (continuous batching: a late request joins the next wave of *its* group
# instead of waiting for a global flush).  The key must therefore be cheap
# enough to compute at submit time: the padded pytree layout is derived
# shape-only via ``jax.eval_shape`` (no FLOPs, no device buffers) and
# memoized per (treedef, leaf shapes/dtypes, n_bucket).

_LAYOUT_CACHE: dict = {}


def _padded_layout(fn, n_bucket: int) -> tuple:
    """(pytree structure, leaf shapes) of ``pad_function(fn, n_bucket)``,
    computed without materializing any padded array."""
    leaves, treedef = jax.tree.flatten(fn)
    cache_key = (
        treedef,
        tuple((tuple(leaf.shape), str(leaf.dtype)) for leaf in leaves),
        n_bucket,
    )
    layout = _LAYOUT_CACHE.get(cache_key)
    if layout is None:
        padded = jax.eval_shape(lambda f: pad_function(f, n_bucket), fn)
        layout = (
            jax.tree.structure(padded),
            tuple(tuple(leaf.shape) for leaf in jax.tree.leaves(padded)),
        )
        _LAYOUT_CACHE[cache_key] = layout
    return layout


def group_key(req: SelectionRequest, *, n_multiple: int = 1) -> tuple:
    """The (family, n-bucket) group identity of a request.

    Two requests with equal keys coalesce into the same wave: padded pytree
    structure + leaf shapes, the (hashable) OptimizerSpec — hyperparameters
    ride along without being enumerated — and the stop flags.  Budgets and
    deadlines deliberately do NOT key: waves mix budgets under one bucketed
    output width, and a deadline shapes flush *scheduling*, never wave
    membership.
    """
    fn = req.fn  # the spec's backend choice applied
    n_bucket = bucket_size(fn.n, n_multiple)
    structure, shapes = _padded_layout(fn, n_bucket)
    spec = req.spec
    return (
        structure,
        shapes,
        spec.optimizer,
        spec.stop_if_zero,
        spec.stop_if_negative,
    )


def group_label(req: SelectionRequest, *, n_multiple: int = 1) -> str:
    """Human-readable metrics label for the request's group:
    ``Family/n<bucket>/<Optimizer>`` (coarser than :func:`group_key` — leaf
    shapes beyond the n-bucket are folded away for readability)."""
    fn = req.spec.fn
    return (
        f"{type(fn).__name__}/n{bucket_size(fn.n, n_multiple)}"
        f"/{req.spec.optimizer.name}"
    )


def _nbytes(fn) -> int:
    """Bytes of a function pytree's leaves, from their shapes and dtypes."""
    return sum(
        int(np.prod(np.shape(leaf))) * np.result_type(leaf).itemsize
        for leaf in jax.tree.leaves(fn)
    )


def waves_for_group(
    requests: Sequence[SelectionRequest],
    *,
    max_wave: int = 64,
    n_multiple: int = 1,
    b_multiple: int = 1,
) -> list[Wave]:
    """Build dispatchable waves from requests sharing one :func:`group_key`
    (one queue's drain).  Padding is materialized HERE, at flush time —
    submit time only ever computes shapes."""
    members = []
    for req in requests:
        fn = req.fn
        members.append((req, pad_function(fn, bucket_size(fn.n, n_multiple))))
    head = requests[0].spec
    waves = []
    for lo in range(0, len(members), max_wave):
        chunk = members[lo : lo + max_wave]
        reqs = [r for r, _ in chunk]
        fns = [f for _, f in chunk]
        budgets = [r.budget for r in reqs]
        # batch pads: budget-0 copies of slot 0, dropped at demux
        b_total = -(-len(fns) // b_multiple) * b_multiple
        fns = fns + [fns[0]] * (b_total - len(fns))
        budgets = budgets + [0] * (b_total - len(reqs))
        n_bucket = fns[0].n
        valid = np.zeros((b_total, n_bucket), bool)
        for i in range(b_total):
            true_n = reqs[i].spec.fn.n if i < len(reqs) else reqs[0].spec.fn.n
            valid[i, :true_n] = True
        waves.append(
            Wave(
                requests=reqs,
                fns=fns,
                valid=valid,
                budgets=budgets,
                max_budget=next_pow2(max(budgets)) if max(budgets) else 1,
                optimizer=head.optimizer,
                stop_if_zero=head.stop_if_zero,
                stop_if_negative=head.stop_if_negative,
                n_bucket=n_bucket,
                # one group key: every padded fn has the same leaf shapes
                bytes_stacked=b_total * _nbytes(fns[0]),
                bytes_live=sum(_nbytes(r.spec.fn) for r in reqs),
                cols_stacked=b_total * n_bucket,
                cols_live=sum(r.spec.fn.n for r in reqs),
            )
        )
    return waves


def coalesce(
    requests: Sequence[SelectionRequest],
    *,
    max_wave: int = 64,
    n_multiple: int = 1,
    b_multiple: int = 1,
) -> list[Wave]:
    """Group requests into dispatchable waves.

    Args:
      requests: pending selection requests (any mix of families/sizes).
      max_wave: cap on real requests per wave.
      n_multiple: pad every n-bucket up to a multiple of this (the mesh
        data-axis size for sharded serving).
      b_multiple: pad every wave's batch up to a multiple of this (the mesh
        batch-axis size for sharded serving).

    Returns waves in first-arrival order of their earliest request.  The
    serving front door keeps per-group queues keyed by :func:`group_key`
    and drains them through :func:`waves_for_group` directly; this function
    is the one-shot composition of the two for flat request lists.
    """
    groups: dict[tuple, list[SelectionRequest]] = {}
    for req in requests:
        groups.setdefault(group_key(req, n_multiple=n_multiple), []).append(req)
    waves = []
    for members in groups.values():
        waves.extend(
            waves_for_group(
                members,
                max_wave=max_wave,
                n_multiple=n_multiple,
                b_multiple=b_multiple,
            )
        )
    return waves

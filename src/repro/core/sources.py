"""Similarity sources: where a kernel-based function's sim(i, j) comes from.

The dense families (``FacilityLocation.from_kernel`` & co) take a
materialized (|U|, n) similarity matrix, which caps n at ~10^4 — the n^2
bytes are the ROADMAP's #1 scale blocker.  A :class:`SimilaritySource` is
the matrix-free replacement: an object that can answer the *same* queries
the memoized statistics need — a single column, a full fused gain sweep, a
gathered-subset sweep — without ever writing the n x n matrix.

Three sources ride one contract:

- :class:`FeatureSource` — raw feature rows plus a metric
  (dot / cosine / euclidean / RBF, matching ``kernels/similarity_kernel.py``).
  Sweeps stream fixed-width column tiles of sim through a ``lax.scan``:
  peak memory is O(n_rows * TILE) per step, O(n * d) overall.  Optional
  integer ``labels`` block-mask the similarity (``sim_ij = 0`` unless
  ``label_i == label_j``) which is exactly the paper's §8 clustered
  decomposition, streamed.
- :class:`KnnSource` — precomputed sparse k-NN similarity in CSR-ish padded
  form: per-row neighbor ``indices`` (int32, -1 = empty slot) and
  nonnegative ``weights``.  Sweeps are O(n * k) scatter-adds.
- :class:`DenseSource` — the materialized matrix itself, so dense requests
  ride the same backend contract (and the existing fused Pallas sweeps).

Sources are frozen pytree dataclasses: they pass through jit / vmap /
``jax.eval_shape`` (the serving coalescer derives group keys shape-only),
and the static meta fields (metric, shapes) key the jit cache.

The queries every source answers (FL = facility location, the relu-reduce
family; the elementwise Graph-Cut statistics ride ``col``/``col_sums``/
``diag``):

  col(j)                 (n_rows,)  similarity of every row to candidate j
  col_sums()             (n_cols,)  per-candidate column sums (GC ``total``)
  diag()                 (n_cols,)  sim(j, j) for square sources (GC diag)
  fl_gains(curmax)       (n_cols,)  sum_i max(sim_ij - curmax_i, 0)
  fl_gains_at(curmax, idx)  (k,)    gathered subset; idx < 0 -> NEG_INF
  masked_rowmax(mask)    (n_rows,)  max_{j: mask_j} sim_ij (empty -> 0)
  quad(mask)             scalar     m^T S m (square sources; GC evaluate)
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.common import (
    DISTANCE_METRICS,
    NEG_INF,
    exact_self_distance,
    fl_gain_sums,
    pytree_dataclass,
)

# Column-tile width of the streamed feature sweeps.  Static so every
# serving bucket of the same source traces to the same per-column
# computation (zero-padding columns to a bucket and then to a TILE
# multiple is the same array as padding straight to the TILE multiple).
TILE = 512


# -- bit-stable streamed blocks ---------------------------------------------
#
# The serving contract pins every response bit-identical to sequential
# ``solve(spec)``, and the dense families meet it for free: their in-engine
# float work is elementwise (plus gathers of materialized data), and
# elementwise float ops are bit-deterministic no matter how XLA fuses or
# batches them.  A matrix-free sweep is not: its similarity dot and its
# column reduction are order-sensitive, and under ``vmap`` (the batched
# engine, every served wave) their SHAPES change — (B, n, t) instead of
# (n, t) — so XLA may pick a different accumulation order and drift by
# ulps.  Empirically even the batch width alone (a wave of 1 vs a batch of
# 2) flips the last bits of a contraction on CPU.
#
# Two measures make the streamed sweep behave like materialized data:
#
# - ``_fence`` (``lax.optimization_barrier``) around each dot / reduce, so
#   it stays a standalone instruction instead of fusing into whatever
#   engine loop surrounds it;
# - a ``custom_vmap`` rule on the similarity block and the column reduce
#   that lowers batching to ``lax.map`` of the UNBATCHED computation, so a
#   batch member runs the exact instructions the sequential program runs,
#   for any batch width.  (Per-instance streaming is also the memory
#   contract: a vectorized batched sweep would hold B live (n, TILE)
#   blocks.)


def _fence(x: jax.Array) -> jax.Array:
    return jax.lax.optimization_barrier(x)


def _map_unbatched(fn, axis_size, in_batched, args):
    """The shared custom_vmap rule: run ``fn`` per batch member via
    ``lax.map`` so batched execution replays the unbatched instructions."""

    def one(i):
        sliced = [
            jnp.take(a, i, axis=0) if b else a for a, b in zip(args, in_batched)
        ]
        return fn(*sliced)

    return jax.lax.map(one, jnp.arange(axis_size)), True


def _tree_dot(x: jax.Array, yt: jax.Array) -> jax.Array:
    """x (n, d) · yt (t, d)^T -> (n, t) as an explicit balanced add-tree of
    outer products over the (static) feature axis.

    A ``dot_general`` of the same shapes is NOT bit-stable across programs:
    XLA's dot lowering (layout assignment, matvec strength reduction) is
    context-dependent, and the accumulation order over d moves with it.  An
    explicit add DAG of elementwise ops is never reassociated, so every
    program — sequential, vmapped at any width, any serving bucket —
    computes the exact same float sequence per output element."""
    terms = [x[:, k][:, None] * yt[None, :, k] for k in range(x.shape[1])]
    while len(terms) > 1:
        nxt = [a + b for a, b in zip(terms[::2], terms[1::2])]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


@functools.lru_cache(maxsize=None)
def _sim_block(metric: str, inv_two_sigma_sq: float, masked: bool, ided: bool):
    """Cached bit-stable similarity block for (metric, sigma, masked,
    ided: column ids name which columns are rows, see
    ``repro.common.exact_self_distance``)."""

    def base(x, yt, xx, yyt, *extra):
        acc = _tree_dot(x, yt)
        if metric == "dot":
            s = acc
        elif metric == "cosine":
            s = 0.5 * (1.0 + acc)  # rows arrive pre-normalized
        else:
            d2 = jnp.maximum(xx[:, None] + yyt[None, :] - 2.0 * acc, 0.0)
            if ided:
                d2 = exact_self_distance(d2, 0, extra[0])
            if metric == "euclidean":
                s = 1.0 / (1.0 + jnp.sqrt(d2))
            else:  # rbf
                s = jnp.exp(-d2 * inv_two_sigma_sq)
        if masked:
            rl, lt = extra[-2:]
            s = jnp.where(rl[:, None] == lt[None, :], s, 0.0)
        return _fence(s)

    f = jax.custom_batching.custom_vmap(base)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        return _map_unbatched(f, axis_size, in_batched, args)

    return f


@jax.custom_batching.custom_vmap
def _colsum(t: jax.Array) -> jax.Array:
    """Bit-stable column sum: (n_rows, tc) -> (tc,)."""
    return _fence(_fence(t).sum(axis=0))


@_colsum.def_vmap
def _colsum_rule(axis_size, in_batched, t):
    return _map_unbatched(_colsum, axis_size, in_batched, (t,))


def _pad_to(a: jax.Array | None, n_to: int, value=0) -> jax.Array | None:
    """``a`` extended along axis 0 to ``n_to`` entries of ``value``."""
    if a is None:
        return None
    return jnp.full((n_to,) + a.shape[1:], value, a.dtype).at[: a.shape[0]].set(a)


def _pad_axis(a: jax.Array, mult: int, axis: int, value=0.0) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@pytree_dataclass(meta_fields=("metric", "rbf_sigma", "d", "n_rows", "n_cols"))
class FeatureSource:
    """Features + metric: sim(i, j) = metric(x_i, y_j), computed on demand.

    ``x`` are the represented-set rows, ``y`` the candidate columns (the
    same array for symmetric sources — build with :func:`feature_source`,
    which also sets ``col_ids`` so that a point's distance to itself is
    exactly 0, see :func:`repro.common.exact_self_distance`).
    For cosine the rows arrive PRE-normalized (zero-norm rows clamp to the
    zero vector, landing on the 0.5 midpoint after the [0, 1] shift, same
    as ``core/similarity.py``); ``xx``/``yy`` are squared row norms feeding
    the euclidean/RBF epilogues.  ``row_labels``/``col_labels`` (int32,
    >= 0; pad slots are -1) switch on clustered block-masking.
    """

    x: jax.Array  # (n_rows, d) fp32
    y: jax.Array  # (n_cols, d) fp32
    xx: jax.Array  # (n_rows,) squared norms
    yy: jax.Array  # (n_cols,)
    row_labels: jax.Array | None
    col_labels: jax.Array | None
    # (n_cols,) int32: the row each column is the same point as (-1: none);
    # None when rows and columns are different point sets
    col_ids: jax.Array | None
    metric: str
    rbf_sigma: float | None
    d: int
    n_rows: int
    n_cols: int

    # -- similarity blocks ---------------------------------------------------
    def _inv_two_sigma_sq(self) -> float:
        sigma = self.rbf_sigma if self.rbf_sigma is not None else float(self.d) ** 0.5
        return 1.0 / (2.0 * sigma * sigma)

    def _sim_cols(self, yt, yyt, lt, ct) -> jax.Array:
        """Similarity block (n_rows, tc) against the column tile ``yt``
        (``lt`` its labels, ``ct`` its column ids; either may be None)."""
        ided = ct is not None and self.metric in DISTANCE_METRICS
        block = _sim_block(
            self.metric, self._inv_two_sigma_sq(), lt is not None, ided
        )
        args = [self.x, yt, self.xx, yyt]
        if ided:
            args.append(ct)
        if lt is not None:
            args += [self.row_labels, lt]
        return block(*args)

    def _col_tiles(self):
        """(y, yy, labels, col ids) reshaped to (nt, TILE, ...) for a
        lax.scan; absent labels / ids stay None."""
        y = _pad_axis(self.y, TILE, 0)
        nt = y.shape[0] // TILE

        def tiles(a, value=0.0):
            if a is None:
                return None
            a = _pad_axis(a, TILE, 0, value=value)
            return a.reshape((nt, TILE) + a.shape[1:])

        return (
            tiles(y),
            tiles(self.yy),
            tiles(self.col_labels, value=-1),
            tiles(self.col_ids, value=-1),
        )

    def _scan_cols(self, per_tile, init):
        """Stream column tiles through ``per_tile(carry, sim_block)``.

        The scan carries ``init`` and stacks per-tile outputs.  Peak live
        bytes: one (n_rows, TILE) block, never (n_rows, n_cols).
        """

        def body(carry, tile):
            return per_tile(carry, self._sim_cols(*tile))

        return jax.lax.scan(body, init, self._col_tiles())

    # -- column operations ---------------------------------------------------
    # Everything indexed by column (y, yy, col_labels, col_ids) moves
    # together here, so callers never touch the per-column arrays one by one.
    def take_cols(self, idx: jax.Array) -> "FeatureSource":
        """The source over candidate columns ``idx`` (in range); rows kept."""

        def take(a):
            return None if a is None else jnp.take(a, idx, axis=0)

        return dataclasses.replace(
            self, y=take(self.y), yy=take(self.yy), col_labels=take(self.col_labels),
            col_ids=take(self.col_ids), n_cols=int(idx.shape[0]),
        )

    def pad_cols(self, n_to: int) -> "FeatureSource":
        """Zero-feature columns appended up to ``n_to``; their labels and
        ids are -1, which match nothing."""
        return dataclasses.replace(
            self, y=_pad_to(self.y, n_to), yy=_pad_to(self.yy, n_to),
            col_labels=_pad_to(self.col_labels, n_to, -1),
            col_ids=_pad_to(self.col_ids, n_to, -1), n_cols=n_to,
        )

    def square_pad(self, n_to: int) -> "FeatureSource":
        """A symmetric source padded on both axes to ``n_to``: the padded
        columns, and as many rows, the same zero-feature points."""
        p = self.pad_cols(n_to)
        return dataclasses.replace(
            p, x=p.y, xx=p.yy, row_labels=p.col_labels, n_rows=n_to
        )

    def append_cols(self, y: jax.Array, yy: jax.Array) -> "FeatureSource":
        """New candidate columns (new points, id -1) after the existing
        ones; rows kept."""
        ids = self.col_ids
        if ids is not None:
            ids = jnp.concatenate([ids, jnp.full(yy.shape, -1, jnp.int32)])
        return dataclasses.replace(
            self, y=jnp.concatenate([self.y, y]), yy=jnp.concatenate([self.yy, yy]),
            col_ids=ids, n_cols=self.n_cols + int(y.shape[0]),
        )

    def append_points(self, x: jax.Array, xx: jax.Array) -> "FeatureSource":
        """A symmetric source grown by the points ``x`` (squared norms
        ``xx``), each a new row and its own column."""
        x = jnp.concatenate([self.x, x])
        xx = jnp.concatenate([self.xx, xx])
        n = int(x.shape[0])
        return dataclasses.replace(
            self, x=x, y=x, xx=xx, yy=xx, col_ids=jnp.arange(n, dtype=jnp.int32),
            n_rows=n, n_cols=n,
        )

    # -- source contract -----------------------------------------------------
    def col(self, j: jax.Array) -> jax.Array:
        """sim(i, j) for every row i, shape (n_rows,)."""
        safe = jnp.clip(j, 0, self.n_cols - 1)
        lt = None if self.col_labels is None else self.col_labels[safe][None]
        ct = None if self.col_ids is None else self.col_ids[safe][None]
        return self._sim_cols(self.y[safe][None], self.yy[safe][None], lt, ct)[:, 0]

    def col_sums(self) -> jax.Array:
        _, out = self._scan_cols(lambda c, s: (c, s.sum(axis=0)), None)
        return out.reshape(-1)[: self.n_cols]

    def diag(self) -> jax.Array:
        """sim(j, j) for square sources, computed metric-exactly (d2 = 0)."""
        if self.metric == "dot":
            return self.yy
        if self.metric == "cosine":
            # yy is the squared norm of the pre-normalized row: 1.0, or 0.0
            # for a zero-norm row (which similarity maps to the 0.5 midpoint)
            return 0.5 * (1.0 + self.yy)
        return jnp.ones((self.n_cols,), jnp.float32)

    def fl_gains(self, curmax: jax.Array) -> jax.Array:
        def per_tile(carry, s):
            return carry, _colsum(jnp.maximum(s - curmax[:, None], 0.0))

        _, out = self._scan_cols(per_tile, None)
        return out.reshape(-1)[: self.n_cols]

    def fl_gains_at(self, curmax: jax.Array, idx: jax.Array) -> jax.Array:
        # the gathered sub-source runs the SAME fixed-TILE scan as the full
        # sweep, so every similarity dot is computed at the same matmul
        # width — subset gains match the full sweep's bit-for-bit (a
        # width-k contraction can differ in the last ulps)
        g = self.take_cols(jnp.clip(idx, 0, self.n_cols - 1)).fl_gains(curmax)
        return jnp.where(idx >= 0, g, NEG_INF)

    def masked_rowmax(self, mask: jax.Array) -> jax.Array:
        mask_p = _pad_axis(mask.astype(bool), TILE, 0, value=False)
        nt = mask_p.shape[0] // TILE
        m_tiles = mask_p.reshape(nt, TILE)
        counter = jnp.zeros((), jnp.int32)  # rides the scan index

        def per_tile(carry, s):
            best, t = carry
            sel = jnp.where(m_tiles[t][None, :], s, 0.0)
            return (jnp.maximum(best, jnp.max(sel, axis=1, initial=0.0)), t + 1), None

        (best, _), _ = self._scan_cols(
            per_tile, (jnp.zeros((self.n_rows,), jnp.float32), counter)
        )
        return best

    def quad(self, mask: jax.Array) -> jax.Array:
        """m^T S m for square sources, streamed (GC evaluate oracle)."""
        m = mask.astype(jnp.float32)
        m_rows = m[: self.n_rows]
        mask_p = _pad_axis(m, TILE, 0)
        nt = mask_p.shape[0] // TILE
        m_tiles = mask_p.reshape(nt, TILE)

        def per_tile(carry, s):
            acc, t = carry
            v = (s * m_rows[:, None]).sum(axis=0)  # (tc,)
            return (acc + (v * m_tiles[t]).sum(), t + 1), None

        (acc, _), _ = self._scan_cols(per_tile, (jnp.zeros(()), jnp.zeros((), jnp.int32)))
        return acc


def feature_source(
    x,
    y=None,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    labels=None,
    col_labels=None,
) -> FeatureSource:
    """Build a :class:`FeatureSource` from raw feature rows.

    ``y=None`` builds the symmetric (square) source over ``x`` itself —
    the ground-set kernel shape Graph Cut and self-represented FL want.
    ``labels`` attaches clustered block-masking to the rows (and, for the
    symmetric case, the columns); ``col_labels`` overrides the column side.
    """
    x32 = jnp.asarray(x, jnp.float32)
    if metric == "cosine":
        x32 = x32 / jnp.maximum(jnp.linalg.norm(x32, axis=1, keepdims=True), 1e-12)
    xx = (x32 * x32).sum(axis=1)
    row_labels = None if labels is None else jnp.asarray(labels, jnp.int32)
    col_ids = None
    if y is None:
        y32, yy = x32, xx
        clab = row_labels if col_labels is None else jnp.asarray(col_labels, jnp.int32)
        col_ids = jnp.arange(x32.shape[0], dtype=jnp.int32)
    else:
        y32 = jnp.asarray(y, jnp.float32)
        if metric == "cosine":
            y32 = y32 / jnp.maximum(
                jnp.linalg.norm(y32, axis=1, keepdims=True), 1e-12
            )
        yy = (y32 * y32).sum(axis=1)
        clab = None if col_labels is None else jnp.asarray(col_labels, jnp.int32)
    if (row_labels is None) != (clab is None):
        raise ValueError("clustered sources need labels on both axes")
    return FeatureSource(
        x=x32,
        y=y32,
        xx=xx,
        yy=yy,
        row_labels=row_labels,
        col_labels=clab,
        col_ids=col_ids,
        metric=metric,
        rbf_sigma=rbf_sigma,
        d=int(x32.shape[1]),
        n_rows=int(x32.shape[0]),
        n_cols=int(y32.shape[0]),
    )


@pytree_dataclass(meta_fields=("n_rows", "n_cols", "k"))
class KnnSource:
    """Sparse k-NN similarity in padded CSR-ish form.

    Row i's neighbors are ``indices[i]`` (int32 column ids, -1 = empty pad
    slot) with similarities ``weights[i]`` (>= 0; pad slots are 0).
    sim(i, j) is ``weights[i, s]`` when ``indices[i, s] == j`` and exactly
    0 otherwise — the sparsified-matrix semantics of
    ``similarity.sparsify_topk``, never materialized.  FL sweeps are
    O(n * k) scatter-adds: off-neighborhood entries contribute
    max(0 - curmax, 0) = 0 exactly (curmax >= 0), so the sparse sweep IS
    the dense sweep over the sparsified matrix.
    """

    indices: jax.Array  # (n_rows, k) int32, -1 pads
    weights: jax.Array  # (n_rows, k) fp32 >= 0
    n_rows: int
    n_cols: int
    k: int

    def _live_w(self) -> jax.Array:
        return jnp.where(self.indices >= 0, self.weights, 0.0)

    def col(self, j: jax.Array) -> jax.Array:
        return jnp.where(self.indices == j, self.weights, 0.0).sum(axis=1)

    def col_sums(self) -> jax.Array:
        return (
            jnp.zeros((self.n_cols,), jnp.float32)
            .at[self.indices]
            .add(self._live_w(), mode="drop")
        )

    def diag(self) -> jax.Array:
        # square sources only (Graph Cut): sim(j, j) is the self-neighbor
        # weight when present, else exactly 0
        row_ids = jnp.arange(self.n_rows, dtype=jnp.int32)[:, None]
        d = jnp.where(self.indices == row_ids, self.weights, 0.0).sum(axis=1)
        if self.n_rows == self.n_cols:
            return d
        return jnp.zeros((self.n_cols,), jnp.float32).at[: self.n_rows].set(
            d[: self.n_cols]
        )

    def fl_gains(self, curmax: jax.Array) -> jax.Array:
        contrib = jnp.where(
            self.indices >= 0,
            jnp.maximum(self.weights - curmax[:, None], 0.0),
            0.0,
        )
        return (
            jnp.zeros((self.n_cols,), jnp.float32)
            .at[self.indices]
            .add(contrib, mode="drop")
        )

    def fl_gains_at(self, curmax: jax.Array, idx: jax.Array) -> jax.Array:
        full = self.fl_gains(curmax)
        safe = jnp.clip(idx, 0, self.n_cols - 1)
        return jnp.where(idx >= 0, full[safe], NEG_INF)

    def masked_rowmax(self, mask: jax.Array) -> jax.Array:
        safe = jnp.clip(self.indices, 0, self.n_cols - 1)
        live = (self.indices >= 0) & mask.astype(bool)[safe]
        return jnp.max(
            jnp.where(live, self.weights, 0.0), axis=1, initial=0.0
        )

    def quad(self, mask: jax.Array) -> jax.Array:
        m = mask.astype(jnp.float32)
        safe = jnp.clip(self.indices, 0, self.n_cols - 1)
        inner = (self._live_w() * m[safe]).sum(axis=1)  # (n_rows,)
        return (inner * m[: self.n_rows]).sum()

    def to_dense(self) -> jax.Array:
        """Materialize the sparsified matrix (tests / small-n interop)."""
        rows = jnp.broadcast_to(
            jnp.arange(self.n_rows, dtype=jnp.int32)[:, None], self.indices.shape
        )
        return (
            jnp.zeros((self.n_rows, self.n_cols), jnp.float32)
            .at[rows, self.indices]
            .add(self._live_w(), mode="drop")
        )


def knn_source(indices, weights, n_cols: int | None = None) -> KnnSource:
    indices = jnp.asarray(indices, jnp.int32)
    weights = jnp.asarray(weights, jnp.float32)
    if indices.shape != weights.shape or indices.ndim != 2:
        raise ValueError(
            f"indices/weights must both be (n, k); got {indices.shape} "
            f"vs {weights.shape}"
        )
    n_rows, k = indices.shape
    return KnnSource(
        indices=indices,
        weights=weights,
        n_rows=n_rows,
        n_cols=int(n_cols) if n_cols is not None else n_rows,
        k=k,
    )


def knn_from_features(
    x, k: int, metric: str = "dot", rbf_sigma: float | None = None,
    batch: int = 2048,
) -> KnnSource:
    """Top-k symmetric k-NN source from features, built in row batches so
    peak memory is O(batch * n), never the full (n, n) matrix."""
    src = feature_source(x, metric=metric, rbf_sigma=rbf_sigma)
    n = src.n_rows
    idx_out, w_out = [], []
    for lo in range(0, n, batch):
        block = dataclasses.replace(
            src,
            x=src.x[lo : lo + batch],
            xx=src.xx[lo : lo + batch],
            n_rows=min(batch, n - lo),
        )
        # column ids shifted so that local row i is global row lo + i
        sim = block._sim_cols(block.y, block.yy, None, src.col_ids - lo)  # (b, n)
        w, i = jax.lax.top_k(sim, k)
        idx_out.append(i.astype(jnp.int32))
        w_out.append(w)
    return knn_source(
        jnp.concatenate(idx_out, axis=0), jnp.concatenate(w_out, axis=0), n_cols=n
    )


@pytree_dataclass(meta_fields=("n_rows", "n_cols"))
class DenseSource:
    """The materialized matrix, riding the same source contract (so dense
    requests — and the existing fused Pallas sweeps — plug into the
    matrix-free families unchanged)."""

    sim: jax.Array  # (n_rows, n_cols)
    n_rows: int
    n_cols: int

    def col(self, j: jax.Array) -> jax.Array:
        return self.sim[:, j]

    def col_sums(self) -> jax.Array:
        return self.sim.sum(axis=0)

    def diag(self) -> jax.Array:
        return jnp.diagonal(self.sim)

    def fl_gains(self, curmax: jax.Array) -> jax.Array:
        # the Pallas FL kernel's order (FacilityLocation.gains does the same)
        return fl_gain_sums(self.sim, curmax)

    def fl_gains_at(self, curmax: jax.Array, idx: jax.Array) -> jax.Array:
        safe = jnp.clip(idx, 0, self.n_cols - 1)
        cols = jnp.take(self.sim, safe, axis=1)
        g = fl_gain_sums(cols, curmax)
        return jnp.where(idx >= 0, g, NEG_INF)

    def masked_rowmax(self, mask: jax.Array) -> jax.Array:
        masked = jnp.where(mask[None, :], self.sim, 0.0)
        return jnp.max(masked, axis=1, initial=0.0)

    def quad(self, mask: jax.Array) -> jax.Array:
        m = mask.astype(self.sim.dtype)
        return m[: self.n_rows] @ self.sim @ m


def dense_source(sim) -> DenseSource:
    sim = jnp.asarray(sim)
    return DenseSource(sim=sim, n_rows=int(sim.shape[0]), n_cols=int(sim.shape[1]))


SimilaritySource = FeatureSource | KnnSource | DenseSource

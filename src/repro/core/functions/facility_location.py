"""Facility Location:  f(A) = sum_{i in U} max_{j in A} S_ij   (paper §2.1.1).

U is the *represented* set (rows of S) which may differ from the ground set V
(columns of S).  Memoized statistic (paper Table 3): ``curmax_i = max_{j in A}
S_ij`` for every i in U; with it a gain query is one fused relu-reduction,
which we evaluate for ALL candidates at once (TPU adaptation, see DESIGN §2).

The per-step full-candidate gain sweep is the compute hotspot and is backed by
the Pallas kernel in ``repro.kernels.fl_gains`` when the matrix is large.

:class:`FacilityLocationMF` is the matrix-free variant: it holds a
:class:`~repro.core.sources.SimilaritySource` (features + metric, sparse
k-NN, or a dense matrix riding the same contract) instead of the
materialized (|U|, n) matrix, so n is bounded by feature bytes, not n^2.
Feature-backed sweeps route through the fused Pallas kernel in
``repro.kernels.flmf_gains`` (similarity computed in-stream).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import fl_gain_sums, pytree_dataclass
from repro.core.functions.base import SetFunction
from repro.core.sources import (
    DenseSource,
    FeatureSource,
    dense_source,
    feature_source,
    knn_source,
)


@pytree_dataclass(meta_fields=("n_rows",))
class FLState:
    curmax: jax.Array  # (n_rows,) max similarity of each represented point to A
    n_rows: int


class FLPallasSweep:
    """GainBackend: fused relu-reduce sweep over the similarity matrix (full
    and gathered-subset entry points; see kernels/fl_gains.py)."""

    name = "pallas-fl"

    def full_sweep(self, fn: "FacilityLocation", state: FLState) -> jax.Array:
        from repro.kernels import ops

        return ops.fl_gains(fn.sim, state.curmax)

    def partial_sweep(
        self, fn: "FacilityLocation", state: FLState, idx: jax.Array
    ) -> jax.Array:
        from repro.kernels import ops

        return ops.fl_gains_at(fn.sim, state.curmax, idx)


@pytree_dataclass(meta_fields=("n", "use_kernel"))
class FacilityLocation(SetFunction):
    sim: jax.Array  # (|U|, n) similarity, rows = represented set, cols = ground set
    n: int
    # True/False routes the gain sweeps through the Pallas kernel / XLA;
    # None defers to the trace-time choose_backend heuristic (backends.py)
    use_kernel: bool | None = False

    @staticmethod
    def from_kernel(
        sim: jax.Array, use_kernel: bool | None = False
    ) -> "FacilityLocation":
        sim = jnp.asarray(sim)
        return FacilityLocation(sim=sim, n=int(sim.shape[1]), use_kernel=use_kernel)

    def init_state(self) -> FLState:
        # f({}) = 0 with the standard convention max over empty set = 0
        # (requires S >= 0 for monotonicity; similarity.py guarantees this).
        return FLState(
            curmax=jnp.zeros((self.sim.shape[0],), self.sim.dtype),
            n_rows=int(self.sim.shape[0]),
        )

    def gains(self, state: FLState) -> jax.Array:
        if self.use_kernel:
            from repro.kernels import ops

            return ops.fl_gains(self.sim, state.curmax)
        # the Pallas kernel's fixed order, not .sum(0): bit-identical across
        # waves and backends (see tiled_sum)
        return fl_gain_sums(self.sim, state.curmax)

    def gain_backend(self) -> FLPallasSweep | None:
        from repro.core.optimizers.backends import kernel_enabled

        return FLPallasSweep() if kernel_enabled(self.use_kernel, self.n) else None

    def gains_at(self, state: FLState, idxs: jax.Array) -> jax.Array:
        cols = self.sim[:, idxs]  # (|U|, k)
        return fl_gain_sums(cols, state.curmax)

    def update(self, state: FLState, j: jax.Array) -> FLState:
        return FLState(
            curmax=jnp.maximum(state.curmax, self.sim[:, j]), n_rows=state.n_rows
        )

    def evaluate(self, mask: jax.Array) -> jax.Array:
        masked = jnp.where(mask[None, :], self.sim, 0.0)
        best = jnp.max(masked, axis=1, initial=0.0)
        return jnp.sum(best)

    def evaluate_state(self, state: FLState) -> jax.Array:
        return jnp.sum(state.curmax)


class FLMFPallasSweep:
    """GainBackend: matrix-free fused FL sweep — similarity computed
    in-stream from feature tiles (kernels/flmf_gains.py).  Dense sources
    reuse the materialized-matrix kernel (kernels/fl_gains.py)."""

    name = "pallas-flmf"

    def full_sweep(self, fn: "FacilityLocationMF", state: FLState) -> jax.Array:
        from repro.kernels import ops

        src = fn.src
        if isinstance(src, DenseSource):
            return ops.fl_gains(src.sim, state.curmax)
        return ops.flmf_gains(
            src.x, src.y, src.xx, src.yy, state.curmax,
            metric=src.metric, rbf_sigma=src.rbf_sigma, col_ids=src.col_ids,
        )

    def partial_sweep(
        self, fn: "FacilityLocationMF", state: FLState, idx: jax.Array
    ) -> jax.Array:
        from repro.kernels import ops

        src = fn.src
        if isinstance(src, DenseSource):
            return ops.fl_gains_at(src.sim, state.curmax, idx)
        return ops.flmf_gains_at(
            src.x, src.y, src.xx, src.yy, state.curmax, idx,
            metric=src.metric, rbf_sigma=src.rbf_sigma, col_ids=src.col_ids,
        )


@pytree_dataclass(meta_fields=("n", "use_kernel"))
class FacilityLocationMF(SetFunction):
    """Matrix-free Facility Location: same objective and memoized statistic
    as :class:`FacilityLocation`, but sim(i, j) is answered on demand by a
    :class:`~repro.core.sources.SimilaritySource` — the (|U|, n) matrix is
    never written.  Peak memory is O(n * d) feature bytes (or O(n * k)
    sparse entries), which is what unlocks n >= 10^6 selection."""

    src: object  # SimilaritySource (FeatureSource | KnnSource | DenseSource)
    n: int
    # True/False routes sweeps through the fused Pallas kernel / XLA; None
    # defers to the trace-time choose_backend heuristic (backends.py)
    use_kernel: bool | None = False

    @staticmethod
    def from_features(
        x,
        y=None,
        metric: str = "dot",
        rbf_sigma: float | None = None,
        labels=None,
        use_kernel: bool | None = False,
    ) -> "FacilityLocationMF":
        """FL over features + metric.  ``y`` is the candidate (column) side
        and defaults to ``x`` itself; ``labels`` switches on the clustered
        block-masked similarity (paper §8), streamed."""
        src = feature_source(x, y, metric=metric, rbf_sigma=rbf_sigma, labels=labels)
        return FacilityLocationMF(src=src, n=src.n_cols, use_kernel=use_kernel)

    @staticmethod
    def from_knn(
        indices, weights, n_cols: int | None = None,
        use_kernel: bool | None = False,
    ) -> "FacilityLocationMF":
        """FL over precomputed sparse k-NN similarity (indices (n, k) int32
        with -1 pads, nonnegative weights)."""
        src = knn_source(indices, weights, n_cols=n_cols)
        return FacilityLocationMF(src=src, n=src.n_cols, use_kernel=use_kernel)

    @staticmethod
    def from_dense(sim, use_kernel: bool | None = False) -> "FacilityLocationMF":
        """Dense matrix riding the matrix-free contract (interop/testing)."""
        src = dense_source(sim)
        return FacilityLocationMF(src=src, n=src.n_cols, use_kernel=use_kernel)

    def init_state(self) -> FLState:
        return FLState(
            curmax=jnp.zeros((self.src.n_rows,), jnp.float32),
            n_rows=self.src.n_rows,
        )

    def gains(self, state: FLState) -> jax.Array:
        return self.src.fl_gains(state.curmax)

    def gains_at(self, state: FLState, idxs: jax.Array) -> jax.Array:
        return self.src.fl_gains_at(state.curmax, idxs)

    def gain_backend(self) -> FLMFPallasSweep | None:
        from repro.core.optimizers.backends import kernel_enabled

        if not kernel_enabled(self.use_kernel, self.n, matrix_free=True):
            return None
        src = self.src
        if isinstance(src, FeatureSource) and src.col_labels is None:
            return FLMFPallasSweep()
        if isinstance(src, DenseSource):
            return FLMFPallasSweep()
        return None  # k-NN / clustered sources stay on the XLA scatter path

    def update(self, state: FLState, j: jax.Array) -> FLState:
        return FLState(
            curmax=jnp.maximum(state.curmax, self.src.col(j)),
            n_rows=state.n_rows,
        )

    def evaluate(self, mask: jax.Array) -> jax.Array:
        return jnp.sum(self.src.masked_rowmax(mask))

    def evaluate_state(self, state: FLState) -> jax.Array:
        return jnp.sum(state.curmax)

"""Similarity / distance kernel creation (paper §8 "usage patterns").

Modes
-----
dense      : full (n_rows, n_cols) kernel — the O(n^2 d) hotspot (paper
             Table 5); routed through the Pallas MXU kernel when requested.
sparse     : fixed top-k neighbour layout — similarity beyond the k nearest
             neighbours is zeroed (paper's sparse mode, TPU-friendly dense
             top-k rather than CSR; DESIGN §8.2).
clustered  : see functions/clustered.py.

Metrics: ``dot``, ``cosine`` (shifted to [0,1]), ``euclidean`` (similarity
1/(1+d)), ``rbf``.  All produced similarities are non-negative, which the
monotone functions (FL) require.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import DISTANCE_METRICS

_METRICS = ("dot", "cosine", "euclidean", "rbf")


def _matmul_t(x: jax.Array, y: jax.Array) -> jax.Array:
    """x @ y.T in full float32.  A bare ``@`` runs one bf16 pass on a TPU
    (about 4e-3 relative error), enough to reorder near-tied greedy gains."""
    return jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)


def pairwise_sq_dists(x: jax.Array, y: jax.Array) -> jax.Array:
    xx = jnp.sum(x * x, axis=1)[:, None]
    yy = jnp.sum(y * y, axis=1)[None, :]
    d2 = xx + yy - 2.0 * _matmul_t(x, y)
    return jnp.maximum(d2, 0.0)


def create_kernel(
    x: jax.Array,
    y: jax.Array | None = None,
    metric: str = "cosine",
    mode: str = "dense",
    num_neighbors: int | None = None,
    rbf_sigma: float | None = None,
    use_pallas: bool = False,
) -> jax.Array:
    """Similarity kernel S of shape (n_x, n_y); ``y`` defaults to ``x``.

    Rows are the *represented* set, columns the ground set, matching the
    paper's U-vs-V distinction.
    """
    if metric not in _METRICS:
        raise ValueError(f"unknown metric {metric!r}; choose from {_METRICS}")
    square = y is None
    x = jnp.asarray(x)
    y = x if square else jnp.asarray(y)

    if use_pallas:
        from repro.kernels import ops

        sim = ops.similarity(x, y, metric=metric, rbf_sigma=rbf_sigma)
    else:
        sim = _reference_kernel(x, y, metric, rbf_sigma)
    if square and metric in DISTANCE_METRICS:
        # a point is at distance 0 from itself: similarity 1 exactly, not
        # the float32 residue of xx + yy - 2 x.y (repro.common.exact_self_distance)
        sim = jnp.fill_diagonal(sim, 1.0, inplace=False)

    if mode == "dense":
        return sim
    if mode == "sparse":
        if num_neighbors is None:
            raise ValueError("sparse mode requires num_neighbors")
        return sparsify_topk(sim, num_neighbors)
    raise ValueError(f"unknown mode {mode!r} (clustered mode lives in functions/clustered.py)")


def _reference_kernel(x, y, metric, rbf_sigma):
    if metric == "dot":
        return _matmul_t(x, y)
    if metric == "cosine":
        xn = x / jnp.maximum(jnp.linalg.norm(x, axis=1, keepdims=True), 1e-12)
        yn = y / jnp.maximum(jnp.linalg.norm(y, axis=1, keepdims=True), 1e-12)
        return 0.5 * (1.0 + _matmul_t(xn, yn))  # shift to [0, 1]
    d2 = pairwise_sq_dists(x, y)
    if metric == "euclidean":
        return 1.0 / (1.0 + jnp.sqrt(d2))
    sigma = rbf_sigma if rbf_sigma is not None else float(x.shape[1]) ** 0.5
    return jnp.exp(-d2 / (2.0 * sigma * sigma))


def sparsify_topk(sim: jax.Array, k: int) -> jax.Array:
    """Keep the k largest entries per row (incl. self), zero the rest."""
    k = min(k, sim.shape[1])
    thresh = jax.lax.top_k(sim, k)[0][:, -1]
    return jnp.where(sim >= thresh[:, None], sim, 0.0)


def kmeans(
    x: jax.Array, k: int, iters: int = 25, key: jax.Array | None = None
) -> jax.Array:
    """Small k-means (labels only) for the internal-clustering option."""
    key = jax.random.PRNGKey(0) if key is None else key
    init = jax.random.choice(key, x.shape[0], (k,), replace=False)
    cents = x[init]

    def step(cents, _):
        d2 = pairwise_sq_dists(x, cents)
        lab = jnp.argmin(d2, axis=1)
        one = jax.nn.one_hot(lab, k, dtype=x.dtype)
        counts = jnp.maximum(one.sum(0)[:, None], 1.0)
        cents = (one.T @ x) / counts
        return cents, None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return jnp.argmin(pairwise_sq_dists(x, cents), axis=1)


def build_extended_kernel(
    ground: jax.Array,
    query: jax.Array | None = None,
    private: jax.Array | None = None,
    metric: str = "cosine",
    eta: float = 1.0,
    nu: float = 1.0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel over V ∪ Q ∪ P with η/ν cross-block scaling (paper §3.4).

    Returns (kernel, q_idx, p_idx); V occupies indices [0, n_v).
    Cross-similarity V<->Q is scaled by η and V<->P by ν, exactly the
    S^{η,ν} construction used by the LogDet information measures.
    """
    parts = [jnp.asarray(ground)]
    n_v = parts[0].shape[0]
    q_idx = jnp.arange(0)
    p_idx = jnp.arange(0)
    if query is not None:
        query = jnp.asarray(query)
        q_idx = jnp.arange(n_v, n_v + query.shape[0])
        parts.append(query)
    if private is not None:
        private = jnp.asarray(private)
        start = n_v + (query.shape[0] if query is not None else 0)
        p_idx = jnp.arange(start, start + private.shape[0])
        parts.append(private)
    allpts = jnp.concatenate(parts, axis=0)
    S = create_kernel(allpts, metric=metric)
    scale = jnp.ones((allpts.shape[0],))
    if query is not None:
        scale = scale.at[q_idx].set(jnp.sqrt(eta) if eta >= 0 else 1.0)
    if private is not None:
        scale = scale.at[p_idx].set(jnp.sqrt(nu) if nu >= 0 else 1.0)
    # symmetric scaling keeps PSD-ness for LogDet: S' = D S D with D diagonal
    S = S * scale[:, None] * scale[None, :]
    # restore untouched diagonal blocks (V-V, Q-Q, P-P keep base similarity)
    grp = jnp.zeros((allpts.shape[0],), jnp.int32)
    grp = grp.at[q_idx].set(1).at[p_idx].set(2)
    same = grp[:, None] == grp[None, :]
    S_base = create_kernel(allpts, metric=metric)
    return jnp.where(same, S_base, S), q_idx, p_idx

"""Feature-Based function (paper §2.3.3):

  f(A) = sum_{f in F} w_f * g(m_f(A)),   m_f(A) = sum_{x in A} m_f(x)

with g concave in {sqrt, log, inverse}.  Memoized statistic (Table 3): the
accumulated modular feature vector m_f(A).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import get_concave, pytree_dataclass, tiled_sum
from repro.core.functions.base import SetFunction


@pytree_dataclass
class FBState:
    acc: jax.Array  # (F,) accumulated feature mass m_f(A)


class FBPallasSweep:
    """GainBackend: fused add -> concave -> weighted-reduce over the feature
    matrix, streamed tile-wise (no (n, F) concave intermediate in HBM)."""

    name = "pallas-fb"

    def full_sweep(self, fn: "FeatureBased", state: FBState) -> jax.Array:
        from repro.kernels import ops

        return ops.fb_gains(fn.feats, state.acc, fn.w, fn.concave)

    def partial_sweep(
        self, fn: "FeatureBased", state: FBState, idx: jax.Array
    ) -> jax.Array:
        from repro.kernels import ops

        return ops.fb_gains_at(fn.feats, state.acc, fn.w, idx, fn.concave)


@pytree_dataclass(meta_fields=("n", "concave", "use_kernel"))
class FeatureBased(SetFunction):
    feats: jax.Array  # (n, F) non-negative feature scores
    w: jax.Array  # (F,)
    n: int
    concave: str = "sqrt"
    # True/False routes sweeps through the Pallas kernel / XLA; None defers
    # to the trace-time choose_backend heuristic (backends.py)
    use_kernel: bool | None = False

    @staticmethod
    def from_features(
        feats: jax.Array,
        w: jax.Array | None = None,
        concave: str = "sqrt",
        use_kernel: bool | None = False,
    ) -> "FeatureBased":
        feats = jnp.maximum(jnp.asarray(feats, jnp.float32), 0.0)
        F = feats.shape[1]
        w = jnp.ones((F,), jnp.float32) if w is None else jnp.asarray(w, jnp.float32)
        get_concave(concave)  # validate
        return FeatureBased(
            feats=feats,
            w=w,
            n=int(feats.shape[0]),
            concave=concave,
            use_kernel=use_kernel,
        )

    def init_state(self) -> FBState:
        return FBState(acc=jnp.zeros((self.feats.shape[1],), jnp.float32))

    def _gain_sums(self, feats: jax.Array, acc: jax.Array) -> jax.Array:
        # elementwise-multiply + the Pallas kernel's fixed add order rather
        # than `@ w` or `.sum(-1)`: both lower to shape-dependent
        # accumulation orders (see tiled_sum), which would break served ==
        # sequential and Pallas == XLA bit-identity.
        g = get_concave(self.concave)
        return tiled_sum(
            lambda f, a, w: (g(a + f) - g(a)) * w,
            (feats, acc, self.w), (1, 0, 0), 1,
        )

    def gains(self, state: FBState) -> jax.Array:
        return self._gain_sums(self.feats, state.acc)

    def gains_at(self, state: FBState, idxs: jax.Array) -> jax.Array:
        return self._gain_sums(self.feats[idxs], state.acc)

    def update(self, state: FBState, j: jax.Array) -> FBState:
        return FBState(acc=state.acc + self.feats[j])

    def gain_backend(self) -> FBPallasSweep | None:
        from repro.core.optimizers.backends import kernel_enabled

        return FBPallasSweep() if kernel_enabled(self.use_kernel, self.n) else None

    def evaluate(self, mask: jax.Array) -> jax.Array:
        g = get_concave(self.concave)
        acc = jnp.where(mask[:, None], self.feats, 0.0).sum(axis=0)
        return jnp.dot(self.w, g(acc))

    def evaluate_state(self, state: FBState) -> jax.Array:
        g = get_concave(self.concave)
        return jnp.dot(self.w, g(state.acc))

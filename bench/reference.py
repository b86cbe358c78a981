"""The plain reference: facility-location greedy in float32, written here
and importing nothing of the program.

It follows ``chip_smoke.py``'s reference, copied so that later edits to the
smoke test cannot move the yardstick.  The similarity is computed from the
embeddings as one whole matmul at full precision, the gains of every
candidate are ``sum_i max(S_ij - curmax_i, 0)`` summed by XLA, and the
greedy takes the first argmax.  ``replay`` holds a served selection to it:
at each step it gives the reference's best gain and the gain of the pick.

``precision`` names how the similarity is computed:

- ``highest``: float32 products (the configurations' stated precision);
- ``high``: three bf16 passes (``hi*hi + hi*lo + lo*hi``), what a TPU does
  at ``Precision.HIGH``, written out so that it does the same on any device;
- ``bfloat16``: the ``highest`` similarity stored in bfloat16.

The last two are the controls: what a lower precision would make of the
same requests.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "bfloat16")
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(a: jax.Array) -> jax.Array:
    """``a`` rounded to bfloat16, kept in float32.  A cast there and back
    may be folded away by XLA (excess precision); this rounding may not."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _dot(x: jax.Array, y: jax.Array, precision: str) -> jax.Array:
    """x @ y.T in the named precision."""
    if precision == "high":
        xh, yh = _bf16(x), _bf16(y)
        xl, yl = _bf16(x - xh), _bf16(y - yh)
        # products of bf16 values are exact in float32
        return (
            jnp.matmul(xh, yh.T, precision=HIGHEST)
            + jnp.matmul(xh, yl.T, precision=HIGHEST)
            + jnp.matmul(xl, yh.T, precision=HIGHEST)
        )
    return jnp.matmul(x, y.T, precision=HIGHEST)


@partial(jax.jit, static_argnames=("metric", "precision"))
def kernel(x: jax.Array, metric: str, precision: str = "highest") -> jax.Array:
    """The (n, n) similarity of one group's rows.  A point is at euclidean
    distance 0 from itself by definition (in float32, ``xx + yy - 2 x.y``
    leaves a residue there that the root blows up)."""
    if metric == "euclidean":
        sq = jnp.sum(x * x, 1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * _dot(x, x, precision)
        d2 = jnp.where(jnp.eye(x.shape[0], dtype=bool), 0.0, jnp.maximum(d2, 0.0))
        s = 1.0 / (1.0 + jnp.sqrt(d2))
    elif metric == "cosine":
        xn = x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, 1, keepdims=True)), 1e-12)
        s = 0.5 * (1.0 + _dot(xn, xn, precision))
    else:
        raise ValueError(f"unknown metric {metric!r}")
    return _bf16(s) if precision == "bfloat16" else s


def _gains(S: jax.Array, curmax: jax.Array) -> jax.Array:
    return jnp.sum(jnp.maximum(S - curmax[:, None], 0.0), axis=0)


@jax.jit
def replay(S: jax.Array, picks: jax.Array):
    """Replay ``picks`` under the reference: per step the best gain of any
    candidate given ``picks[:t]`` and the gain of ``picks[t]``; and
    f(picks)."""

    def step(curmax, j):
        g = _gains(S, curmax)
        return jnp.maximum(curmax, S[:, j]), (jnp.max(g), g[j])

    curmax, (top, picked) = jax.lax.scan(step, jnp.zeros((S.shape[0],), jnp.float32), picks)
    return top, picked, jnp.sum(curmax)


@partial(jax.jit, static_argnames="k")
def greedy(S: jax.Array, k: int):
    """Naive greedy under the reference: (picks, gains), each (k,)."""

    def step(curmax, _):
        g = _gains(S, curmax)
        j = jnp.argmax(g)
        return jnp.maximum(curmax, S[:, j]), (j, g[j])

    return jax.lax.scan(step, jnp.zeros((S.shape[0],), jnp.float32), None, length=k)[1]


def compare(S: jax.Array, picks, gains, budget: int) -> dict:
    """Hold one served selection (``picks`` with their reported ``gains``)
    to the reference similarity ``S``.

    - ``invalid``: picks missing, repeated or out of range (an exact count);
    - ``shortfall``: largest (best gain - gain of the pick) / best gain, over
      the steps: 0 where the pick is the reference's argmax, small where it
      is a near tie, large where a clearly better candidate was passed over;
    - ``gain_err``: largest |reported gain - reference gain of the pick| /
      best gain, over the steps;
    - ``gain_err_f``: the same gap over f(picks), a scale that does not
      shrink as the greedy's gains do;
    - ``objective_rel``: |sum of reported gains - f(picks)| / f(picks).
    """
    n = S.shape[1]
    picks = np.asarray(picks, np.int64)
    gains = np.asarray(gains, np.float64)
    ok = (picks >= 0) & (picks < n)
    invalid = int(budget - len(picks)) + int(np.sum(~ok))
    valid = picks[ok]
    invalid += len(valid) - len(np.unique(valid))
    if invalid:
        return {"invalid": invalid, "shortfall": 1.0, "gain_err": 1.0, "gain_err_f": 1.0, "objective_rel": 1.0}
    top, picked, f = (np.asarray(a, np.float64) for a in jax.device_get(replay(S, jnp.asarray(picks, jnp.int32))))
    scale = np.maximum(np.abs(top), np.finfo(np.float32).tiny)
    return {
        "invalid": 0,
        "shortfall": float(np.max((top - picked) / scale)),
        "gain_err": float(np.max(np.abs(gains - picked) / scale)),
        "gain_err_f": float(np.max(np.abs(gains - picked)) / abs(f)),
        "objective_rel": float(abs(np.sum(gains) - f) / abs(f)),
    }


def worst(readings: list[dict]) -> dict:
    """The numbers of many compared selections, each at its worst."""
    if not readings:
        return {}
    return {
        k: (sum(r[k] for r in readings) if k == "invalid" else max(r[k] for r in readings))
        for k in readings[0]
    }

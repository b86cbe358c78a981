"""The plain reference: facility-location greedy in float32, written here
and importing nothing of the program.

It follows ``chip_smoke.py``'s reference, copied so that later edits to the
smoke test cannot move the yardstick.  The similarity is computed from the
embeddings by matmuls at full precision, the gains of every candidate are
``sum_i max(S_ij - curmax_i, 0)`` summed by XLA, and the greedy takes the
first argmax.  ``replay`` holds a served selection to it: at each step it
gives the reference's best gain and the gain of the pick.

No program here holds a group's whole (n, n) similarity.  It is made one
block of ``block_rows(n)`` rows at a time, at most ``BLOCK_BYTES``: every
row keeps a curmax of its own and every gain is a sum over rows, so a block
replays alone and adds its share of each step's gains.  A group of up to
16,384 points is one block, whose programs compute what the whole matrix
would.

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
BLOCK_BYTES = 1 << 30  # float32 similarity one block of rows may hold


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


def block_rows(n: int) -> int:
    """Rows of a group's (n, n) similarity that one block holds: all n where
    the whole fits in ``BLOCK_BYTES`` of float32, else the largest multiple
    of 8 that does (5,368 at n 50,000)."""
    if n * n * 4 <= BLOCK_BYTES:
        return n
    return BLOCK_BYTES // (4 * n) // 8 * 8


def _starts(n: int, rows: int) -> tuple[np.ndarray, np.ndarray]:
    """(start, first) of every block.  A block covers rows ``start`` to
    ``start + rows``; the last one is moved back to end at n, and its rows
    before ``first`` are the previous block's, so it zeroes them."""
    firsts = np.arange(0, n, rows, dtype=np.int32)
    return np.minimum(firsts, n - rows), firsts


def _block(x: jax.Array, start, first, rows: int, metric: str, precision: str) -> jax.Array:
    """Rows ``start`` to ``start + rows`` of the (rows, n) similarity of one
    group's points, rows before ``first`` zeroed.  A point is at euclidean
    distance 0 from itself by definition (in float32, ``xx + yy - 2 x.y``
    leaves a residue there that the root blows up)."""
    n = x.shape[0]
    i = start + jnp.arange(rows)
    if metric == "euclidean":
        sq = jnp.sum(x * x, 1)
        xb = jax.lax.dynamic_slice_in_dim(x, start, rows)
        d2 = jax.lax.dynamic_slice_in_dim(sq, start, rows)[:, None] + sq[None, :] - 2.0 * _dot(xb, x, precision)
        d2 = jnp.where(i[:, None] == jnp.arange(n)[None, :], 0.0, jnp.maximum(d2, 0.0))
        s = 1.0 / (1.0 + jnp.sqrt(d2))
    elif metric == "cosine":
        xn = x / jnp.maximum(jnp.sqrt(jnp.sum(x * x, 1, keepdims=True)), 1e-12)
        s = 0.5 * (1.0 + _dot(jax.lax.dynamic_slice_in_dim(xn, start, rows), xn, precision))
    else:
        raise ValueError(f"unknown metric {metric!r}")
    s = _bf16(s) if precision == "bfloat16" else s
    return jnp.where((i >= first)[:, None], s, 0.0)


def _gains(S: jax.Array, curmax: jax.Array) -> jax.Array:
    return jnp.sum(jnp.maximum(S - curmax[:, None], 0.0), axis=0)


@partial(jax.jit, static_argnames=("metric", "rows"))
def _replay(x: jax.Array, picks: jax.Array, metric: str, rows: int):
    n, k = x.shape[0], picks.shape[0]

    def block(acc, b):
        S = _block(x, *b, rows, metric, "highest")

        def step(curmax, j):
            return jnp.maximum(curmax, S[:, j]), _gains(S, curmax)

        curmax, g = jax.lax.scan(step, jnp.zeros((rows,), jnp.float32), picks)
        return (acc[0] + g, acc[1] + jnp.sum(curmax)), None

    init = (jnp.zeros((k, n), jnp.float32), jnp.zeros((), jnp.float32))
    (g, f), _ = jax.lax.scan(block, init, _starts(n, rows))
    return jnp.max(g, 1), g[jnp.arange(k), picks], f


def replay(x: jax.Array, metric: str, picks):
    """Replay ``picks`` under the reference, one block of rows at a time:
    per step the best gain of any candidate given ``picks[:t]`` and the gain
    of ``picks[t]``; and f(picks).  Rows never interact, so each block
    replays alone and its share of every step's gains is summed."""
    return _replay(x, jnp.asarray(picks, jnp.int32), metric, block_rows(x.shape[0]))


@partial(jax.jit, static_argnames=("metric", "k", "precision", "rows"))
def _greedy(x: jax.Array, metric: str, k: int, precision: str, rows: int):
    n = x.shape[0]
    starts = _starts(n, rows)
    if rows == n:  # the whole group is one block: build it once
        whole = _block(x, 0, 0, n, metric, precision)
        block = lambda start, first: whole  # noqa: E731
    else:  # every step builds each block again
        block = partial(_block, x, rows=rows, metric=metric, precision=precision)

    def step(carry, _):
        curmax, j = carry  # per block; the previous pick, -1 before the first

        def visit(g, b):
            start, first, cm = b
            S = block(start, first)
            cm = jnp.where(j < 0, cm, jnp.maximum(cm, S[:, j]))
            return g + _gains(S, cm), cm

        g, curmax = jax.lax.scan(visit, jnp.zeros((n,), jnp.float32), (*starts, curmax))
        j = jnp.argmax(g)
        return (curmax, j), (j, g[j])

    init = (jnp.zeros((len(starts[0]), rows), jnp.float32), jnp.int32(-1))
    return jax.lax.scan(step, init, None, length=k)[1]


def greedy(x: jax.Array, metric: str, k: int, precision: str = "highest"):
    """Naive greedy under the reference, its similarity computed in
    ``precision``: (picks, gains), each (k,).  Every step's argmax needs
    every block's gains, so a group of more than one block builds each
    block again at every step."""
    return _greedy(x, metric, k, precision, block_rows(x.shape[0]))


def compare(x: jax.Array, metric: str, picks, gains, budget: int) -> dict:
    """Hold one served selection (``picks`` with their reported ``gains``)
    to the reference similarity of the group's points ``x`` under
    ``metric``.

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
    n = x.shape[0]
    picks = np.asarray(picks, np.int64)
    gains = np.asarray(gains, np.float64)
    ok = (picks >= 0) & (picks < n)
    invalid = int(budget - len(picks)) + int(np.sum(~ok))
    valid = picks[ok]
    invalid += len(valid) - len(np.unique(valid))
    if invalid:
        return {"invalid": invalid, "shortfall": 1.0, "gain_err": 1.0, "gain_err_f": 1.0, "objective_rel": 1.0}
    top, picked, f = (np.asarray(a, np.float64) for a in jax.device_get(replay(x, metric, picks)))
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

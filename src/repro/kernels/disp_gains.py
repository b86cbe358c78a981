"""Pallas TPU kernels: fused disparity (dispersion) gain sweeps.

Both kernels recompute the sweep FROM THE SELECTION MASK in one streamed
pass over the (n, n) distance matrix — the stateless serving shape (no
memoized per-query state resident), mirroring ``gc_gains.py``:

  DisparitySum   gains_j = sum_k d_jk * m_k          (masked matvec)
  DisparityMin   gains_j = min(surr_j, BIG) - f(A),
                 surr_j  = 0 if |A| = 0 else min_{k in A} d_jk
                 (masked min — the Dasgupta et al. farthest-point surrogate,
                  see core/functions/disparity.py)

Each (BJ x BK) tile of D streams through VMEM exactly once; the (1, BJ)
output block accumulates over the K strips (sum for DisparitySum, min for
DisparityMin) and DisparityMin finalizes with the |A|-conditional and the
current-dispersion subtraction on the last strip (|A| and f(A) ride in SMEM).

grid = (n/BJ, n/BK) with K innermost.  Zero row/column padding is exact:
padded candidates read only masked-out columns (sum adds 0 * m, min keeps
BIG), and real candidates never see a padded column selected.

Note DisparityMin's masked min is order-independent and float-exact, so this
stateless sweep reproduces the memoized ``mind`` statistic bit-for-bit; the
DisparitySum sum is a different reduction order than the incrementally
accumulated ``selsum`` and matches to ulps only (see the use_kernel notes in
``core/functions/disparity.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BJ = 256  # candidate columns of the output per tile
BK = 256  # summed/minimized-over ground elements per tile

_BIG = 1e30  # matches core/functions/disparity.py


def _dsum_kernel(d_ref, m_ref, out_ref):
    kblk = pl.program_id(1)

    @pl.when(kblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = d_ref[...].astype(jnp.float32)  # (BJ, BK) rows j = candidates
    m = m_ref[...].astype(jnp.float32)  # (1, BK) selection indicator
    out_ref[...] += (d * m).sum(axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "bj", "bk"))
def dsum_gains_pallas(
    dist: jax.Array,
    selmask: jax.Array,
    interpret: bool = False,
    bj: int = BJ,
    bk: int = BK,
) -> jax.Array:
    """dist (n, n) pairwise distances, selmask (n,) 0/1 selection indicator
    -> DisparitySum gains (n,) fp32."""
    n = dist.shape[0]
    pad_j = (-n) % bj
    pad_k = (-n) % bk
    dp = jnp.pad(dist, ((0, pad_j), (0, pad_k)))
    mp = jnp.pad(selmask.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    npj, npk = dp.shape
    out = pl.pallas_call(
        _dsum_kernel,
        grid=(npj // bj, npk // bk),
        in_specs=[
            pl.BlockSpec((bj, bk), lambda j, k: (j, k)),
            pl.BlockSpec((1, bk), lambda j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npj), jnp.float32),
        interpret=interpret,
        name="dsum_gains",  # the compiled custom call's name, read by the trace
    )(dp, mp)
    return out[0, :n]


def _dmin_kernel(cnt_ref, cur_ref, d_ref, m_ref, out_ref, *, nk):
    kblk = pl.program_id(1)

    @pl.when(kblk == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _BIG)

    d = d_ref[...].astype(jnp.float32)  # (BJ, BK)
    m = m_ref[...].astype(jnp.float32)  # (1, BK)
    vals = jnp.where(m > 0.0, d, _BIG)  # unselected columns drop out of the min
    out_ref[...] = jnp.minimum(out_ref[...], vals.min(axis=1)[None, :])

    @pl.when(kblk == nk - 1)
    def _finalize():
        count = cnt_ref[0]
        curmin = cur_ref[0]
        surrogate = jnp.where(count == 0, 0.0, out_ref[...])
        out_ref[...] = jnp.minimum(surrogate, _BIG) - curmin


@functools.partial(jax.jit, static_argnames=("interpret", "bj", "bk"))
def dmin_gains_pallas(
    dist: jax.Array,
    selmask: jax.Array,
    count: jax.Array,
    curmin: jax.Array,
    interpret: bool = False,
    bj: int = BJ,
    bk: int = BK,
) -> jax.Array:
    """dist (n, n), selmask (n,) 0/1 indicator, count scalar |A|, curmin
    scalar f(A) -> DisparityMin surrogate gains (n,) fp32."""
    n = dist.shape[0]
    pad_j = (-n) % bj
    pad_k = (-n) % bk
    dp = jnp.pad(dist, ((0, pad_j), (0, pad_k)))
    mp = jnp.pad(selmask.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    npj, npk = dp.shape
    nk = npk // bk
    cnt = jnp.asarray(count, jnp.int32).reshape((1,))
    cur = jnp.asarray(curmin, jnp.float32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_dmin_kernel, nk=nk),
        grid=(npj // bj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bj, bk), lambda j, k: (j, k)),
            pl.BlockSpec((1, bk), lambda j, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npj), jnp.float32),
        interpret=interpret,
        name="dmin_gains",  # the compiled custom call's name, read by the trace
    )(cnt, cur, dp, mp)
    return out[0, :n]

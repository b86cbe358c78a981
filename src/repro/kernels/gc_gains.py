"""Pallas TPU kernel: fused graph-cut marginal-gain sweep.

For Graph Cut (paper §2.1.2) the marginal gain of candidate j given the
selection indicator m (m_k = 1 iff k in A) is

    gains_j = total_j - lam * (2 * selsum_j + S_jj),
    selsum_j = sum_k S_jk * m_k

This kernel recomputes the sweep FROM THE SELECTION MASK in one fused pass:
each (BJ x BK) tile of S streams through VMEM exactly once, contributing
sum_k S_jk * (2*m_k + [j == k])  (masked matvec + diagonal extraction) to a
(1, BJ) accumulator that is finalized to  total - lam * acc  on the last K
strip.  grid = (n/BJ, n/BK) with K innermost; ``lam`` rides along in SMEM.

Trade-off vs the memoized path: GraphCut's incremental ``selsum`` statistic
makes a gain sweep O(n) elementwise, which is cheaper inside a greedy loop
that updates state every step.  This kernel is O(n^2) streamed once, but
STATELESS — it answers a sweep from just (S, mask), which is the shape
one-shot scoring and serving paths want (no per-query memoized state to
keep resident).  See GraphCut.gain_backend for routing.

``gc_gains_at_pallas`` is the masked-subset entry point (the lazy engines'
``partial_sweep`` contract): an XLA gather of the K requested kernel ROWS
feeds the same masked-matvec tile stream, with the rows' GLOBAL indices
riding along so the in-stream ``[j == k]`` diagonal fold — and therefore the
per-row accumulation order, and the floats — match the full sweep exactly.
Slots with idx < 0 are padding and return NEG_INF.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import NEG_INF

BJ = 256  # candidate columns of the output per tile
BK = 256  # summed-over ground elements per tile


def _gc_kernel(lam_ref, s_ref, m_ref, tot_ref, out_ref, *, nk, bj, bk):
    jblk = pl.program_id(0)
    kblk = pl.program_id(1)

    @pl.when(kblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.float32)  # (BJ, BK) rows j, cols k
    m = m_ref[...].astype(jnp.float32)  # (1, BK)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bj, bk), 0) + jblk * bj
    cols = jax.lax.broadcasted_iota(jnp.int32, (bj, bk), 1) + kblk * bk
    w = 2.0 * m + jnp.where(rows == cols, 1.0, 0.0)  # (BJ, BK)
    out_ref[...] += (s * w).sum(axis=1)[None, :]

    @pl.when(kblk == nk - 1)
    def _finalize():
        lam = lam_ref[0]
        tot = tot_ref[...].astype(jnp.float32)  # (1, BJ)
        out_ref[...] = tot - lam * out_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bj", "bk"))
def gc_gains_pallas(
    sim: jax.Array,
    selmask: jax.Array,
    total: jax.Array,
    lam: jax.Array,
    interpret: bool = False,
    bj: int = BJ,
    bk: int = BK,
) -> jax.Array:
    """sim (n, n) ground kernel, selmask (n,) 0/1 selection indicator,
    total (n,) modular representation term, lam scalar -> gains (n,) fp32."""
    n = sim.shape[0]
    pad_j = (-n) % bj
    pad_k = (-n) % bk
    sp = jnp.pad(sim, ((0, pad_j), (0, pad_k)))
    mp = jnp.pad(selmask.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    tp = jnp.pad(total.astype(jnp.float32)[None, :], ((0, 0), (0, pad_j)))
    npj, npk = sp.shape
    nk = npk // bk
    lam_s = jnp.asarray(lam, jnp.float32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_gc_kernel, nk=nk, bj=bj, bk=bk),
        grid=(npj // bj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bj, bk), lambda j, k: (j, k)),
            pl.BlockSpec((1, bk), lambda j, k: (0, k)),
            pl.BlockSpec((1, bj), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npj), jnp.float32),
        interpret=interpret,
        name="gc_gains",  # the compiled custom call's name, read by the trace
    )(lam_s, sp, mp, tp)
    return out[0, :n]


def _gc_at_kernel(lam_ref, s_ref, m_ref, tot_ref, gid_ref, out_ref, *, nk, bj, bk):
    kblk = pl.program_id(1)

    @pl.when(kblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.float32)  # (BJ, BK) gathered candidate rows
    m = m_ref[...].astype(jnp.float32)  # (1, BK)
    gid = gid_ref[...]  # (BJ, 1) global row ids of the gathered candidates
    cols = jax.lax.broadcasted_iota(jnp.int32, (bj, bk), 1) + kblk * bk
    w = 2.0 * m + jnp.where(gid == cols, 1.0, 0.0)  # (BJ, BK)
    out_ref[...] += (s * w).sum(axis=1)[None, :]

    @pl.when(kblk == nk - 1)
    def _finalize():
        lam = lam_ref[0]
        tot = tot_ref[...].astype(jnp.float32)  # (1, BJ)
        out_ref[...] = tot - lam * out_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "bk"))
def gc_gains_at_pallas(
    sim: jax.Array,
    selmask: jax.Array,
    total: jax.Array,
    lam: jax.Array,
    idx: jax.Array,
    interpret: bool = False,
    bk: int = BK,
) -> jax.Array:
    """Masked-subset sweep: gains at the gathered candidates ``idx`` (k,)
    int32 -> (k,) fp32; slots with idx < 0 are padding and return NEG_INF."""
    n = sim.shape[0]
    (k,) = idx.shape
    from repro.kernels.fl_gains import _subset_tile

    bj = _subset_tile(k, BJ)
    safe = jnp.clip(idx, 0, n - 1)
    rows = jnp.take(sim, safe, axis=0)  # (k, n) gather feeding the fused sweep
    pad_j = (-k) % bj
    pad_k = (-n) % bk
    sp = jnp.pad(rows, ((0, pad_j), (0, pad_k)))
    mp = jnp.pad(selmask.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    tp = jnp.pad(total[safe].astype(jnp.float32)[None, :], ((0, 0), (0, pad_j)))
    # padded slots get gid -1: never equal to a column id, so no diag term
    gp = jnp.pad(safe[:, None], ((0, pad_j), (0, 0)), constant_values=-1)
    npj, npk = sp.shape
    nk = npk // bk
    lam_s = jnp.asarray(lam, jnp.float32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(_gc_at_kernel, nk=nk, bj=bj, bk=bk),
        grid=(npj // bj, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bj, bk), lambda j, kb: (j, kb)),
            pl.BlockSpec((1, bk), lambda j, kb: (0, kb)),
            pl.BlockSpec((1, bj), lambda j, kb: (0, j)),
            pl.BlockSpec((bj, 1), lambda j, kb: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda j, kb: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npj), jnp.float32),
        interpret=interpret,
        name="gc_gains_at",  # the compiled custom call's name, read by the trace
    )(lam_s, sp, mp, tp, gp)
    return jnp.where(idx >= 0, out[0, :k], NEG_INF)

"""Pallas TPU kernels: fused Set-Cover family gain sweeps.

Both covers maintain an O(m) memoized statistic over the concept axis
(paper Table 3), so a full candidate sweep is one pass over the (n, m)
concept-incidence matrix:

  SetCover             gains_j = sum_u w_u * max(G_ju - covered_u, 0)
  ProbabilisticSetCover gains_j = sum_u w_u * Pbar_u(A) * p_ju

XLA materializes the (n, m) relu / product intermediate in HBM; these
kernels stream each (BN x BM) tile of the incidence matrix through VMEM
once and fuse mask -> weight -> row-reduce in-register on the VPU,
accumulating the m strips into a (1, BN) output block — the same shape as
the feature-based sweep (``fb_gains.py``), with the memoized vector
(``covered`` resp. ``w * miss``) riding along as a (1, BM) row.

grid = (n/BN, m/BM), m innermost.  Zero padding is exact for both: a padded
concept has G = 0 / p = 0 and w = 0, so it contributes nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 256  # candidates per tile
BM = 256  # concepts per tile


def _sc_kernel(g_ref, cov_ref, w_ref, out_ref):
    mblk = pl.program_id(1)

    @pl.when(mblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)  # (BN, BM) incidence tile
    cov = cov_ref[...].astype(jnp.float32)  # (1, BM) covered indicator
    w = w_ref[...].astype(jnp.float32)  # (1, BM) concept weights
    out_ref[...] += (jnp.maximum(g - cov, 0.0) * w).sum(axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bm"))
def sc_gains_pallas(
    cover: jax.Array,
    covered: jax.Array,
    w: jax.Array,
    interpret: bool = False,
    bn: int = BN,
    bm: int = BM,
) -> jax.Array:
    """cover (n, m) binary incidence, covered (m,) memoized indicator,
    w (m,) concept weights -> gains (n,) fp32."""
    n, m = cover.shape
    pad_n = (-n) % bn
    pad_m = (-m) % bm
    gp = jnp.pad(cover, ((0, pad_n), (0, pad_m)))
    cp = jnp.pad(covered.astype(jnp.float32)[None, :], ((0, 0), (0, pad_m)))
    wp = jnp.pad(w.astype(jnp.float32)[None, :], ((0, 0), (0, pad_m)))
    npn, npm = gp.shape
    out = pl.pallas_call(
        _sc_kernel,
        grid=(npn // bn, npm // bm),
        in_specs=[
            pl.BlockSpec((bn, bm), lambda j, u: (j, u)),
            pl.BlockSpec((1, bm), lambda j, u: (0, u)),
            pl.BlockSpec((1, bm), lambda j, u: (0, u)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, u: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npn), jnp.float32),
        interpret=interpret,
        name="sc_gains",  # the compiled custom call's name, read by the trace
    )(gp, cp, wp)
    return out[0, :n]


def _psc_kernel(p_ref, wm_ref, out_ref):
    mblk = pl.program_id(1)

    @pl.when(mblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    p = p_ref[...].astype(jnp.float32)  # (BN, BM) membership probabilities
    wm = wm_ref[...].astype(jnp.float32)  # (1, BM) w_u * Pbar_u(A)
    out_ref[...] += (p * wm).sum(axis=1)[None, :]


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bm"))
def psc_gains_pallas(
    probs: jax.Array,
    miss: jax.Array,
    w: jax.Array,
    interpret: bool = False,
    bn: int = BN,
    bm: int = BM,
) -> jax.Array:
    """probs (n, m) membership probabilities, miss (m,) memoized
    Pbar_u(A) = prod_{j in A}(1 - p_ju), w (m,) weights -> gains (n,) fp32.

    The weighted miss vector ``w * miss`` is formed once on the host side of
    the kernel (O(m)) so the tile loop is a single fused multiply-reduce."""
    n, m = probs.shape
    pad_n = (-n) % bn
    pad_m = (-m) % bm
    pp = jnp.pad(probs, ((0, pad_n), (0, pad_m)))
    wm = (w.astype(jnp.float32) * miss.astype(jnp.float32))[None, :]
    wmp = jnp.pad(wm, ((0, 0), (0, pad_m)))
    npn, npm = pp.shape
    out = pl.pallas_call(
        _psc_kernel,
        grid=(npn // bn, npm // bm),
        in_specs=[
            pl.BlockSpec((bn, bm), lambda j, u: (j, u)),
            pl.BlockSpec((1, bm), lambda j, u: (0, u)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda j, u: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npn), jnp.float32),
        interpret=interpret,
        name="psc_gains",  # the compiled custom call's name, read by the trace
    )(pp, wmp)
    return out[0, :n]

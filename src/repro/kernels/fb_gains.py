"""Pallas TPU kernel: fused feature-based (concave-over-modular) gain sweep.

For the Feature-Based function (paper §2.3.3) with memoized feature mass
``acc_f = m_f(A)``, the marginal gain of every candidate j is

    gains_j = sum_f w_f * ( g(acc_f + X_jf) - g(acc_f) )

with g a concave scalarizer (sqrt / log1p / inverse).  XLA materializes the
(n, F) concave intermediate in HBM; this kernel streams each (BN x BF) tile
of the feature matrix through VMEM once and fuses add -> concave -> weighted
row-reduce in-register on the VPU.  The (BN, BLOCK) output block is
revisited across F strips as the fp32 accumulator: each (BN, BF) tile of
weighted terms is added to it BLOCK columns at a time, in column order
(``repro.common.add_blocks``), and the wrapper sums its BLOCK columns with
``tree_sum``.  That is exactly ``repro.common.tiled_sum``, the XLA sweep's
order, so the Pallas and XLA routes return the same bits.

grid = (n/BN, F/BF), F innermost; the concave name is a static kernel param.

``fb_gains_at_pallas`` is the masked-subset entry point (the lazy engines'
``partial_sweep`` contract): an XLA gather of the K requested feature rows
feeds the same fused add -> concave -> weighted-reduce tile stream, sized to
the subset.  Per-row F-strip accumulation is independent of the other rows,
so subset values are bit-identical to the full sweep's at the same indices.
Slots with idx < 0 are padding and return NEG_INF.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import BLOCK, NEG_INF, add_blocks, get_concave, tree_sum

BN = 256  # candidates per tile
BF = 256  # features per tile


def _fb_kernel(x_ref, acc_ref, w_ref, out_ref, *, concave):
    fblk = pl.program_id(1)

    @pl.when(fblk == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = get_concave(concave)
    x = x_ref[...].astype(jnp.float32)  # (BN, BF)
    a = acc_ref[...].astype(jnp.float32)  # (1, BF)
    w = w_ref[...].astype(jnp.float32)  # (1, BF)
    out_ref[...] = add_blocks(out_ref[...], (g(a + x) - g(a)) * w, 1)


@functools.partial(jax.jit, static_argnames=("concave", "interpret", "bn", "bf"))
def fb_gains_pallas(
    feats: jax.Array,
    acc: jax.Array,
    w: jax.Array,
    concave: str = "sqrt",
    interpret: bool = False,
    bn: int = BN,
    bf: int = BF,
) -> jax.Array:
    """feats (n, F) non-negative scores, acc (F,) memoized mass, w (F,)
    weights -> gains (n,) fp32.  Padded features get w = 0 so contribute 0.
    ``bf`` must be a multiple of BLOCK to sum in the XLA order (a
    narrower ``bf`` accumulates in blocks of its own width)."""
    n, F = feats.shape
    pad_n = (-n) % bn
    pad_f = (-F) % bf
    xp = jnp.pad(feats, ((0, pad_n), (0, pad_f)))
    ap = jnp.pad(acc.astype(jnp.float32)[None, :], ((0, 0), (0, pad_f)))
    wp = jnp.pad(w.astype(jnp.float32)[None, :], ((0, 0), (0, pad_f)))
    npn, npf = xp.shape
    lanes = min(bf, BLOCK)
    out = pl.pallas_call(
        functools.partial(_fb_kernel, concave=concave),
        grid=(npn // bn, npf // bf),
        in_specs=[
            pl.BlockSpec((bn, bf), lambda j, f: (j, f)),
            pl.BlockSpec((1, bf), lambda j, f: (0, f)),
            pl.BlockSpec((1, bf), lambda j, f: (0, f)),
        ],
        out_specs=pl.BlockSpec((bn, lanes), lambda j, f: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((npn, lanes), jnp.float32),
        interpret=interpret,
        name="fb_gains",  # the compiled custom call's name, read by the trace
    )(xp, ap, wp)
    return tree_sum(out[:n], axis=1)


@functools.partial(jax.jit, static_argnames=("concave", "interpret"))
def fb_gains_at_pallas(
    feats: jax.Array,
    acc: jax.Array,
    w: jax.Array,
    idx: jax.Array,
    concave: str = "sqrt",
    interpret: bool = False,
) -> jax.Array:
    """Masked-subset sweep: feats (n, F), acc (F,), w (F,), idx (k,) int32 ->
    gains (k,) fp32; slots with idx < 0 are padding and return NEG_INF."""
    from repro.kernels.fl_gains import _subset_tile

    (k,) = idx.shape
    safe = jnp.clip(idx, 0, feats.shape[0] - 1)
    rows = jnp.take(feats, safe, axis=0)  # (k, F) gather feeding the fused sweep
    out = fb_gains_pallas(
        rows, acc, w, concave=concave, interpret=interpret, bn=_subset_tile(k, BN)
    )
    return jnp.where(idx >= 0, out, NEG_INF)

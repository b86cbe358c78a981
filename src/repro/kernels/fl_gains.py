"""Pallas TPU kernel: fused facility-location marginal gains.

Per greedy step the hotspot is  gains_j = sum_i max(S_ij - curmax_i, 0)
over the whole candidate set (paper Table 3 memoization, vectorized — see
DESIGN §2).  A naive XLA lowering materializes the (U, N) relu intermediate
in HBM (3x traffic: read S, write relu, read relu for the reduce).  This
kernel streams each S tile through VMEM exactly once and fuses
subtract→relu→column-reduce in-register, so the op stays at the 1x-HBM-read
roofline of S itself.

grid = (N/BN, U/BU) with U innermost.  The (BLOCK, BN) output block is
revisited across U steps as the fp32 accumulator: each (BU, BN) tile of
relu terms is added to it BLOCK rows at a time, in row order
(``repro.common.add_blocks``), and the wrapper sums its BLOCK rows with
``tree_sum``.  That is exactly ``repro.common.tiled_sum``, the XLA FL
sweep's order, so the Pallas and XLA routes return the same bits.

``fl_gains_at_pallas`` is the masked-subset entry point (the lazy engines'
``partial_sweep`` contract): an XLA gather of the K requested columns feeds
the SAME fused subtract->relu->reduce tile stream, sized to the subset, so a
bucketed lazy step touches O(U * K) of S instead of O(U * N).  Slots with
idx < 0 are padding and return NEG_INF.  Because each output column's
accumulation order over U tiles is independent of the other columns, the
subset values are bit-identical to the full sweep's at the same indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.common import BLOCK, NEG_INF, add_blocks, tree_sum

BU = 256  # represented-set rows per tile
BN = 512  # candidates per tile

_PAD_CM = 3.0e38  # pad value for curmax: relu(s - huge) == 0 contributes nothing


def _fl_gains_kernel(s_ref, cm_ref, out_ref):
    u = pl.program_id(1)

    @pl.when(u == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    s = s_ref[...].astype(jnp.float32)  # (BU, BN)
    cm = cm_ref[...].astype(jnp.float32)  # (BU, 1)
    out_ref[...] = add_blocks(out_ref[...], jnp.maximum(s - cm, 0.0), 0)


@functools.partial(jax.jit, static_argnames=("interpret", "bu", "bn"))
def fl_gains_pallas(
    sim: jax.Array,
    curmax: jax.Array,
    interpret: bool = False,
    bu: int = BU,
    bn: int = BN,
) -> jax.Array:
    """sim (u, n), curmax (u,) -> gains (n,) in fp32.  ``bu`` must be a
    multiple of BLOCK to sum in the XLA order (a smaller ``bu``
    accumulates in blocks of its own height)."""
    u, n = sim.shape
    pad_u = (-u) % bu
    pad_n = (-n) % bn
    sp = jnp.pad(sim, ((0, pad_u), (0, pad_n)))
    cmp_ = jnp.pad(
        curmax.astype(jnp.float32)[:, None], ((0, pad_u), (0, 0)),
        constant_values=_PAD_CM,
    )
    up, npad = sp.shape
    grid = (npad // bn, up // bu)
    rows = min(bu, BLOCK)
    out = pl.pallas_call(
        _fl_gains_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bu, bn), lambda j, i: (i, j)),
            pl.BlockSpec((bu, 1), lambda j, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((rows, npad), jnp.float32),
        interpret=interpret,
        name="fl_gains",  # the compiled custom call's name, read by the trace
    )(sp, cmp_)
    return tree_sum(out[:, :n], axis=0)


def _subset_tile(k: int, cap: int) -> int:
    """Candidate-tile width for a K-subset sweep: one lane-width-aligned tile
    when the subset is small, the full-sweep tiling otherwise."""
    b = 128
    while b < min(k, cap):
        b *= 2
    return min(b, cap)


@functools.partial(jax.jit, static_argnames=("interpret", "bu"))
def fl_gains_at_pallas(
    sim: jax.Array,
    curmax: jax.Array,
    idx: jax.Array,
    interpret: bool = False,
    bu: int = BU,
) -> jax.Array:
    """Masked-subset sweep: sim (u, n), curmax (u,), idx (k,) int32 ->
    gains (k,) fp32; slots with idx < 0 are padding and return NEG_INF."""
    (k,) = idx.shape
    safe = jnp.clip(idx, 0, sim.shape[1] - 1)
    cols = jnp.take(sim, safe, axis=1)  # (u, k) gather feeding the fused sweep
    out = fl_gains_pallas(cols, curmax, interpret=interpret, bu=bu, bn=_subset_tile(k, BN))
    return jnp.where(idx >= 0, out, NEG_INF)

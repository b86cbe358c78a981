"""Pallas TPU kernel: fused facility-location marginal gains.

Per greedy step the hotspot is  gains_j = sum_i max(S_ij - curmax_i, 0)
over the whole candidate set (paper Table 3 memoization, vectorized — see
DESIGN §2).  A naive XLA lowering materializes the (U, N) relu intermediate
in HBM (3x traffic: read S, write relu, read relu for the reduce).  This
kernel streams each S tile through VMEM exactly once and fuses
subtract→relu→row-reduce in-register, so the op stays at the 1x-HBM-read
roofline of S itself.

The kernel reads S through its transposed view S.T, (N, U): candidates on
sublanes, represented rows on lanes.  That is the layout a greedy loop
keeps S in anyway, because the curmax update gathers a column ``S[:, j]``
every step and a column gather wants the rows on lanes.  Inside such a
loop the transpose is a bitcast of the loop's carry, so the sweep reads
the carry as it lies and no step copies S into another layout.

grid = (cdiv(N, BN), cdiv(U, BU)) with U innermost.  A (BN, BLOCK) fp32
accumulator in VMEM lives across the U steps of a candidate tile: each
(BN, BU) tile of relu terms is added to it BLOCK lanes at a time, in row
order (``repro.common.add_blocks``).  After the last U step the kernel
stores it transposed, as the (BLOCK, BN) block of a (BLOCK, N) output,
and the wrapper sums its BLOCK rows with ``tree_sum``.  That is exactly
``repro.common.tiled_sum``, the XLA FL sweep's order, so the Pallas and
XLA routes return the same bits.  The transposed store keeps the tree
sum over contiguous halves, as it is for the XLA sweep; an (N, BLOCK)
output would add a relayout of it every step.

Nothing is padded.  The last row tile of a U that is not a multiple of BU
reaches past the array, and what a block read holds there is unspecified
(possibly NaN).  The kernel masks those rows with a select against an
iota of row indices, so each adds exactly +0.0, as the zero-padded rows
of ``tiled_sum`` do; a multiply would carry a NaN through.  Candidates
past N land in output columns that the wrapper slices off.

``fl_gains_at_pallas`` is the masked-subset entry point (the lazy engines'
``partial_sweep`` contract): an XLA gather of the K requested rows of S.T
feeds the SAME fused subtract->relu->reduce tile stream, sized to the
subset, so a bucketed lazy step touches O(U * K) of S instead of O(U * N).
Slots with idx < 0 are padding and return NEG_INF.  Because each
candidate's accumulation order over U tiles is independent of the other
candidates, the subset values are bit-identical to the full sweep's at the
same indices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import BLOCK, NEG_INF, add_blocks, tree_sum

BU = 1024  # represented-set rows per tile, on lanes
BN = 512  # candidates per tile, on sublanes


def _fl_gains_kernel(s_ref, cm_ref, out_ref, acc_ref, *, u: int, bu: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    s = s_ref[...].astype(jnp.float32)  # (BN, BU): candidates x rows
    cm = cm_ref[...].astype(jnp.float32)  # (1, BU)
    relu = jnp.maximum(s - cm, 0.0)
    if u % bu:  # the last row tile reaches past u: select, never multiply
        row = i * bu + jax.lax.broadcasted_iota(jnp.int32, (1, bu), 1)
        relu = jnp.where(row < u, relu, 0.0)
    acc_ref[...] = add_blocks(acc_ref[...], relu, 1)

    @pl.when(i == pl.num_programs(1) - 1)
    def _store():
        out_ref[...] = acc_ref[...].T


def _sweep_t(simt, curmax, interpret: bool, bu: int, bn: int) -> jax.Array:
    """The sweep over the transposed view: simt (n, u), curmax (u,) ->
    gains (n,) in fp32."""
    n, u = simt.shape
    width = min(bu, BLOCK)
    tiles_n = pl.cdiv(n, bn)
    out = pl.pallas_call(
        functools.partial(_fl_gains_kernel, u=u, bu=bu),
        grid=(tiles_n, pl.cdiv(u, bu)),
        in_specs=[
            pl.BlockSpec((bn, bu), lambda j, i: (j, i)),
            pl.BlockSpec((1, bu), lambda j, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((width, bn), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((width, tiles_n * bn), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, width), jnp.float32)],
        interpret=interpret,
        name="fl_gains",  # the compiled custom call's name, read by the trace
    )(simt, curmax.astype(jnp.float32)[None, :])
    return tree_sum(out[:, :n], axis=0)


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
    accumulates in blocks of its own width)."""
    return _sweep_t(sim.T, curmax, interpret, bu, bn)


def _subset_tile(k: int, cap: int) -> int:
    """Candidate-tile height for a K-subset sweep: one 128-aligned tile
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
    rows = jnp.take(sim.T, safe, axis=0)  # (k, u) gather feeding the fused sweep
    out = _sweep_t(rows, curmax, interpret, bu, _subset_tile(k, BN))
    return jnp.where(idx >= 0, out, NEG_INF)

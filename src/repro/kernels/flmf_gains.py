"""Pallas TPU kernel: matrix-free fused facility-location gain sweep.

The dense sweep (``fl_gains.py``) streams a materialized (U, N) similarity
matrix; at n >= 10^6 that matrix does not exist.  This kernel fuses the
similarity computation itself into the sweep: feature tiles of the
represented set ``x`` (U, d) and the candidate set ``y`` (N, d) stream
through the MXU exactly as in ``similarity_kernel.py`` — matmul strips into
an fp32 VMEM scratch accumulator, metric epilogue (cosine shift / euclidean
/ RBF) in-register — and the finished (BU, BN) similarity block feeds the
subtract->relu->column-reduce of the gain sweep without ever leaving VMEM.
HBM traffic is O(n * d) feature bytes; the n x n matrix is never written.

grid = (N/BN, U/BU, d/BK) with the contraction strip innermost; the
(1, BN) output block is revisited across the U and K steps.  The (BU, BN)
similarity scratch lives in VMEM (``scratch_shapes``), zeroed at each
candidate/row tile's first K strip and folded into the output on its last.

``flmf_gains_at_pallas`` is the masked-subset entry point (the lazy
engines' ``partial_sweep`` contract): an XLA gather of the K requested
candidate ROWS of ``y`` feeds the same fused stream, sized to the subset.
Slots with idx < 0 are padding and return NEG_INF.  Each output column's
accumulation order over U and d tiles is independent of the other columns,
so subset values match the full sweep's at the same indices.

Row padding: ``x`` pads with zero rows and ``curmax`` with ``_PAD_CM``
(relu(s - huge) == 0), so pad rows contribute nothing for ANY metric —
including cosine/RBF, whose zero-feature similarity is nonzero.  Candidate
padding is sliced off the output.  Cosine inputs arrive PRE-normalized
(the :class:`~repro.core.sources.FeatureSource` contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import DISTANCE_METRICS, NEG_INF, exact_self_distance

BU = 256  # represented-set rows per tile
BN = 512  # candidates per tile
BK = 512  # feature-contraction strip

_PAD_CM = 3.0e38  # pad value for curmax: relu(s - huge) == 0 contributes nothing


def _flmf_kernel(
    x_ref, y_ref, xx_ref, yy_ref, cm_ref, *refs,
    metric, inv_two_sigma_sq, nd, ided,
):
    ids_ref, out_ref, acc_ref = refs if ided else (None,) + refs
    u = pl.program_id(1)
    kd = pl.program_id(2)

    @pl.when((u == 0) & (kd == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(kd == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)  # (BU, BK)
    y = y_ref[...].astype(jnp.float32)  # (BN, BK)
    acc_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # full f32, not one bf16 pass
    )

    @pl.when(kd == nd - 1)
    def _fold():
        acc = acc_ref[...]  # (BU, BN) raw dot block
        if metric == "dot":
            s = acc
        elif metric == "cosine":
            s = 0.5 * (1.0 + acc)
        else:
            xx = xx_ref[...].astype(jnp.float32)  # (BU, 1)
            yy = yy_ref[...].astype(jnp.float32)  # (1, BN)
            d2 = jnp.maximum(xx + yy - 2.0 * acc, 0.0)
            if ided:
                d2 = exact_self_distance(d2, u * acc.shape[0], ids_ref[...])
            if metric == "euclidean":
                s = 1.0 / (1.0 + jnp.sqrt(d2))
            else:  # rbf
                s = jnp.exp(-d2 * inv_two_sigma_sq)
        cm = cm_ref[...].astype(jnp.float32)  # (BU, 1)
        out_ref[...] += jnp.maximum(s - cm, 0.0).sum(axis=0)[None, :]


@functools.partial(
    jax.jit,
    static_argnames=("metric", "rbf_sigma", "interpret", "bu", "bn", "bk"),
)
def flmf_gains_pallas(
    x: jax.Array,
    y: jax.Array,
    xx: jax.Array,
    yy: jax.Array,
    curmax: jax.Array,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    interpret: bool = False,
    bu: int = BU,
    bn: int = BN,
    bk: int = BK,
    col_ids: jax.Array | None = None,
) -> jax.Array:
    """x (u, d), y (n, d), squared norms xx (u,) / yy (n,), curmax (u,)
    -> gains (n,) fp32, without materializing the (u, n) similarity.

    ``col_ids`` (n,) int32 names the row of ``x`` each candidate is the same
    point as (-1: none), so distance metrics see exactly 0 there
    (:func:`repro.common.exact_self_distance`)."""
    u, d = x.shape
    n = y.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((0, (-u) % bu), (0, (-d) % bk)))
    yp = jnp.pad(y.astype(jnp.float32), ((0, (-n) % bn), (0, (-d) % bk)))
    xxp = jnp.pad(xx.astype(jnp.float32)[:, None], ((0, (-u) % bu), (0, 0)))
    yyp = jnp.pad(yy.astype(jnp.float32)[None, :], ((0, 0), (0, (-n) % bn)))
    cmp_ = jnp.pad(
        curmax.astype(jnp.float32)[:, None], ((0, (-u) % bu), (0, 0)),
        constant_values=_PAD_CM,
    )
    up, dp = xp.shape
    npad = yp.shape[0]
    nd = dp // bk
    sigma = rbf_sigma if rbf_sigma is not None else float(d) ** 0.5
    grid = (npad // bn, up // bu, nd)
    ided = col_ids is not None and metric in DISTANCE_METRICS
    in_specs = [
        pl.BlockSpec((bu, bk), lambda j, i, k: (i, k)),
        pl.BlockSpec((bn, bk), lambda j, i, k: (j, k)),
        pl.BlockSpec((bu, 1), lambda j, i, k: (i, 0)),
        pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),
        pl.BlockSpec((bu, 1), lambda j, i, k: (i, 0)),
    ]
    args = [xp, yp, xxp, yyp, cmp_]
    if ided:
        in_specs.append(pl.BlockSpec((1, bn), lambda j, i, k: (0, j)))
        args.append(
            jnp.pad(col_ids.astype(jnp.int32)[None, :], ((0, 0), (0, (-n) % bn)),
                    constant_values=-1)
        )
    out = pl.pallas_call(
        functools.partial(
            _flmf_kernel,
            metric=metric,
            inv_two_sigma_sq=1.0 / (2.0 * sigma * sigma),
            nd=nd,
            ided=ided,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn), lambda j, i, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bu, bn), jnp.float32)],
        interpret=interpret,
        name="flmf_gains",  # the compiled custom call's name, read by the trace
    )(*args)
    return out[0, :n]


@functools.partial(
    jax.jit, static_argnames=("metric", "rbf_sigma", "interpret", "bu", "bk")
)
def flmf_gains_at_pallas(
    x: jax.Array,
    y: jax.Array,
    xx: jax.Array,
    yy: jax.Array,
    curmax: jax.Array,
    idx: jax.Array,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    interpret: bool = False,
    bu: int = BU,
    bk: int = BK,
    col_ids: jax.Array | None = None,
) -> jax.Array:
    """Masked-subset sweep: gains at the gathered candidates ``idx`` (k,)
    int32 -> (k,) fp32; slots with idx < 0 are padding and return NEG_INF.
    ``col_ids`` as in :func:`flmf_gains_pallas`, over all n candidates.

    Unlike the dense subset sweeps, the candidate tile stays at the
    full-sweep width BN: the similarity dot is recomputed here, and a
    narrower contraction can drift from the full sweep by ulps — fixed
    tiling keeps subset and full-sweep gains bit-identical."""
    safe = jnp.clip(idx, 0, y.shape[0] - 1)
    out = flmf_gains_pallas(
        x,
        jnp.take(y, safe, axis=0),
        xx,
        jnp.take(yy, safe),
        curmax,
        metric=metric,
        rbf_sigma=rbf_sigma,
        interpret=interpret,
        bu=bu,
        bn=BN,
        bk=bk,
        col_ids=None if col_ids is None else jnp.take(col_ids, safe),
    )
    return jnp.where(idx >= 0, out, NEG_INF)

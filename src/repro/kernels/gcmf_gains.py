"""Pallas TPU kernel: matrix-free fused graph-cut gain sweep.

Stateless Graph-Cut sweep (``gc_gains.py`` semantics) with the similarity
computed in-stream from feature tiles instead of read from a materialized
(n, n) kernel:

    gains_j = total_j - lam * (2 * selsum_j + diag_j),
    selsum_j = sum_k sim(y_j, y_k) * m_k

Each (BJ, BKC) similarity block is built on the MXU from d-strips of the
candidate rows ``y_j`` and ground columns ``y_k`` (fp32 VMEM scratch,
metric epilogue in-register, exactly the ``similarity_kernel.py`` tiling)
and immediately collapsed into the masked matvec — HBM traffic stays at
the O(n * d) feature bytes.

``diag`` and ``total`` arrive precomputed (they are the memoized Graph-Cut
statistics :class:`~repro.core.functions.graph_cut.GraphCutMF` already
holds), so the stateless sweep agrees with the memoized gains on the same
diagonal instead of re-deriving sim(j, j) from a d2 = 0 roundtrip.

grid = (n/BJ, n/BKC, d/BKD), contraction strip innermost; the (1, BJ)
output accumulates selsum over the BKC steps and is finalized to
``total - lam * (2 * selsum + diag)`` on the last (k, d) step.  Ground
padding is exact: pad columns carry m = 0, so their (possibly nonzero
zero-feature) similarity contributes nothing.

``gcmf_gains_at_pallas`` gathers the K requested candidate rows (plus
their ``total``/``diag`` entries) and runs the same stream sized to the
subset; idx < 0 slots are padding and return NEG_INF.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common import NEG_INF

BJ = 256  # candidate rows of the output per tile
BKC = 256  # summed-over ground elements per tile
BKD = 512  # feature-contraction strip


def _gcmf_kernel(
    lam_ref, yj_ref, yk_ref, yyj_ref, yyk_ref, m_ref, tot_ref, diag_ref,
    out_ref, acc_ref, *, metric, inv_two_sigma_sq, nkc, nd,
):
    kc = pl.program_id(1)
    kd = pl.program_id(2)

    @pl.when((kc == 0) & (kd == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(kd == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    yj = yj_ref[...].astype(jnp.float32)  # (BJ, BKD)
    yk = yk_ref[...].astype(jnp.float32)  # (BKC, BKD)
    acc_ref[...] += jax.lax.dot_general(
        yj, yk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # full f32, not one bf16 pass
    )

    @pl.when(kd == nd - 1)
    def _fold():
        acc = acc_ref[...]  # (BJ, BKC)
        if metric == "dot":
            s = acc
        elif metric == "cosine":
            s = 0.5 * (1.0 + acc)
        else:
            yyj = yyj_ref[...].astype(jnp.float32)  # (BJ, 1)
            yyk = yyk_ref[...].astype(jnp.float32)  # (1, BKC)
            d2 = jnp.maximum(yyj + yyk - 2.0 * acc, 0.0)
            if metric == "euclidean":
                s = 1.0 / (1.0 + jnp.sqrt(d2))
            else:  # rbf
                s = jnp.exp(-d2 * inv_two_sigma_sq)
        m = m_ref[...].astype(jnp.float32)  # (1, BKC)
        out_ref[...] += (s * m).sum(axis=1)[None, :]

    @pl.when((kc == nkc - 1) & (kd == nd - 1))
    def _finalize():
        lam = lam_ref[0]
        tot = tot_ref[...].astype(jnp.float32)  # (1, BJ)
        dg = diag_ref[...].astype(jnp.float32)  # (1, BJ)
        out_ref[...] = tot - lam * (2.0 * out_ref[...] + dg)


@functools.partial(
    jax.jit,
    static_argnames=("metric", "rbf_sigma", "interpret", "bj", "bkc", "bkd"),
)
def gcmf_gains_pallas(
    yj: jax.Array,
    yk: jax.Array,
    yyj: jax.Array,
    yyk: jax.Array,
    selmask: jax.Array,
    total: jax.Array,
    diag: jax.Array,
    lam: jax.Array,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    interpret: bool = False,
    bj: int = BJ,
    bkc: int = BKC,
    bkd: int = BKD,
) -> jax.Array:
    """Candidate rows yj (j, d) vs ground yk (n, d) with squared norms
    yyj/yyk, selection mask (n,), total/diag (j,), lam scalar -> (j,) fp32."""
    j, d = yj.shape
    n = yk.shape[0]
    pad_j = (-j) % bj
    pad_k = (-n) % bkc
    pad_d = (-d) % bkd
    yjp = jnp.pad(yj.astype(jnp.float32), ((0, pad_j), (0, pad_d)))
    ykp = jnp.pad(yk.astype(jnp.float32), ((0, pad_k), (0, pad_d)))
    yyjp = jnp.pad(yyj.astype(jnp.float32)[:, None], ((0, pad_j), (0, 0)))
    yykp = jnp.pad(yyk.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    mp = jnp.pad(selmask.astype(jnp.float32)[None, :], ((0, 0), (0, pad_k)))
    tp = jnp.pad(total.astype(jnp.float32)[None, :], ((0, 0), (0, pad_j)))
    dgp = jnp.pad(diag.astype(jnp.float32)[None, :], ((0, 0), (0, pad_j)))
    jp, dp = yjp.shape
    nkc = ykp.shape[0] // bkc
    nd = dp // bkd
    sigma = rbf_sigma if rbf_sigma is not None else float(d) ** 0.5
    lam_s = jnp.asarray(lam, jnp.float32).reshape((1,))
    out = pl.pallas_call(
        functools.partial(
            _gcmf_kernel,
            metric=metric,
            inv_two_sigma_sq=1.0 / (2.0 * sigma * sigma),
            nkc=nkc,
            nd=nd,
        ),
        grid=(jp // bj, nkc, nd),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bj, bkd), lambda jb, kc, kd: (jb, kd)),
            pl.BlockSpec((bkc, bkd), lambda jb, kc, kd: (kc, kd)),
            pl.BlockSpec((bj, 1), lambda jb, kc, kd: (jb, 0)),
            pl.BlockSpec((1, bkc), lambda jb, kc, kd: (0, kc)),
            pl.BlockSpec((1, bkc), lambda jb, kc, kd: (0, kc)),
            pl.BlockSpec((1, bj), lambda jb, kc, kd: (0, jb)),
            pl.BlockSpec((1, bj), lambda jb, kc, kd: (0, jb)),
        ],
        out_specs=pl.BlockSpec((1, bj), lambda jb, kc, kd: (0, jb)),
        out_shape=jax.ShapeDtypeStruct((1, jp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bj, bkc), jnp.float32)],
        interpret=interpret,
        name="gcmf_gains",  # the compiled custom call's name, read by the trace
    )(lam_s, yjp, ykp, yyjp, yykp, mp, tp, dgp)
    return out[0, :j]


@functools.partial(
    jax.jit, static_argnames=("metric", "rbf_sigma", "interpret", "bkc", "bkd")
)
def gcmf_gains_at_pallas(
    y: jax.Array,
    yy: jax.Array,
    selmask: jax.Array,
    total: jax.Array,
    diag: jax.Array,
    lam: jax.Array,
    idx: jax.Array,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    interpret: bool = False,
    bkc: int = BKC,
    bkd: int = BKD,
) -> jax.Array:
    """Masked-subset sweep: gains at the gathered candidates ``idx`` (k,)
    int32 -> (k,) fp32; slots with idx < 0 are padding and return NEG_INF.

    The candidate-row tile stays at the full-sweep width BJ (the
    similarity contraction is recomputed in-stream; see flmf_gains)."""
    safe = jnp.clip(idx, 0, y.shape[0] - 1)
    out = gcmf_gains_pallas(
        jnp.take(y, safe, axis=0),
        y,
        jnp.take(yy, safe),
        yy,
        selmask,
        jnp.take(total, safe),
        jnp.take(diag, safe),
        lam,
        metric=metric,
        rbf_sigma=rbf_sigma,
        interpret=interpret,
        bj=BJ,
        bkc=bkc,
        bkd=bkd,
    )
    return jnp.where(idx >= 0, out, NEG_INF)

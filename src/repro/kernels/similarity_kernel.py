"""Pallas TPU kernel: pairwise similarity (the paper's O(n^2 d) hotspot).

The paper's C++ engine builds the kernel element-wise; Table 5 shows it
dominating wall-time at scale.  On TPU the problem is matmul-shaped, so the
kernel is tiled for the MXU:

  grid = (n/BN, m/BM, d/BK), K innermost; each step multiplies a
  (BN, BK) x (BK, BM) tile pair on the MXU into an fp32 VMEM accumulator
  (the output block, revisited across the K steps), and the final K step
  applies the metric epilogue (cosine shift / euclidean / RBF) in-register —
  the distance matrix is never materialized in HBM.

VMEM working set at the default BN=BM=128, BK=512:
  x tile 128*512*4 + y tile 512*128*4 + out 128*128*4 ≈ 0.6 MiB  « 16 MiB.
MXU dims (128, 128, 512) are all multiples of the 128-lane width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 128  # rows per tile
BM = 128  # cols per tile
BK = 512  # contraction strip


def _sim_kernel(x_ref, y_ref, xx_ref, yy_ref, out_ref, *, metric, inv_two_sigma_sq, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)  # (BN, BK)
    y = y_ref[...].astype(jnp.float32)  # (BM, BK)
    out_ref[...] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,  # full f32, not one bf16 pass
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = out_ref[...]
        if metric == "dot":
            return
        if metric == "cosine":
            # inputs arrive pre-normalized; shift to [0, 1]
            out_ref[...] = 0.5 * (1.0 + acc)
            return
        xx = xx_ref[...].astype(jnp.float32)  # (BN, 1)
        yy = yy_ref[...].astype(jnp.float32)  # (1, BM)
        d2 = jnp.maximum(xx + yy - 2.0 * acc, 0.0)
        if metric == "euclidean":
            out_ref[...] = 1.0 / (1.0 + jnp.sqrt(d2))
        else:  # rbf
            out_ref[...] = jnp.exp(-d2 * inv_two_sigma_sq)


def _pad_to(a: jax.Array, mult: int, axis: int, value=0.0) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("metric", "rbf_sigma", "interpret", "bn", "bm", "bk")
)
def similarity_pallas(
    x: jax.Array,
    y: jax.Array,
    metric: str = "dot",
    rbf_sigma: float | None = None,
    interpret: bool = False,
    bn: int = BN,
    bm: int = BM,
    bk: int = BK,
) -> jax.Array:
    """(n, d), (m, d) -> (n, m) similarity in fp32."""
    n, d = x.shape
    m = y.shape[0]
    x32 = x.astype(jnp.float32)
    y32 = y.astype(jnp.float32)
    if metric == "cosine":
        x32 = x32 / jnp.maximum(jnp.linalg.norm(x32, axis=1, keepdims=True), 1e-12)
        y32 = y32 / jnp.maximum(jnp.linalg.norm(y32, axis=1, keepdims=True), 1e-12)
    xp = _pad_to(_pad_to(x32, bn, 0), bk, 1)
    yp = _pad_to(_pad_to(y32, bm, 0), bk, 1)
    xx = (xp * xp).sum(axis=1, keepdims=True)  # (np, 1)
    yy = (yp * yp).sum(axis=1, keepdims=True).T  # (1, mp)
    npad, dp = xp.shape
    mpad = yp.shape[0]
    nk = dp // bk
    sigma = rbf_sigma if rbf_sigma is not None else float(d) ** 0.5
    grid = (npad // bn, mpad // bm, nk)
    out = pl.pallas_call(
        functools.partial(
            _sim_kernel,
            metric=metric,
            inv_two_sigma_sq=1.0 / (2.0 * sigma * sigma),
            nk=nk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bn, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, mpad), jnp.float32),
        interpret=interpret,
    )(xp, yp, xx, yy)
    return out[:n, :m]

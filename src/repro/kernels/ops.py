"""Public jit'd wrappers around the Pallas kernels.

On TPU the compiled kernels run natively; on CPU (this container) they run in
``interpret=True`` mode, which executes the kernel body in Python and is what
the allclose test-suite validates against the ``ref.py`` oracles.
"""
from __future__ import annotations

import jax

from repro.kernels import ref
from repro.kernels.disp_gains import dmin_gains_pallas, dsum_gains_pallas
from repro.kernels.fb_gains import fb_gains_at_pallas, fb_gains_pallas
from repro.kernels.fl_gains import fl_gains_at_pallas, fl_gains_pallas
from repro.kernels.flmf_gains import flmf_gains_at_pallas, flmf_gains_pallas
from repro.kernels.gc_gains import gc_gains_at_pallas, gc_gains_pallas
from repro.kernels.gcmf_gains import gcmf_gains_at_pallas, gcmf_gains_pallas
from repro.kernels.sc_gains import psc_gains_pallas, sc_gains_pallas
from repro.kernels.similarity_kernel import similarity_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def similarity(x, y, metric: str = "dot", rbf_sigma: float | None = None):
    return similarity_pallas(
        x, y, metric=metric, rbf_sigma=rbf_sigma, interpret=_interpret()
    )


def fl_gains(sim, curmax):
    return fl_gains_pallas(sim, curmax, interpret=_interpret())


def fl_gains_at(sim, curmax, idx):
    return fl_gains_at_pallas(sim, curmax, idx, interpret=_interpret())


def gc_gains(sim, selmask, total, lam):
    return gc_gains_pallas(sim, selmask, total, lam, interpret=_interpret())


def gc_gains_at(sim, selmask, total, lam, idx):
    return gc_gains_at_pallas(sim, selmask, total, lam, idx, interpret=_interpret())


def flmf_gains(
    x, y, xx, yy, curmax, metric: str = "dot", rbf_sigma: float | None = None,
    col_ids=None,
):
    return flmf_gains_pallas(
        x, y, xx, yy, curmax, metric=metric, rbf_sigma=rbf_sigma,
        interpret=_interpret(), col_ids=col_ids,
    )


def flmf_gains_at(
    x, y, xx, yy, curmax, idx, metric: str = "dot", rbf_sigma: float | None = None,
    col_ids=None,
):
    return flmf_gains_at_pallas(
        x, y, xx, yy, curmax, idx, metric=metric, rbf_sigma=rbf_sigma,
        interpret=_interpret(), col_ids=col_ids,
    )


def gcmf_gains(
    y, yy, selmask, total, diag, lam,
    metric: str = "dot", rbf_sigma: float | None = None,
):
    return gcmf_gains_pallas(
        y, y, yy, yy, selmask, total, diag, lam,
        metric=metric, rbf_sigma=rbf_sigma, interpret=_interpret(),
    )


def gcmf_gains_at(
    y, yy, selmask, total, diag, lam, idx,
    metric: str = "dot", rbf_sigma: float | None = None,
):
    return gcmf_gains_at_pallas(
        y, yy, selmask, total, diag, lam, idx,
        metric=metric, rbf_sigma=rbf_sigma, interpret=_interpret(),
    )


def fb_gains(feats, acc, w, concave: str = "sqrt"):
    return fb_gains_pallas(feats, acc, w, concave=concave, interpret=_interpret())


def fb_gains_at(feats, acc, w, idx, concave: str = "sqrt"):
    return fb_gains_at_pallas(
        feats, acc, w, idx, concave=concave, interpret=_interpret()
    )


def sc_gains(cover, covered, w):
    return sc_gains_pallas(cover, covered, w, interpret=_interpret())


def psc_gains(probs, miss, w):
    return psc_gains_pallas(probs, miss, w, interpret=_interpret())


def dsum_gains(dist, selmask):
    return dsum_gains_pallas(dist, selmask, interpret=_interpret())


def dmin_gains(dist, selmask, count, curmin):
    return dmin_gains_pallas(dist, selmask, count, curmin, interpret=_interpret())


# re-export oracles for convenience
similarity_ref = ref.similarity_ref
fl_gains_ref = ref.fl_gains_ref
gc_gains_ref = ref.gc_gains_ref
flmf_gains_ref = ref.flmf_gains_ref
gcmf_gains_ref = ref.gcmf_gains_ref
flmf_gains_at_ref = ref.flmf_gains_at_ref
gcmf_gains_at_ref = ref.gcmf_gains_at_ref
fb_gains_ref = ref.fb_gains_ref
fl_gains_at_ref = ref.fl_gains_at_ref
gc_gains_at_ref = ref.gc_gains_at_ref
fb_gains_at_ref = ref.fb_gains_at_ref
sc_gains_ref = ref.sc_gains_ref
psc_gains_ref = ref.psc_gains_ref
dsum_gains_ref = ref.dsum_gains_ref
dmin_gains_ref = ref.dmin_gains_ref

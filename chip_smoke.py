#!/usr/bin/env python3
"""Chip smoke test: the served selection path on one TPU v5e chip.

Deployment: per-class facility-location coreset selection in the style of
CRAIG (Mirzasoleiman et al., ICML 2020) at CIFAR-10 training-set shapes:
10 classes of 5,000 items with 512-wide embeddings (the width of
ResNet-18's penultimate layer).  The embeddings are generated from
``--seed``; nothing is downloaded.  The budget is 10% of each class (500
items), an assumed coreset fraction.

Phases, all in this one process:

1. device: refuse anything but a named TPU kind.  There is no CPU
   fallback and no interpret mode.
2. dense served waves: one ``FacilityLocation`` per class over its
   ``create_kernel(x, metric="euclidean")`` kernel, ten specs per wave
   through one ``SelectionServer``: NaiveGreedy and LazyGreedy on the
   Pallas sweep, then NaiveGreedy on the XLA sweep.  Served results must
   equal sequential ``solve`` bit for bit on two classes, lazy ids must
   equal naive ids, and the XLA wave must equal the Pallas wave bit for bit
   (both sum in one order, so a Pallas -> XLA reroute changes nothing).
3. matrix-free pool: ``FacilityLocationMF`` over all 50,000 embeddings,
   LazyGreedy with budget 100, on the fused Pallas sweep.
4. plain reference: a float32 greedy written here, independent of the
   library, with the similarity computed in row blocks at full matmul
   precision.  It checks phases 2 and 3, and a negative control shows its
   bounds reject a similarity taken from bf16-rounded inputs.

Each phase prints the wall time of its first (compiling) call and of a
second warm call, the backend, the comparison margins and the device's peak
bytes.  A failed check raises and the run exits non-zero; the last line is
the JSON result only when every check passed.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # the wave on a 2x2 mesh vs one chip
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.common import enable_compile_cache  # noqa: E402
from repro.core import (  # noqa: E402
    FacilityLocation,
    FacilityLocationMF,
    SelectionSpec,
    create_kernel,
    solve,
)
from repro.core.optimizers.backends import backend_name  # noqa: E402
from repro.launch.serve import SelectionServer  # noqa: E402

KNOWN_KINDS = ("TPU v5 lite", "TPU v5e")

# Reference bounds.  Float32 sums of 5,000-50,000 terms taken in another
# order differ by about 1e-6 to 1e-5 relative, so a correct sweep stays well
# inside 1e-4.  A similarity from bf16-rounded inputs is off by about 4e-3
# relative per entry, which moves gains by far more than 1e-4 and reorders
# clear greedy steps; the negative control checks that these bounds say so.
TIE_RTOL = 1e-4  # top two reference gains closer than this: a tie
OBJ_RTOL = 1e-4  # library objective vs the reference's f(selected set)


@dataclasses.dataclass(frozen=True)
class Sizes:
    classes: int = 10  # CIFAR-10
    per_class: int = 5_000  # 50,000 training images / 10 classes
    dim: int = 512  # ResNet-18 penultimate layer
    budget: int = 500  # 10% of a class (assumed)
    pool_budget: int = 100  # the matrix-free phase over all classes
    block: int = 1_000  # reference similarity rows per block, one class
    pool_block: int = 2_500  # the same over the 50,000 pool
    checked: int = 2  # classes checked served == sequential


CIFAR10 = Sizes()


class SmokeFailure(RuntimeError):
    """A check failed; the run must exit non-zero."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# data


@partial(jax.jit, static_argnames=("classes", "per_class", "dim"))
def embeddings(seed, classes: int, per_class: int, dim: int) -> jax.Array:
    """(classes, per_class, dim) non-negative float32 embeddings.

    Post-ReLU features of low intrinsic dimension: each class is a mixture
    of 16 modes in a 32-wide latent space, projected to ``dim``."""
    latent, modes = 32, 16
    k = jax.random.split(jax.random.key(seed), 5)
    proj = jax.random.normal(k[0], (latent, dim)) / np.sqrt(latent)
    centers = 3.0 * jax.random.normal(k[1], (classes, 1, latent))
    mode_means = centers + jax.random.normal(k[2], (classes, modes, latent))
    which = jax.random.randint(k[3], (classes, per_class), 0, modes)
    z = jax.vmap(lambda m, w: m[w])(mode_means, which)
    z = z + 0.5 * jax.random.normal(k[4], (classes, per_class, latent))
    return jax.nn.relu(jnp.einsum("cpl,ld->cpd", z, proj, precision="highest"))


# ---------------------------------------------------------------------------
# plain reference: float32 facility-location greedy, written independently


def ref_similarity(x: jax.Array, y: jax.Array, same: jax.Array) -> jax.Array:
    """Euclidean similarity 1 / (1 + ||x_i - y_j||) in full float32.

    ``same`` marks the pairs that are one point, whose distance is 0 by
    definition (in float32, ``xx + yy - 2 x.y`` leaves a residue there that
    the square root blows up to a visible error)."""
    with jax.default_matmul_precision("highest"):
        dot = x @ y.T
    d2 = jnp.sum(x * x, 1)[:, None] + jnp.sum(y * y, 1)[None, :] - 2.0 * dot
    d2 = jnp.where(same, 0.0, jnp.maximum(d2, 0.0))
    return 1.0 / (1.0 + jnp.sqrt(d2))


def _ref_gains(x: jax.Array, curmax: jax.Array, block: int) -> jax.Array:
    """sum_i max(S_ij - curmax_i, 0) for every candidate j, S in row blocks."""
    n = x.shape[0]

    def add(acc, i):
        rows = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        cm = jax.lax.dynamic_slice_in_dim(curmax, i * block, block)
        same = (i * block + jnp.arange(block))[:, None] == jnp.arange(n)[None, :]
        s = ref_similarity(rows, x, same)
        return acc + jnp.maximum(s - cm[:, None], 0.0).sum(0), None

    return jax.lax.scan(add, jnp.zeros((n,), jnp.float32), jnp.arange(n // block))[0]


def _ref_add(x: jax.Array, curmax: jax.Array, j) -> jax.Array:
    same = (jnp.arange(x.shape[0]) == j)[:, None]
    return jnp.maximum(curmax, ref_similarity(x, x[j][None, :], same)[:, 0])


@partial(jax.jit, static_argnames="block")
def reference_trace(x: jax.Array, picks: jax.Array, block: int):
    """Replay ``picks`` under the reference.  At step t, the gains of every
    candidate given ``picks[:t]``: returns per step the reference argmax,
    its top two gains and the gain of ``picks[t]``, and f(picks)."""
    if x.shape[0] % block:
        raise ValueError(f"block {block} does not divide n = {x.shape[0]}")

    def step(curmax, j):
        g = _ref_gains(x, curmax, block)
        top = jax.lax.top_k(g, 2)[0]
        return _ref_add(x, curmax, j), (jnp.argmax(g), top[0], top[1], g[j])

    curmax = jnp.zeros((x.shape[0],), jnp.float32)
    curmax, per_step = jax.lax.scan(step, curmax, picks)
    return per_step, curmax.sum()


@partial(jax.jit, static_argnames=("k", "block"))
def reference_greedy(x: jax.Array, k: int, block: int):
    """Naive greedy under the reference: (picks, gains), each (k,)."""

    def step(curmax, _):
        g = _ref_gains(x, curmax, block)
        j = jnp.argmax(g)
        return _ref_add(x, curmax, j), (j, g[j])

    curmax = jnp.zeros((x.shape[0],), jnp.float32)
    return jax.lax.scan(step, curmax, None, length=k)[1]


@dataclasses.dataclass(frozen=True)
class Margins:
    steps: int
    ties: int  # steps whose top two reference gains lie within TIE_RTOL
    clear_mismatches: int  # clear steps where the pick is not the argmax
    min_clear_gap: float  # smallest relative top-two gap of a clear step
    max_shortfall: float  # largest (top1 - gain of the pick) / top1
    objective_rel: float  # |library objective - reference f(set)| / f(set)

    @property
    def ok(self) -> bool:
        return (
            self.clear_mismatches == 0
            and self.max_shortfall <= TIE_RTOL
            and self.objective_rel <= OBJ_RTOL
        )


def compare(trace, picks, gains) -> Margins:
    """Hold a library selection (``picks`` with their reported ``gains``)
    to :func:`reference_trace` of the same picks."""
    (best, top1, top2, picked), ref_obj = jax.device_get(trace)
    picks = np.asarray(picks)
    top1, top2, picked = (np.asarray(a, np.float64) for a in (top1, top2, picked))
    scale = np.maximum(np.abs(top1), np.finfo(np.float32).tiny)
    gap = (top1 - top2) / scale
    clear = gap > TIE_RTOL
    library_obj = float(np.sum(np.asarray(gains, np.float64)))
    ref_obj = float(ref_obj)
    return Margins(
        steps=len(picks),
        ties=int(np.sum(~clear)),
        clear_mismatches=int(np.sum(clear & (picks != np.asarray(best)))),
        min_clear_gap=float(gap[clear].min()) if clear.any() else float("inf"),
        max_shortfall=float(np.max((top1 - picked) / scale)),
        objective_rel=abs(library_obj - ref_obj) / abs(ref_obj),
    )


# ---------------------------------------------------------------------------
# phases


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def peak_bytes(device=None) -> int | None:
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def same_bits(a, b) -> bool:
    """GreedyResults equal bit for bit: ids, gains and n_evals."""
    return all(
        np.asarray(getattr(a, f)).tobytes() == np.asarray(getattr(b, f)).tobytes()
        for f in ("order", "gains", "n_evals")
    )


WAVES = (  # (label, optimizer, use_kernel, expected backend)
    ("naive-pallas", "NaiveGreedy", True, "pallas-fl"),
    ("lazy-pallas", "LazyGreedy", True, "pallas-fl"),
    ("naive-xla", "NaiveGreedy", False, "xla"),
)


def class_specs(kernels, budget: int, optimizer: str, use_kernel: bool) -> list:
    return [
        SelectionSpec(FacilityLocation.from_kernel(S, use_kernel=use_kernel), budget, optimizer)
        for S in kernels
    ]


def dense_phase(xs: jax.Array, sizes: Sizes) -> None:
    """Phase 2 (+ its reference checks): three served waves of one spec per
    class.  Each wave's times are reported before its checks run."""
    kernels = [create_kernel(x, metric="euclidean") for x in xs]
    server = SelectionServer()
    traces: dict = {}
    results = {}
    for label, optimizer, use_kernel, backend in WAVES:
        specs = class_specs(kernels, sizes.budget, optimizer, use_kernel)
        cold_resp, cold = timed(lambda: server.select(specs))
        warm_resp, warm = timed(lambda: server.select(specs))
        report(
            f"dense/{label}/times",
            wave_size=cold_resp[0].wave_size,
            n_bucket=cold_resp[0].n_bucket,
            first_s=cold,
            warm_s=warm,
        )
        for r, w in zip(cold_resp, warm_resp):
            check(r.backend == backend, f"{label}: backend {r.backend}, want {backend}")
            check(r.degraded is None, f"{label}: wave degraded to {r.degraded}")
            check(same_bits(r.result, w.result), f"{label}: second wave differs")
        seq = []
        for c in range(sizes.checked):
            res, t = timed(lambda: jax.device_get(solve(specs[c])))
            check(
                same_bits(cold_resp[c].result, res),
                f"{label}: served != sequential solve on class {c}",
            )
            seq.append(t)
        margins = []
        for c, r in enumerate(cold_resp):
            picks = np.asarray(r.result.order)
            key = (c, picks.tobytes())
            if key not in traces:
                traces[key] = reference_trace(xs[c], jnp.asarray(picks), sizes.block)
            m = compare(traces[key], picks, r.result.gains)
            check(m.ok, f"{label}: class {c} disagrees with the reference: {m}")
            margins.append(m)
        results[label] = [r.result for r in cold_resp]
        report(
            f"dense/{label}",
            backend=backend,
            sequential_first_s=seq[0],
            sequential_warm_s=seq[-1],
            served_eq_sequential_classes=sizes.checked,
            ties=sum(m.ties for m in margins),
            clear_mismatches=sum(m.clear_mismatches for m in margins),
            min_clear_gap=min(m.min_clear_gap for m in margins),
            max_shortfall=max(m.max_shortfall for m in margins),
            max_objective_rel=max(m.objective_rel for m in margins),
            peak_bytes_in_use=peak_bytes(),
        )
    naive = results["naive-pallas"]
    for c, (lazy, pallas, xla) in enumerate(
        zip(results["lazy-pallas"], naive, results["naive-xla"])
    ):
        check(
            np.array_equal(np.asarray(lazy.order), np.asarray(pallas.order)),
            f"lazy ids != naive ids on class {c}",
        )
        # one summation order on both routes: a breaker's Pallas -> XLA
        # reroute must answer with the Pallas route's bits
        check(same_bits(xla, pallas), f"naive-xla != naive-pallas on class {c}")
    summary = server.stats.summary()
    check(summary["fallbacks_total"] == 0, f"fallbacks: {summary['fallbacks_total']}")
    report(
        "dense/server",
        fallbacks_total=summary["fallbacks_total"],
        waves=summary["waves"],
        lazy_ids_eq_naive_classes=len(naive),
        xla_eq_pallas_classes=len(naive),
    )


def negative_control(x: jax.Array, sizes: Sizes) -> None:
    """The reference bounds must reject greedy over a similarity computed
    from bf16-rounded inputs (what one default-precision MXU pass does to
    the dot; the real default is worse, as it also mixes in f32 norms)."""
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    picks, gains = reference_greedy(rounded, sizes.budget, sizes.block)
    m = compare(reference_trace(x, picks, sizes.block), picks, gains)
    check(not m.ok, f"the reference bounds accept a bf16 similarity: {m}")
    report("reference/bf16-control", rejected=True, **dataclasses.asdict(m))


def pool_phase(pool: jax.Array, sizes: Sizes) -> None:
    """Phase 3: matrix-free LazyGreedy over the whole pool."""
    fn = FacilityLocationMF.from_features(pool, metric="euclidean", use_kernel=True)
    backend = backend_name(fn)
    check(backend == "pallas-flmf", f"pool: backend {backend}, want pallas-flmf")
    spec = SelectionSpec(fn, sizes.pool_budget, "LazyGreedy")
    first, cold = timed(lambda: jax.device_get(solve(spec)))
    second, warm = timed(lambda: jax.device_get(solve(spec)))
    check(same_bits(first, second), "pool: second solve differs")
    picks = np.asarray(first.order)
    trace, ref_s = timed(
        lambda: jax.device_get(reference_trace(pool, jnp.asarray(picks), sizes.pool_block))
    )
    m = compare(trace, picks, first.gains)
    check(m.ok, f"pool disagrees with the reference: {m}")
    report(
        "pool/lazy-flmf",
        backend=backend,
        n=int(pool.shape[0]),
        first_s=cold,
        warm_s=warm,
        reference_s=ref_s,
        peak_bytes_in_use=peak_bytes(),
        **dataclasses.asdict(m),
    )


def mesh_phase(xs: jax.Array, sizes: Sizes) -> None:
    """--four-chips: the naive Pallas wave on a 2x2 (batch, data) mesh of
    every device, against the same wave on one device."""
    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, found {len(devices)}")
    kernels = [create_kernel(x, metric="euclidean") for x in xs]
    specs = class_specs(kernels, sizes.budget, "NaiveGreedy", True)
    mesh = jax.make_mesh((2, 2), ("batch", "data"))
    before = [peak_bytes(d) for d in devices]
    sharded_server = SelectionServer(mesh=mesh)
    sharded, cold = timed(lambda: sharded_server.select(specs))
    after = [peak_bytes(d) for d in devices]
    _, warm = timed(lambda: sharded_server.select(specs))
    single, single_s = timed(lambda: SelectionServer().select(specs))
    for c, (a, b) in enumerate(zip(sharded, single)):
        check(a.degraded is None, f"mesh wave degraded to {a.degraded}")
        check(same_bits(a.result, b.result), f"mesh != single device on class {c}")
    # every device must have held its quarter of the padded wave, and the
    # three that are not the default device nothing of it before
    n_bucket = sharded[0].n_bucket
    share = len(specs) * sizes.per_class * n_bucket * 4 // 4
    for i, (d, b, a) in enumerate(zip(devices, before, after)):
        check(i == 0 or b < share, f"device {d.id} held {b} bytes before the wave")
        check(a >= share, f"device {d.id} peaked at {a} bytes < its share {share}")
    report(
        "mesh/naive-pallas",
        mesh="2x2 (batch, data)",
        backend=sharded[0].backend,
        n_bucket=n_bucket,
        first_s=cold,
        warm_s=warm,
        single_device_s=single_s,
        mesh_eq_single_classes=len(specs),
        wave_share_bytes=share,
        peak_bytes_before=before,
        peak_bytes_after_mesh_wave=after,
    )


def chip_or_exit(want: int) -> jax.Device:
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu" or dev.device_kind not in KNOWN_KINDS:
        sys.exit(
            f"chip_smoke: needs a TPU ({', '.join(KNOWN_KINDS)}); found "
            f"{dev.platform} {dev.device_kind!r}"
        )
    if len(devices) != want:
        sys.exit(f"chip_smoke: needs {want} chip(s); found {len(devices)}")
    return dev


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips",
        action="store_true",
        help="run only the 2x2-mesh wave and its one-device comparison",
    )
    args = ap.parse_args(argv)
    want = 4 if args.four_chips else 1
    dev = chip_or_exit(want)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    s = CIFAR10
    xs, gen_s = timed(
        lambda: jax.block_until_ready(embeddings(args.seed, s.classes, s.per_class, s.dim))
    )
    report("data", shape=list(xs.shape), seconds=gen_s, seed=args.seed)
    if args.four_chips:
        mesh_phase(xs, s)
    else:
        dense_phase(xs, s)
        negative_control(xs[0], s)
        pool_phase(xs.reshape(-1, s.dim), s)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": dev.platform, "kind": dev.device_kind, "count": want},
            }
        )
    )


if __name__ == "__main__":
    main()

"""Compile every Pallas entry point behind ``repro.kernels.ops`` for a TPU
v5e at deployment shapes, without a chip.

The TPU compiler is installed even where no chip is attached: a described
``v5e:2x2`` topology gives devices to compile for.  This catches what
interpret mode cannot — tiles the Mosaic compiler refuses, scoped-VMEM
overruns, programs that do not fit the chip's 16 GB of HBM — at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

Shapes are the CIFAR-10 per-class coreset deployment (10 classes of 5,000
items, 512-wide embeddings): dense sweeps at the served n-bucket of a class,
``bucket_size(5000)`` = 5,120 (FL keeps its 5,000 represented rows), the FL
sweep also vmapped over a wave of 10 classes, and matrix-free sweeps over
the whole 50,000 x 512 pool (with and without the column ids of a
symmetric source).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every test worker imports
this file.
"""
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.disp_gains import dmin_gains_pallas, dsum_gains_pallas
from repro.kernels.fb_gains import fb_gains_at_pallas, fb_gains_pallas
from repro.kernels.fl_gains import fl_gains_at_pallas, fl_gains_pallas
from repro.kernels.flmf_gains import flmf_gains_at_pallas, flmf_gains_pallas
from repro.kernels.gc_gains import gc_gains_at_pallas, gc_gains_pallas
from repro.kernels.gcmf_gains import gcmf_gains_at_pallas, gcmf_gains_pallas
from repro.kernels.sc_gains import psc_gains_pallas, sc_gains_pallas
from repro.kernels.similarity_kernel import similarity_pallas
from repro.launch.coalesce import bucket_size

HBM_BYTES = 16 * 10**9  # one TPU v5e chip
U = 5_000  # represented rows of one class
N = bucket_size(U)  # served n-bucket of a 5,000-item class
B = 10  # classes per served wave
POOL = 50_000  # whole training set, matrix-free
D = 512  # embedding width (ResNet-18 penultimate layer)
F = 512  # feature / concept columns of the modular-coverage families
K = 512  # gathered subset of a lazy step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# name -> (function of the arrays, [(shape, dtype), ...]); interpret=False
# compiles the kernel for the chip instead of the Python interpreter
CASES = {
    "fl": (partial(fl_gains_pallas, interpret=False), [((U, N), "f"), ((U,), "f")]),
    "fl_at": (
        partial(fl_gains_at_pallas, interpret=False),
        [((U, N), "f"), ((U,), "f"), ((K,), "i")],
    ),
    "fl_wave": (
        jax.vmap(partial(fl_gains_pallas, interpret=False)),
        [((B, U, N), "f"), ((B, U), "f")],
    ),
    "flmf": (
        partial(flmf_gains_pallas, metric="euclidean", interpret=False),
        [((POOL, D), "f"), ((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f")],
    ),
    "flmf_at": (
        partial(flmf_gains_at_pallas, metric="euclidean", interpret=False),
        [((POOL, D), "f"), ((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f"), ((K,), "i")],
    ),
    # a symmetric source (from_features(x)) marks its columns' own rows
    "flmf_ided": (
        lambda x, y, xx, yy, cm, ids: flmf_gains_pallas(
            x, y, xx, yy, cm, metric="euclidean", interpret=False, col_ids=ids
        ),
        [((POOL, D), "f"), ((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f"), ((POOL,), "i")],
    ),
    "flmf_at_ided": (
        lambda x, y, xx, yy, cm, idx, ids: flmf_gains_at_pallas(
            x, y, xx, yy, cm, idx, metric="euclidean", interpret=False, col_ids=ids
        ),
        [((POOL, D), "f"), ((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f"), ((K,), "i"), ((POOL,), "i")],
    ),
    "gc": (
        partial(gc_gains_pallas, interpret=False),
        [((N, N), "f"), ((N,), "f"), ((N,), "f"), ((), "f")],
    ),
    "gc_at": (
        partial(gc_gains_at_pallas, interpret=False),
        [((N, N), "f"), ((N,), "f"), ((N,), "f"), ((), "f"), ((K,), "i")],
    ),
    "gcmf": (
        partial(gcmf_gains_pallas, metric="euclidean", interpret=False),
        [((POOL, D), "f"), ((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f"), ((POOL,), "f"), ((POOL,), "f"), ((), "f")],
    ),
    "gcmf_at": (
        partial(gcmf_gains_at_pallas, metric="euclidean", interpret=False),
        [((POOL, D), "f"), ((POOL,), "f"), ((POOL,), "f"), ((POOL,), "f"),
         ((POOL,), "f"), ((), "f"), ((K,), "i")],
    ),
    "fb": (
        partial(fb_gains_pallas, interpret=False),
        [((N, F), "f"), ((F,), "f"), ((F,), "f")],
    ),
    "fb_at": (
        partial(fb_gains_at_pallas, interpret=False),
        [((N, F), "f"), ((F,), "f"), ((F,), "f"), ((K,), "i")],
    ),
    "sc": (
        partial(sc_gains_pallas, interpret=False),
        [((N, F), "f"), ((F,), "f"), ((F,), "f")],
    ),
    "psc": (
        partial(psc_gains_pallas, interpret=False),
        [((N, F), "f"), ((F,), "f"), ((F,), "f")],
    ),
    "dsum": (
        partial(dsum_gains_pallas, interpret=False),
        [((N, N), "f"), ((N,), "f")],
    ),
    "dmin": (
        partial(dmin_gains_pallas, interpret=False),
        [((N, N), "f"), ((N,), "f"), ((), "i"), ((), "f")],
    ),
    "similarity": (
        partial(similarity_pallas, metric="euclidean", interpret=False),
        [((U, D), "f"), ((U, D), "f")],
    ),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = CASES[name]
    dtypes = {"f": jnp.float32, "i": jnp.int32}
    args = [
        jax.ShapeDtypeStruct(shape, dtypes[dt], sharding=one_chip)
        for shape, dt in specs
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    assert used < HBM_BYTES, f"{name} needs {used} bytes on one 16 GB chip"


def test_served_fl_wave_names_its_kernel_fl_gains(one_chip, no_persistent_cache, monkeypatch):
    """The served FL wave's program (the batched naive greedy over a wave of
    10 classes) calls the sweep as a custom call whose instruction name is
    the kernel's fixed name, ``fl_gains`` or ``fl_gains.<n>``, which the
    trace's per-op table reads (not a name derived from a Python wrapper,
    such as ``fl_gains_pallas.11``)."""
    import re

    import repro.kernels.ops as ops
    from repro.core import FacilityLocation
    from repro.core.optimizers.batched import _batched_naive

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # compile for the chip
    sim = jax.ShapeDtypeStruct((B, U, N), jnp.float32, sharding=one_chip)
    fns = FacilityLocation(sim=sim, n=N, use_kernel=True)
    budgets = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((B, N), jnp.bool_, sharding=one_chip)
    hlo = _batched_naive.lower(fns, 512, budgets, valid, True, True).compile().as_text()
    kernels = re.findall(r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", hlo)
    assert kernels and all(re.fullmatch(r"fl_gains(\.\d+)?", k) for k in kernels), kernels


COMPUTATION = re.compile(r"(?ms)^(?:ENTRY )?%([\w.-]+) [^\n]*\{\n(.*?)\n\}")


def _called(body: str) -> list[str]:
    """The computations an HLO computation's instructions call."""
    names = re.findall(r"(?:calls|body|condition|to_apply)=%([\w.-]+)", body)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
        names += re.findall(r"%([\w.-]+)", group)
    return names


def _in_loops(hlo: str) -> set[str]:
    """Every computation that runs inside a while loop's body."""
    comps = dict(COMPUTATION.findall(hlo))
    seen, todo = set(), re.findall(r"\bbody=%([\w.-]+)", hlo)
    while todo:
        name = todo.pop()
        if name not in seen and name in comps:
            seen.add(name)
            todo += _called(comps[name])
    return seen


def _wave_hlo(optimizer: str, topo, one_chip) -> tuple[str, int, int]:
    """The compiled served FL wave on the Pallas sweep, with the batch and
    candidate widths of the kernel each device holds."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import FacilityLocation
    from repro.core.optimizers.batched import _batched_lazy, _batched_naive
    from repro.core.optimizers.distributed import FLShardRule, sharded_batched_greedy

    if optimizer == "sharded":  # 2x2 (batch, data) mesh: a quarter each
        mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("batch", "data"))

        def on(*spec):
            return NamedSharding(mesh, P(*spec))

        wave = jax.jit(
            lambda s, b, v: sharded_batched_greedy(
                FLShardRule(use_kernel=True), (s,), b, v, max_budget=512, mesh=mesh
            )
        )
        args = (
            jax.ShapeDtypeStruct((B, U, N), jnp.float32, sharding=on("batch", None, "data")),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=on("batch")),
            jax.ShapeDtypeStruct((B, N), jnp.bool_, sharding=on("batch", "data")),
        )
        return wave.lower(*args).compile().as_text(), B // 2, N // 2
    sim = jax.ShapeDtypeStruct((B, U, N), jnp.float32, sharding=one_chip)
    fns = FacilityLocation(sim=sim, n=N, use_kernel=True)
    budgets = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((B, N), jnp.bool_, sharding=one_chip)
    if optimizer == "naive":
        lowered = _batched_naive.lower(fns, 512, budgets, valid, True, True)
    else:
        lowered = _batched_lazy.lower(fns, 512, budgets, valid, 16, True, True)
    return lowered.compile().as_text(), B, N


@pytest.mark.parametrize(
    "optimizer, outside", [("naive", 1), ("lazy", 2), ("sharded", 1)]
)
def test_served_fl_wave_reads_its_kernel_in_the_loop_layout(
    optimizer, outside, topo, one_chip, no_persistent_cache, monkeypatch
):
    """The served FL wave (10 classes of 5,000 rows, n-bucket 5,120, on the
    Pallas sweep; on one chip, or on a 2x2 mesh with a quarter of the
    stacked kernel on each) keeps its kernel in one layout through the
    greedy loop: the sweep reads the loop's carry through a bitcast and the
    curmax update gathers columns from the same carry, so no step pads or
    copies a whole kernel.  The copy into that layout happens before the
    loop: once for the naive wave; the lazy wave's bound sweep before its
    loop takes a second one, since the loop keeps a buffer of its own."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)  # compile for the chip
    hlo, b, n = _wave_hlo(optimizer, topo, one_chip)
    in_loop = _in_loops(hlo)
    assert in_loop, "no while loop in the wave"
    whole = re.compile(
        rf"(?m)^  (?:ROOT )?%[\w.-]+ = f32\[{b},(?:(?:{U}|5120),{n}|{n},(?:{U}|5120))\]"
        r"\{[^}]*\} (pad|copy)\("
    )
    found = {"loop": [], "outside": []}
    for name, body in COMPUTATION.findall(hlo):
        where = "loop" if name in in_loop else "outside"
        found[where] += whole.findall(body)
    assert found["loop"] == [], found
    assert "pad" not in found["outside"], found
    assert len(found["outside"]) <= outside, found

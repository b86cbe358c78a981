"""Batched multi-query greedy engine (serving-shaped maximization).

``maximize`` answers one selection query per call; a deployment answering
many users wants the B-query form: run B independent greedy problems — same
function family, different kernels / queries / budgets — as ONE vmap-ed,
jitted program, so every per-step full sweep becomes a single batched
matmul-shaped op on the accelerator instead of B dispatches.

Heterogeneity is expressed with padding masks rather than shape polymorphism:

- different ground-set sizes: pad every instance's arrays to a common n and
  pass ``valid`` (B, n) — padded candidates are masked to -inf and never
  selected, and ``n_evals`` counts only the live candidates, so a padded
  instance reports the same count it would sequentially;
- different budgets: pass a per-instance budget vector; an instance
  freezes once its budget is spent.  The naive engine stops at max(budgets)
  even under a larger (bucketed) ``max_budget``, which only sets the width
  of the output; the lazy and sharded engines run to ``max_budget``.

The per-instance results are bit-identical to a Python loop of single
``maximize`` calls (same sweep -> argmax -> update ordering, same stopping
rule, same ``n_evals`` accounting); ``tests/test_batched.py`` pins this.
Full sweeps route through the pluggable gain backend (backends.py), so a
function family's fused Pallas sweep is used inside the batch too.

Passing ``mesh=`` (a 2-D jax Mesh) promotes the engine to the **distributed
batched** form: the batch axis shards over ``batch_axis`` and every
instance's candidate axis over ``data_axis``, running the shard_map
engines from ``optimizers/distributed.py`` — the partition-greedy sweep for
"NaiveGreedy" and the bucketed lazy engine (gathered-subset partial sweeps
+ merged stale-bound prefixes) for "LazyGreedy".  Results keep the same
bit-identical contract (``tests/test_serving.py`` pins it on a >=4-device
host mesh).

LazyGreedy's eval savings survive batching because its screen levels branch
on *scalar* ``lax.cond`` predicates shared by the wave, instead of the old
per-instance ``lax.cond`` that vmap lowers to select (both branches
executing, i.e. a full sweep every step — the ROADMAP "Lazy batched engine
efficiency" item this module closed).
"""
from __future__ import annotations

import contextlib
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.optimizers.greedy import (
    GreedyResult,
    _lazy_bucketed_impl,
    _naive_impl,
)
from repro.core.optimizers.spec import (
    OptimizerSpec,
    resolve_optimizer,
    wave_capable_names,
)


def stack_functions(fns: Sequence) -> object:
    """Stack B same-family SetFunction pytrees into one batched pytree.

    All instances must share the treedef (same class, same static meta — n,
    concave, use_kernel, ...) and per-leaf shapes; pad kernels/features to a
    common n first and express the true sizes through ``valid`` masks.
    """
    fns = list(fns)
    if not fns:
        raise ValueError("stack_functions: need at least one function")
    treedefs = {jax.tree.structure(f) for f in fns}
    if len(treedefs) != 1:
        raise ValueError(
            "stack_functions: all instances must share one function family and "
            f"static meta fields; got {len(treedefs)} distinct structures"
        )
    try:
        return jax.tree.map(lambda *leaves: jnp.stack(leaves), *fns)
    except (ValueError, TypeError) as e:
        raise ValueError(
            "stack_functions: leaf shapes differ across instances — pad every "
            "kernel/feature matrix to a common ground-set size and pass a "
            "`valid` mask to batched_maximize"
        ) from e


@partial(jax.jit, static_argnums=(1, 4, 5))
def _batched_naive(fns, max_budget, budgets, valid, stop_if_zero, stop_if_negative):
    # per-instance behaviour is greedy._naive_impl itself — the bit-identical
    # contract with sequential naive_greedy holds by construction.  The loop
    # stops at the wave's largest live budget: ``steps`` is computed outside
    # the vmap, so it stays unbatched and lowers to a while loop whose
    # predicate is one scalar (a batched predicate would select the whole
    # carry every step).  ``max_budget`` stays the static output width, so
    # waves of different budget mixes still share one compiled program.
    steps = jnp.minimum(jnp.max(budgets), max_budget)
    return jax.vmap(
        lambda fn, b, v: _naive_impl(
            fn,
            max_budget,
            stop_if_zero,
            stop_if_negative,
            budget_i=b,
            valid=v,
            steps=steps,
        )
    )(fns, budgets, valid)


@partial(jax.jit, static_argnums=(1, 4, 5, 6))
def _batched_lazy(
    fns, max_budget, budgets, valid, screen_k, stop_if_zero, stop_if_negative
):
    # the bucketed lazy sweep IS the sequential lazy_greedy (B=1) run with an
    # explicit batch dimension, so bit-identity holds by construction — and,
    # unlike the old vmap(_lazy_impl) form, its screen levels gate on SCALAR
    # lax.cond predicates, so an all-accept step costs O(B * screen_k)
    # gathered evals instead of the O(B * n) select-lowered full sweep
    return _lazy_bucketed_impl(
        fns, max_budget, budgets, valid, screen_k, stop_if_zero, stop_if_negative
    )


class BatchedEngine:
    """A reusable B-instance selection engine (the serving shape).

    Stacking B kernel/feature matrices costs O(B * n * stat) HBM traffic, so
    a server does it ONCE at ingest and then answers many selection calls
    against the resident batch; each :meth:`maximize` is a single jitted
    dispatch.  ``batched_maximize`` is the one-shot convenience wrapper.

    With ``mesh=`` the resident batch is laid out over a 2-D device mesh:
    batch axis over ``batch_axis``, candidate axis over ``data_axis`` (B and
    n must each be a multiple of the corresponding mesh axis size — the
    serving coalescer in ``launch/coalesce.py`` pads waves to guarantee
    this).
    """

    def __init__(
        self,
        fns: Sequence,
        valid: jax.Array | None = None,
        mesh: jax.sharding.Mesh | None = None,
        batch_axis: str = "batch",
        data_axis: str = "data",
    ):
        fns = list(fns)
        if not fns:
            raise ValueError("BatchedEngine: need at least one instance")
        self.fns = fns
        self.batch_size = len(fns)
        self.n = fns[0].n
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.data_axis = data_axis
        self._stacked = None
        # greedy steps the last enqueued loop runs (see enqueue)
        self.steps_run: int | None = None
        if mesh is None:
            self._stacked = stack_functions(fns)
        else:
            from repro.core.optimizers.distributed import shard_rule, stack_parts

            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for name, dim, what in (
                (batch_axis, self.batch_size, "batch size"),
                (data_axis, self.n, "ground-set size"),
            ):
                if name not in sizes:
                    raise ValueError(
                        f"mesh has no axis {name!r} (axes: {mesh.axis_names})"
                    )
                if dim % sizes[name]:
                    raise ValueError(
                        f"{what} {dim} is not a multiple of mesh axis "
                        f"{name!r} size {sizes[name]}"
                    )
            self.rule = shard_rule(fns[0])
            self.parts = stack_parts(self.rule, fns)
        self.valid = (
            jnp.ones((self.batch_size, self.n), bool)
            if valid is None
            else jnp.asarray(valid, bool)
        )
        if self.valid.shape != (self.batch_size, self.n):
            raise ValueError(
                f"valid mask must be ({self.batch_size}, {self.n}), "
                f"got {self.valid.shape}"
            )

    @property
    def stacked(self):
        """The B-stacked function pytree; built lazily on a mesh (only the
        mesh-replicated optimizer path needs it there)."""
        if self._stacked is None:
            self._stacked = stack_functions(self.fns)
        return self._stacked

    def run(
        self,
        budget: int | Sequence[int],
        optimizer: OptimizerSpec | str = "NaiveGreedy",
        *,
        stop_if_zero: bool = True,
        stop_if_negative: bool = True,
        max_budget: int | None = None,
        span: Callable = contextlib.nullcontext,
    ) -> list[GreedyResult]:
        """Solve the resident batch through the optimizer registry:
        :meth:`enqueue`, then :meth:`fetch`.

        This is the typed engine path behind ``solve()`` and the serving
        dispatch.  ``span(name)`` is a context-manager factory that brackets
        the two steps, ``"engine.launch"`` and ``"engine.fetch"``; the
        serving layer passes its span recorder (nothing here reads a clock).
        """
        with span("engine.launch"):
            launched = self.enqueue(
                budget,
                optimizer,
                stop_if_zero=stop_if_zero,
                stop_if_negative=stop_if_negative,
                max_budget=max_budget,
            )
        with span("engine.fetch"):
            return self.fetch(launched)

    def enqueue(
        self,
        budget: int | Sequence[int],
        optimizer: OptimizerSpec | str = "NaiveGreedy",
        *,
        stop_if_zero: bool = True,
        stop_if_negative: bool = True,
        max_budget: int | None = None,
    ) -> tuple[GreedyResult, list[int]]:
        """Dispatch the batch's selection program and return at once: the
        batched :class:`GreedyResult` of device arrays, and the per-instance
        budgets that :meth:`fetch` slices it by.

        The optimizer (an :class:`OptimizerSpec`, or a name built into one)
        carries its validated hyperparameters, and the registry supplies the
        batched / sharded execution hook — an optimizer without one is
        rejected here with the batched-capable set named, never mid-trace.
        ``max_budget`` optionally raises the static output width above
        max(budgets) — serving uses bucketed widths so waves with different
        budget mixes share one compiled program.  The naive loop still stops
        at max(budgets); the others run to ``max_budget``.  Either way the
        step count is left in ``self.steps_run``.
        """
        opt = OptimizerSpec(optimizer) if not isinstance(optimizer, OptimizerSpec) else optimizer
        defn = resolve_optimizer(opt.name)
        B = self.batch_size
        budgets = (
            [int(budget)] * B
            if isinstance(budget, (int, np.integer))
            else [int(b) for b in budget]
        )
        if len(budgets) != B:
            raise ValueError(
                f"budget list has {len(budgets)} entries for {B} instances"
            )
        max_budget = max(budgets) if max_budget is None else int(max_budget)
        if max_budget < max(budgets):
            raise ValueError(
                f"max_budget {max_budget} < largest per-instance budget "
                f"{max(budgets)}"
            )
        b_arr = jnp.asarray(budgets, jnp.int32)
        # on a mesh: a collective sharded engine when the optimizer has one,
        # else a mesh-replicated optimizer runs its batched hook as-is (the
        # program is sequential in its data pass, so every device computes
        # the identical answer — on-mesh == off-mesh bit-identity holds)
        sharded = self.mesh is not None and defn.sharded_run is not None
        hook = defn.sharded_run if sharded else defn.batched_run
        if hook is None or (
            self.mesh is not None and not sharded and not defn.mesh_replicated
        ):
            raise ValueError(
                f"optimizer {opt.name!r} does not support "
                f"{'sharded' if self.mesh is not None else 'batched'} "
                f"execution; batched-capable optimizers: {wave_capable_names()}"
            )
        # the single-device naive loop stops at the largest live budget;
        # every other loop runs to the bucket
        self.steps_run = (
            max(budgets) if opt.name == "NaiveGreedy" and not sharded else max_budget
        )
        if sharded:
            order, gains, evals, value = hook(
                self.rule,
                self.parts,
                b_arr,
                self.valid,
                max_budget,
                self.mesh,
                (self.batch_axis,),
                (self.data_axis,),
                stop_if_zero,
                stop_if_negative,
                **opt.params,
            )
            res = GreedyResult(order=order, gains=gains, n_evals=evals, value=value)
        else:
            res = hook(
                self.stacked,
                max_budget,
                b_arr,
                self.valid,
                stop_if_zero,
                stop_if_negative,
                **opt.params,
            )
        return res, budgets

    def fetch(self, launched: tuple[GreedyResult, list[int]]) -> list[GreedyResult]:
        """Wait for an :meth:`enqueue`-d program and slice its answer per
        instance."""
        res, budgets = launched
        # one transfer for the whole batch, then host-side slicing — B tiny
        # device slices would dominate small-query serving latency
        order, gains, evals, value = jax.device_get(
            (res.order, res.gains, res.n_evals, res.value)
        )
        return [
            GreedyResult(
                order=order[i, :b],
                gains=gains[i, :b],
                n_evals=evals[i],
                value=value[i],
            )
            for i, b in enumerate(budgets)
        ]

    def maximize(
        self,
        budget: int | Sequence[int],
        optimizer: str = "NaiveGreedy",
        return_result: bool = False,
        max_budget: int | None = None,
        **kwargs,
    ) -> list:
        """Deprecated: delegate to :meth:`run` with an :class:`OptimizerSpec`
        built from ``optimizer`` + kwargs (unknown options now raise)."""
        from repro.core.optimizers.api import _warn_shim

        _warn_shim(
            "BatchedEngine.maximize()",
            "BatchedEngine.run(budgets, OptimizerSpec(...))",
        )
        opt, stop_zero, stop_neg = _legacy_optimizer_spec(optimizer, kwargs)
        results = self.run(
            budget,
            opt,
            stop_if_zero=stop_zero,
            stop_if_negative=stop_neg,
            max_budget=max_budget,
        )
        return results if return_result else [r.as_list() for r in results]


def _legacy_optimizer_spec(optimizer: str, kwargs: dict):
    """Split legacy ``**kwargs`` into (OptimizerSpec, stop_zero, stop_neg).

    Shared by the deprecated engine entry points: stop rules keep their old
    engine-level ``True`` defaults (family defaults are a spec-layer
    concern), everything else is validated as optimizer hyperparameters —
    so a misspelled flag raises instead of being silently dropped.
    """
    kwargs = dict(kwargs)
    stop_zero = bool(kwargs.pop("stopIfZeroGain", True))
    stop_neg = bool(kwargs.pop("stopIfNegativeGain", True))
    return OptimizerSpec(optimizer, **kwargs), stop_zero, stop_neg


def batched_maximize(
    fns: Sequence,
    budget: int | Sequence[int],
    optimizer: str = "NaiveGreedy",
    valid: jax.Array | None = None,
    return_result: bool = False,
    mesh: jax.sharding.Mesh | None = None,
    batch_axis: str = "batch",
    data_axis: str = "data",
    **kwargs,
) -> list:
    """Deprecated one-shot wrapper: solve B selection problems in one jitted
    program.  Use ``solve([SelectionSpec(...), ...], mode="batched")`` (or
    ``mesh=`` for the sharded route); for a padded batch with a ``valid``
    mask, build a :class:`BatchedEngine` and call :meth:`BatchedEngine.run`.

    Args:
      fns: B same-family SetFunction instances (identical static meta).
      budget: shared int or per-instance sequence of ints.
      optimizer: "NaiveGreedy" or "LazyGreedy" (both also run sharded).
      valid: optional (B, n) bool — False marks padded candidates.
      return_result: True -> list of per-instance :class:`GreedyResult`
        (order/gains sliced to that instance's budget), False -> list of
        submodlib-style [(index, gain), ...] lists.
      mesh: optional 2-D mesh — shard the batch axis over ``batch_axis`` and
        the candidate axis over ``data_axis`` (the distributed batched form).
      kwargs: stopIfZeroGain / stopIfNegativeGain / optimizer
        hyperparameters (screen_k); unknown options raise ``TypeError``.
    """
    from repro.core.optimizers.api import _warn_shim

    _warn_shim(
        "batched_maximize()",
        'solve([SelectionSpec(...), ...], mode="batched")',
    )
    fns = list(fns)
    if not fns:
        return []
    opt, stop_zero, stop_neg = _legacy_optimizer_spec(optimizer, kwargs)
    engine = BatchedEngine(
        fns, valid=valid, mesh=mesh, batch_axis=batch_axis, data_axis=data_axis
    )
    results = engine.run(
        budget, opt, stop_if_zero=stop_zero, stop_if_negative=stop_neg
    )
    return results if return_result else [r.as_list() for r in results]

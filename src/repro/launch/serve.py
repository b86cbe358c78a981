"""Selection serving: a coalescing front door over the batched engines.

This is the serving shape for submodular subset selection (the paper's
engine is single-node, one query at a time): clients submit selection
requests — :class:`~repro.core.optimizers.spec.SelectionSpec` objects, the
same typed request the whole library runs on — and the server answers them
in **waves**:

  submit()  ->  per-(family, n-bucket) pending queue  [continuous batching]
  flush()   ->  drain queues into padded waves
            ->  one batched-engine dispatch per wave
                  (single device, or a 2-D batch x data mesh via ``mesh=``)
            ->  demultiplex per-request responses + structured metrics

Requests queue **per group** (the coalescer's :func:`~repro.launch.coalesce.
group_key`, computed shape-only at submit time), so a front end can flush
one hot group the moment it fills while a cold group keeps waiting for
co-travellers — the continuous-batching discipline LLM servers use, applied
to selection waves.  ``submit`` applies **backpressure**: when ``max_queue``
requests are already pending, it raises :class:`ServerOverloaded` instead
of letting the queue grow without bound.  Specs may carry a ``deadline_s``;
the async front end flushes a group early to honor the earliest deadline,
and responses report whether theirs was missed.

Failure discipline: a mid-flush engine error raises :class:`FlushError`
carrying the exact partition of the work — already-computed responses are
re-held for the next flush, never-dispatched requests are re-enqueued at
the front of their queues, and only the poisoned wave's requests are named
as failed (and also re-enqueued by ``flush()``, so the caller can ``cancel``
them or retry).  Nothing is ever dropped.

Results are bit-identical to a loop of single ``maximize`` calls per request
(``tests/test_serving.py`` pins this): zero-padding adds zero-gain
candidates that the ``valid`` mask blocks, budget bucketing only extends the
frozen tail of the greedy loop, and the sharded path preserves the
sweep -> first-argmax -> update ordering exactly.  Per-group queueing only
changes *when* a wave dispatches, never what rides it.

    # 8 host devices, 2x2 batch x data mesh, a mixed random workload:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --requests 32 --mesh 2x2

See docs/serving.md for the request lifecycle and knob table,
``launch/metrics.py`` for the metrics schema, and benchmarks/serve_bench.py
for the wave-size x mesh-shape throughput sweep.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Optional, Sequence

import numpy as np

from repro.core.optimizers.backends import backend_name
from repro.core.optimizers.batched import BatchedEngine
from repro.core.optimizers.spec import (
    SelectionSpec,
    resolve_optimizer,
    wave_capable_names,
)
from repro.launch import faults
from repro.launch.coalesce import (
    SelectionRequest,
    Wave,
    group_key,
    group_label,
    waves_for_group,
)
from repro.launch.metrics import ServerMetrics
from repro.launch.resilience import (
    SINGLE_ATTEMPT,
    BreakerBoard,
    RequestFailed,
    RetryPolicy,
)


class ServerOverloaded(RuntimeError):
    """``submit`` refused: the server already holds ``max_queue`` pending
    requests.  Retry after a flush drains the queue, raise ``max_queue``, or
    (async front end) submit with ``block=True`` to wait for space."""


class FlushError(RuntimeError):
    """An engine dispatch failed mid-flush.

    Carries the exact partition of the flush's work so no request and no
    computed response is ever lost:

    - ``completed``: {rid: response} for waves that finished BEFORE the
      failure (``flush()`` re-holds these for its next call);
    - ``failed_requests``: the poisoned wave's requests (``flush()``
      re-enqueues them at the front of their queue — ``cancel(rid)`` them
      before retrying if the poison is the request itself);
    - ``undispatched_requests``: requests whose waves never ran
      (``flush()`` re-enqueues them, original arrival stamps intact).

    ``__cause__`` is the engine's original exception.
    """

    def __init__(
        self,
        message: str,
        *,
        completed: dict,
        failed_requests: list,
        undispatched_requests: list,
    ):
        super().__init__(message)
        self.completed = completed
        self.failed_requests = failed_requests
        self.undispatched_requests = undispatched_requests

    @property
    def failed_rids(self) -> list:
        return [r.rid for r in self.failed_requests]

    @property
    def undispatched_rids(self) -> list:
        return [r.rid for r in self.undispatched_requests]


@dataclasses.dataclass
class SelectionResponse:
    """Answer to one request, plus where/how it was served.

    Latency accounting is truthful and decomposed: ``queue_s`` is how long
    THIS request waited for co-travellers (its ``serve.queue`` span: submit
    -> its wave's dispatch start), ``wave_s`` is the wave's ``serve.wave``
    span (shared by the wave), and ``latency_s`` is their sum — what the
    client observed.  A
    request that waited 500 ms for a 10 ms wave reports 510 ms, not 10.
    """

    rid: int | str
    selection: list  # [(index, gain), ...] in pick order, true-n index space
    result: object  # the per-request GreedyResult (== sequential solve)
    wave_size: int  # real requests in the wave that served this
    n_bucket: int  # padded ground-set size of that wave
    backend: str  # gain-sweep backend that answered ("xla", "pallas-fl", ...)
    latency_s: float  # client-observed: queue_s + wave_s
    queue_s: float = 0.0  # submit -> wave dispatch start (this request's wait)
    wave_s: float = 0.0  # the wave's serve.wave span (shared by the wave)
    deadline_missed: bool = False  # delivered after the spec's deadline_s
    attempts: int = 1  # dispatch attempts this request survived (retries + 1)
    degraded: str | None = None  # "xla" / "single-device" when a breaker
    #   rerouted the wave off its primary backend or mesh (results are still
    #   bit-identical to sequential solve — only the implementation changed)


class ServerStats:
    """Aggregate accounting across flushes — a bounded-memory view over
    :class:`~repro.launch.metrics.ServerMetrics`.

    Replaces the old unbounded ``wave_seconds`` list: totals are exact
    (count / sum / max), percentiles come from a fixed-size reservoir, so a
    long-lived server's accounting is O(1) in flush count.  ``summary()``
    keeps the historical keys (requests / waves / slots / padded_slots /
    total_s: the sum of wave times, queueing left out) and adds the
    latency-decomposition and backpressure fields; ``snapshot()`` is the
    full structured tree.
    """

    def __init__(self, metrics: ServerMetrics | None = None):
        self.metrics = metrics if metrics is not None else ServerMetrics()

    @property
    def requests(self) -> int:
        return self.metrics.counters["requests"]

    @property
    def waves(self) -> int:
        return self.metrics.counters["waves"]

    @property
    def slots(self) -> int:  # total engine slots dispatched (incl. batch pads)
        return self.metrics.counters["slots"]

    @property
    def padded_slots(self) -> int:  # batch-pad slots (wasted work)
        return self.metrics.counters["padded_slots"]

    @property
    def rejections(self) -> int:  # submits refused by backpressure
        return self.metrics.counters["rejections"]

    @property
    def total_seconds(self) -> float:
        return float(self.metrics.wave_s.total)

    def summary(self) -> dict:
        m = self.metrics
        return {
            "requests": self.requests,
            "waves": self.waves,
            "slots": self.slots,
            "padded_slots": self.padded_slots,
            "total_s": round(self.total_seconds, 4),
            "wave_p50_s": round(m.wave_s.percentile(0.50), 4) if self.waves else 0.0,
            "wave_p99_s": round(m.wave_s.percentile(0.99), 4) if self.waves else 0.0,
            "queue_p50_s": round(m.queue_s.percentile(0.50), 4)
            if m.queue_s.count
            else 0.0,
            "queue_p99_s": round(m.queue_s.percentile(0.99), 4)
            if m.queue_s.count
            else 0.0,
            "rejections": self.rejections,
            "deadline_misses": m.counters["deadline_misses"],
            "retries_total": m.counters["retries_total"],
            "fallbacks_total": m.counters["fallbacks_total"],
            "quarantined_total": m.counters["quarantined_total"],
            "breaker_state": dict(sorted(m.breaker_states.items())),
        }

    def snapshot(self) -> dict:
        """The full structured metric tree (see launch/metrics.py schema)."""
        return self.metrics.snapshot()


class SelectionServer:
    """Per-group coalescing selection server over :class:`BatchedEngine`.

    Args:
      mesh: None for single-device serving, or a 2-D mesh whose
        ``batch_axis`` shards the wave's batch dimension and ``data_axis``
        shards every instance's candidate axis (the distributed batched
        engine).  Wave padding automatically rounds up to the mesh axis
        sizes.
      max_wave: cap on real requests per wave (bounds per-wave latency).
      max_queue: admission-control cap on TOTAL pending requests across all
        group queues; ``submit`` raises :class:`ServerOverloaded` beyond it.
        None (default) disables backpressure.
      retry_policy: server-wide default :class:`~repro.launch.resilience.
        RetryPolicy`.  When it is set — or any pending spec carries its own
        ``retry`` — ``flush()`` switches to the resilient path: transient
        wave failures are retried with backoff, the poison request is
        isolated into a singleton wave so it cannot re-poison its group,
        and exhausted requests resolve to typed
        :class:`~repro.launch.resilience.RequestFailed` entries
        (``take_failures()``) instead of aborting the flush.  A request's
        ``spec.retry`` always wins over the server default.  With neither
        set, ``flush()`` keeps the legacy single-attempt
        :class:`FlushError` contract exactly.
      breakers: a :class:`~repro.launch.resilience.BreakerBoard` (one is
        created when omitted).  Dispatch consults ``(family, "kernel")``
        before running a fused backend and ``(family, "mesh")`` before a
        mesh dispatch; an open breaker reroutes the wave degraded —
        Pallas -> XLA via ``use_kernel=False``, mesh -> single device —
        which stays bit-identical to sequential ``solve()``.

    The dispatch path is synchronous; ``submit`` only enqueues (into the
    request's group queue — the coalescer's wave identity promoted to queue
    identity).  The async front-end that flushes each group on its own
    depth / timer / deadline triggers and completes futures is
    :class:`repro.launch.async_serve.AsyncSelectionServer`; it drives this
    server through ``drain`` / ``dispatch_waves`` so its lock never covers
    an engine dispatch.
    """

    def __init__(
        self,
        mesh=None,
        batch_axis: str = "batch",
        data_axis: str = "data",
        max_wave: int = 64,
        max_queue: int | None = None,
        retry_policy: RetryPolicy | None = None,
        breakers: BreakerBoard | None = None,
    ):
        self.mesh = mesh
        self.batch_axis = batch_axis
        self.data_axis = data_axis
        self.max_wave = max_wave
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 or None, got {max_queue}")
        self.max_queue = max_queue
        if retry_policy is not None and not isinstance(retry_policy, RetryPolicy):
            raise TypeError(
                f"retry_policy must be a RetryPolicy or None, "
                f"got {type(retry_policy).__name__!r}"
            )
        self.retry_policy = retry_policy
        self.breakers = breakers if breakers is not None else BreakerBoard()
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            for name in (batch_axis, data_axis):
                if name not in sizes:
                    raise ValueError(
                        f"mesh has no axis {name!r} (axes: {mesh.axis_names})"
                    )
            self.b_multiple = sizes[batch_axis]
            self.n_multiple = sizes[data_axis]
        else:
            self.b_multiple = 1
            self.n_multiple = 1
        # group_key -> FIFO of SelectionRequests (insertion-ordered dict, so
        # flush order follows each group's first arrival)
        self._queues: dict[tuple, list[SelectionRequest]] = {}
        self._undelivered: dict = {}  # flushed but not yet returned to a caller
        self._failures: dict = {}  # rid -> RequestFailed, not yet taken
        self._attempts: dict = {}  # rid -> [attempt dicts] across retries
        self._next_rid = 0
        self._dispatch_seq = 0  # 0-based dispatch ordinal (fault addressing)
        self.metrics = ServerMetrics()
        self.stats = ServerStats(self.metrics)
        self.breakers.bind(self.metrics.set_breaker)

    # -- request ingest ------------------------------------------------------

    @property
    def pending_count(self) -> int:
        """Total pending requests across all group queues."""
        return sum(len(q) for q in self._queues.values())

    def submit_spec(self, spec: SelectionSpec, rid=None):
        """Enqueue one validated :class:`SelectionSpec` into its group's
        queue; returns its request id.

        Everything that could poison a flush is rejected HERE, at submit
        time, so a bad request can never abort the flush that would have
        answered everyone else's:

        - an unsupported function family (no registered padder) raises
          ``NotImplementedError`` naming ``register_padder``;
        - an optimizer without batched execution hooks (e.g.
          StochasticGreedy) raises ``ValueError`` naming the batched-capable
          set;
        - a full server (``max_queue`` pending) raises
          :class:`ServerOverloaded` — admission control, counted under
          ``rejections``.

        Unknown optimizer names, misspelled hyperparameters, and family
        stop-rule defaults were already handled when the spec was built —
        requests are specs, so serving adds no second validation dialect.
        """
        from repro.launch.coalesce import resolve_padder

        with self.metrics.span("serve.submit") as sp:
            if not isinstance(spec, SelectionSpec):
                raise TypeError(
                    f"submit_spec() takes a SelectionSpec, got {type(spec).__name__!r}"
                )
            resolve_padder(type(spec.fn))  # raises NotImplementedError if unsupported
            defn = resolve_optimizer(spec.optimizer.name)
            if not defn.batched_capable:
                raise ValueError(
                    f"optimizer {spec.optimizer.name!r} has no batched execution "
                    f"hooks, so it cannot ride served waves; batched-capable "
                    f"optimizers: {wave_capable_names()}"
                )
            if self.max_queue is not None and self.pending_count >= self.max_queue:
                self.metrics.inc("rejections")
                raise ServerOverloaded(
                    f"pending queue is full ({self.pending_count}/{self.max_queue} "
                    f"requests); flush, raise max_queue, or retry after a drain"
                )
            if rid is None:
                rid = self._next_rid
                self._next_rid += 1
            sp.attrs["rid"] = rid
            req = SelectionRequest(rid=rid, spec=spec)
            key = group_key(req, n_multiple=self.n_multiple)
            queue = self._queues.setdefault(key, [])
            queue.append(req)
            self.metrics.observe_enqueue(
                group_label(req, n_multiple=self.n_multiple), len(queue)
            )
            return rid

    def submit(
        self,
        request,
        budget: int | None = None,
        optimizer: str | None = None,
        rid=None,
        **kwargs,
    ):
        """Enqueue one selection request; returns its request id.

        The request is a :class:`SelectionSpec` (the typed path —
        equivalent to :meth:`submit_spec`).  The legacy
        ``submit(fn, budget, optimizer=..., stopIfZeroGain=..., screen_k=...)``
        form is deprecated: it builds the spec for you (family stop-rule
        defaults — e.g. Disparity*'s ``stopIfZeroGain=False`` — now resolve
        inside :class:`SelectionSpec`, so sequential and served execution
        agree) and emits a ``DeprecationWarning``.
        """
        if isinstance(request, SelectionSpec):
            if budget is not None or optimizer is not None or kwargs:
                raise TypeError(
                    "submit(spec) takes no extra options — budget, optimizer "
                    "and stop rules already live on the SelectionSpec"
                )
            return self.submit_spec(request, rid=rid)
        from repro.core.optimizers.api import _warn_shim

        _warn_shim(
            "SelectionServer.submit(fn, budget, ...)",
            "SelectionServer.submit(SelectionSpec(fn, budget, ...))",
        )
        spec = SelectionSpec(
            request,
            budget,
            "NaiveGreedy" if optimizer is None else optimizer,
            stopIfZeroGain=kwargs.pop("stopIfZeroGain", None),
            stopIfNegativeGain=kwargs.pop("stopIfNegativeGain", None),
            **kwargs,
        )
        return self.submit_spec(spec, rid=rid)

    def open_session(self, spec: SelectionSpec, *, sid=None, journal=None):
        """Open a long-lived :class:`~repro.launch.sessions.SelectionSession`
        around ``spec``: feed ground-set deltas with ``extend(features=...)``
        / ``extend(indices=...)`` and get the refreshed selection after each.
        Deltas ride the normal per-group queues (same coalescing, same
        backpressure), so every update is bit-identical to a direct
        ``solve()`` over the stream so far.  Pass a
        :class:`~repro.launch.sessions.SessionJournal` (and optionally a
        stable ``sid``) to journal committed deltas for crash recovery via
        :func:`~repro.launch.sessions.restore_sessions`."""
        from repro.launch.sessions import SelectionSession

        return SelectionSession(self, spec, sid=sid, journal=journal)

    def cancel(self, rid) -> bool:
        """Remove one pending request (or one undelivered response) by id.
        Returns True if something was removed.  The escape hatch after a
        :class:`FlushError` named a poisoned request as failed: cancel it
        and re-flush the survivors."""
        for key, queue in list(self._queues.items()):
            for i, req in enumerate(queue):
                if req.rid == rid:
                    del queue[i]
                    if not queue:
                        del self._queues[key]
                    return True
        return self._undelivered.pop(rid, None) is not None

    def group_states(self) -> list[tuple]:
        """Scheduling view of the pending queues: one
        ``(group_key, depth, oldest_enqueue_t, earliest_deadline_t)`` tuple
        per non-empty group (``earliest_deadline_t`` is None when no member
        carries a deadline).  The async front end's flush triggers read
        this; it is also handy for dashboards."""
        out = []
        for key, queue in self._queues.items():
            deadlines = [t for t in (r.deadline_t for r in queue) if t is not None]
            out.append(
                (
                    key,
                    len(queue),
                    queue[0].enqueue_t,
                    min(deadlines) if deadlines else None,
                )
            )
        return out

    # -- dispatch ------------------------------------------------------------

    def _dispatch(self, wave: Wave) -> dict:
        fam = type(wave.requests[0].spec.fn).__name__
        widx = self._dispatch_seq
        self._dispatch_seq += 1
        # bookkeeping probe: the wave's PRIMARY backend, for breaker routing
        # and fault addressing — suspended so it never consumes fault budget
        with faults.suspended():
            primary = backend_name(wave.fns[0])
        fns, mesh = wave.fns, self.mesh
        kernel_degraded = mesh_degraded = False
        if primary != "xla" and not self.breakers.allow((fam, "kernel")):
            # open kernel breaker: reroute Pallas -> XLA.  use_kernel is a
            # static meta field, so replace() only changes the trace-time
            # backend choice — results stay bit-identical (pinned parity).
            fns = [dataclasses.replace(f, use_kernel=False) for f in fns]
            kernel_degraded = True
        if mesh is not None and not self.breakers.allow((fam, "mesh")):
            mesh = None  # open mesh breaker: serve single-device
            mesh_degraded = True
        degraded = "+".join(
            label
            for flag, label in (
                (kernel_degraded, "xla"),
                (mesh_degraded, "single-device"),
            )
            if flag
        ) or None
        if degraded is not None:
            self.metrics.inc("fallbacks_total")
        label = wave.label
        metrics = self.metrics
        with metrics.span(
            "serve.wave",
            wave=True,
            rids=tuple(r.rid for r in wave.requests),
            B=wave.batch_size,
            n_bucket=wave.n_bucket,
            bytes_stacked=wave.bytes_stacked,
            bytes_live=wave.bytes_live,
            cols_stacked=wave.cols_stacked,
            cols_live=wave.cols_live,
            steps_bucket=wave.max_budget,
        ) as sp:
            queued = [
                metrics.record_span(
                    "serve.queue", req.enqueue_ns, sp.start_ns, parent=sp, rid=req.rid
                )
                for req in wave.requests
            ]
            try:
                faults.check(
                    "dispatch",
                    family=fam,
                    backend=primary,
                    wave_index=widx,
                    mesh=mesh is not None,
                    rids=sp.attrs["rids"],
                    label=label,
                )
                # host-side backend resolution doubles as the "kernel" fault
                # boundary (resolve_backend); also names the backend that
                # actually answers after any degraded rewrite
                name = sp.attrs["backend"] = backend_name(fns[0])
                with metrics.span("engine.build"):
                    engine = BatchedEngine(
                        fns,
                        valid=wave.valid,
                        mesh=mesh,
                        batch_axis=self.batch_axis,
                        data_axis=self.data_axis,
                    )
                results = engine.run(
                    wave.budgets,
                    wave.optimizer,
                    stop_if_zero=wave.stop_if_zero,
                    stop_if_negative=wave.stop_if_negative,
                    max_budget=wave.max_budget,
                    span=metrics.span,
                )
                sp.attrs["steps_run"] = engine.steps_run
            except Exception as e:
                # attribute the failure to the path that was actually in
                # play: kernel-site faults (and any error while a fused
                # backend was live) charge the kernel breaker; dispatch
                # errors on a mesh charge the mesh breaker
                site = getattr(e, "site", None)
                if site == "kernel":
                    self.breakers.record_failure((fam, "kernel"))
                elif mesh is not None:
                    self.breakers.record_failure((fam, "mesh"))
                elif primary != "xla" and not kernel_degraded:
                    self.breakers.record_failure((fam, "kernel"))
                raise
            if primary != "xla" and not kernel_degraded:
                self.breakers.record_success((fam, "kernel"))
            if mesh is not None:
                self.breakers.record_success((fam, "mesh"))
            with metrics.span("serve.demux"):
                by_rid = wave.demux(results)
                out = {
                    req.rid: SelectionResponse(
                        rid=req.rid,
                        selection=by_rid[req.rid].as_list(),
                        result=by_rid[req.rid],
                        wave_size=len(wave.requests),
                        n_bucket=wave.n_bucket,
                        backend=name,
                        latency_s=q.seconds,
                        queue_s=q.seconds,
                        degraded=degraded,
                    )
                    for req, q in zip(wave.requests, queued)
                }
        # the wave's time is its span's: known once the span has closed
        wave_s, end_t = sp.seconds, sp.end_ns * 1e-9
        metrics.observe_wave(
            label,
            wave_s,
            requests=len(wave.requests),
            slots=wave.batch_size,
            padded_slots=wave.n_padded_slots,
            bytes_stacked=wave.bytes_stacked,
            bytes_live=wave.bytes_live,
            cols_stacked=wave.cols_stacked,
            cols_live=wave.cols_live,
            steps_run=sp.attrs["steps_run"],
            steps_bucket=wave.max_budget,
        )
        for req in wave.requests:
            resp = out[req.rid]
            resp.wave_s = wave_s
            resp.latency_s += wave_s
            resp.deadline_missed = req.deadline_t is not None and end_t > req.deadline_t
            metrics.observe_served(
                label, resp.queue_s, deadline_missed=resp.deadline_missed
            )
        return out

    def drain(
        self, keys: Optional[Sequence[tuple]] = None, *, take_undelivered: bool = True
    ) -> tuple[list[Wave], dict]:
        """Atomically remove pending requests and build their waves.

        Args:
          keys: group keys to drain (default: every non-empty group).  This
            is the continuous-batching hook — a front end drains just the
            groups whose own trigger fired.
          take_undelivered: also take (and clear) the held responses from
            earlier partial flushes; ``flush()`` wants them, the async front
            end leaves them for the sync caller.

        Returns ``(waves, undelivered)``.  ALL waves are built before any
        queue entry is removed, so a wave-build error leaves the server
        state fully intact (nothing half-drained).
        """
        if keys is None:
            keys = list(self._queues)
        waves: list[Wave] = []
        with self.metrics.span("serve.drain") as sp:
            for key in keys:
                requests = self._queues.get(key)
                if not requests:
                    continue
                waves.extend(
                    waves_for_group(
                        requests,
                        max_wave=self.max_wave,
                        n_multiple=self.n_multiple,
                        b_multiple=self.b_multiple,
                    )
                )
            for key in keys:
                self._queues.pop(key, None)
            sp.attrs["waves"] = len(waves)
        undelivered: dict = {}
        if take_undelivered:
            undelivered, self._undelivered = self._undelivered, {}
        return waves, undelivered

    def dispatch_waves(self, waves: Sequence[Wave]) -> dict:
        """Dispatch already-built waves in order; returns {rid: response}.

        Pure compute — touches no queues, so it is safe to call OUTSIDE any
        lock guarding them.  On an engine error it raises
        :class:`FlushError` carrying the exact work partition (completed
        responses / failed wave / undispatched waves); the caller decides
        how to re-hold and re-enqueue.
        """
        responses: dict = {}
        for i, wave in enumerate(waves):
            try:
                responses.update(self._dispatch(wave))
            except Exception as e:
                self.metrics.inc("flush_errors")
                undispatched = [r for w in waves[i + 1 :] for r in w.requests]
                failed = list(wave.requests)
                raise FlushError(
                    f"wave {i + 1}/{len(waves)} ({wave.label}, "
                    f"{len(failed)} requests: {[r.rid for r in failed]}) "
                    f"failed: {e}; {len(responses)} completed responses held, "
                    f"{len(undispatched)} undispatched requests preserved",
                    completed=responses,
                    failed_requests=failed,
                    undispatched_requests=undispatched,
                ) from e
        return responses

    def requeue(self, requests: Sequence[SelectionRequest]) -> None:
        """Put drained-but-unserved requests back at the FRONT of their
        group queues, original arrival stamps intact (so queue-time
        accounting spans the failure, truthfully)."""
        for req in reversed(list(requests)):
            key = group_key(req, n_multiple=self.n_multiple)
            self._queues.setdefault(key, []).insert(0, req)
        if requests:
            self.metrics.inc("requeued", len(requests))

    # -- resilience ----------------------------------------------------------

    def _resilience_active(self) -> bool:
        """True when flushes should run the retry/quarantine path: a
        server-wide ``retry_policy``, or any pending spec carrying its own
        ``retry``.  With neither, flush keeps the legacy single-attempt
        :class:`FlushError` contract."""
        if self.retry_policy is not None:
            return True
        return any(
            req.spec.retry is not None
            for queue in self._queues.values()
            for req in queue
        )

    def _policy_for(self, req: SelectionRequest) -> RetryPolicy:
        """The request's effective policy: its spec's, else the server's,
        else single-attempt (fail typed on first error, no retry)."""
        if req.spec.retry is not None:
            return req.spec.retry
        if self.retry_policy is not None:
            return self.retry_policy
        return SINGLE_ATTEMPT

    def _note_attempt(self, req: SelectionRequest, error) -> RequestFailed | None:
        """Charge one failed attempt against ``req``'s budget.  Returns the
        terminal :class:`RequestFailed` when the budget is exhausted —
        ``max_attempts`` (``"quarantined"``) or wall-clock ``timeout_s``
        (``"timeout"``) — else None (the request may retry)."""
        now = time.perf_counter()
        hist = self._attempts.setdefault(req.rid, [])
        hist.append(
            {
                "attempt": len(hist) + 1,
                "error": f"{type(error).__name__}: {error}",
                "elapsed_s": round(max(0.0, now - req.enqueue_t), 6),
            }
        )
        pol = self._policy_for(req)
        if pol.timeout_s is not None and now - req.enqueue_t >= pol.timeout_s:
            reason = "timeout"
        elif len(hist) >= pol.max_attempts:
            reason = "quarantined"
            self.metrics.inc("quarantined_total")
        else:
            return None
        self._attempts.pop(req.rid, None)
        return RequestFailed(req.rid, reason, hist, cause=error)

    def _isolate(self, req: SelectionRequest, failures: dict) -> Wave | None:
        """Rebuild ``req`` as a singleton wave for a retry.  Build (padder)
        errors are charged against its attempt budget like any other; on
        exhaustion the terminal failure lands in ``failures`` and None is
        returned."""
        while True:
            try:
                return waves_for_group(
                    [req],
                    max_wave=1,
                    n_multiple=self.n_multiple,
                    b_multiple=self.b_multiple,
                )[0]
            except Exception as e:
                self.metrics.inc("flush_errors")
                term = self._note_attempt(req, e)
                if term is not None:
                    failures[req.rid] = term
                    return None
                self.metrics.inc("retries_total")
                wait = self._policy_for(req).backoff(
                    len(self._attempts[req.rid]), seed=req.rid
                )
                if wait > 0:
                    time.sleep(wait)

    def dispatch_resilient(self, waves: Sequence[Wave]) -> tuple[dict, dict]:
        """Dispatch waves with per-request retry, poison isolation, and
        typed quarantine; returns ``(responses, failures)`` — every drained
        rid resolves into exactly one of the two dicts, and no exception
        escapes for a wave failure.

        On a wave failure each rider is charged one attempt: exhausted
        requests fail typed (:class:`RequestFailed` in ``failures``), the
        rest retry — a multi-request wave is rebuilt as singleton waves
        first, so the one poison request cannot re-poison its co-travellers
        (they succeed alone on the next attempt).  Backoff between attempts
        follows each request's policy with jitter seeded by its rid, so
        reruns back off identically.  Like :meth:`dispatch_waves` this
        touches no queues and is safe outside any queue lock.
        """
        responses: dict = {}
        failures: dict = {}
        pending: list[Wave] = list(waves)
        while pending:
            wave = pending.pop(0)
            try:
                out = self._dispatch(wave)
            except Exception as e:
                self.metrics.inc("flush_errors")
                retryable = []
                for req in wave.requests:
                    term = self._note_attempt(req, e)
                    if term is not None:
                        failures[req.rid] = term
                    else:
                        retryable.append(req)
                if not retryable:
                    continue
                self.metrics.inc("retries_total", len(retryable))
                if len(wave.requests) > 1:
                    # poison isolation: each survivor retries ALONE
                    rebuilt = []
                    for req in retryable:
                        w = self._isolate(req, failures)
                        if w is not None:
                            rebuilt.append(w)
                    pending[:0] = rebuilt
                else:
                    pending.insert(0, wave)  # already a singleton
                live = [r for r in retryable if r.rid in self._attempts]
                if live:
                    wait = max(
                        self._policy_for(r).backoff(
                            len(self._attempts[r.rid]), seed=r.rid
                        )
                        for r in live
                    )
                    if wait > 0:
                        time.sleep(wait)
                continue
            for req in wave.requests:
                prior = self._attempts.pop(req.rid, None)
                if prior:
                    out[req.rid].attempts = len(prior) + 1
            responses.update(out)
        return responses, failures

    def drain_resilient(
        self, keys: Optional[Sequence[tuple]] = None, *, take_undelivered: bool = True
    ) -> tuple[list[Wave], dict, dict, float]:
        """Like :meth:`drain`, but a wave-build (padder) error costs ONE
        group instead of aborting the whole drain, and requests whose
        wall-clock ``timeout_s`` already lapsed are reaped before any build.

        Returns ``(waves, undelivered, failures, retry_wait)``:
        ``failures`` maps reaped/exhausted rids to :class:`RequestFailed`;
        a group whose build failed keeps its retryable requests QUEUED and
        reports the backoff to wait before re-draining via ``retry_wait``
        (this method never sleeps — the async front end calls it under its
        lock).
        """
        if keys is None:
            keys = list(self._queues)
        waves: list[Wave] = []
        failures: dict = {}
        retry_wait = 0.0
        with self.metrics.span("serve.drain") as sp:
            for key in list(keys):
                requests = self._queues.get(key)
                if not requests:
                    self._queues.pop(key, None)
                    continue
                now = time.perf_counter()
                live = []
                for req in requests:
                    pol = self._policy_for(req)
                    if pol.timeout_s is not None and now - req.enqueue_t >= pol.timeout_s:
                        hist = self._attempts.pop(req.rid, [])
                        failures[req.rid] = RequestFailed(req.rid, "timeout", hist)
                    else:
                        live.append(req)
                if not live:
                    self._queues.pop(key, None)
                    continue
                try:
                    group_waves = waves_for_group(
                        live,
                        max_wave=self.max_wave,
                        n_multiple=self.n_multiple,
                        b_multiple=self.b_multiple,
                    )
                except Exception as e:
                    self.metrics.inc("flush_errors")
                    keep = []
                    for req in live:
                        term = self._note_attempt(req, e)
                        if term is not None:
                            failures[req.rid] = term
                        else:
                            keep.append(req)
                    if keep:
                        self.metrics.inc("retries_total", len(keep))
                        self._queues[key] = keep
                        retry_wait = max(
                            retry_wait,
                            max(
                                self._policy_for(r).backoff(
                                    len(self._attempts[r.rid]), seed=r.rid
                                )
                                for r in keep
                            ),
                        )
                    else:
                        self._queues.pop(key, None)
                    continue
                waves.extend(group_waves)
                self._queues.pop(key, None)
            sp.attrs["waves"] = len(waves)
        undelivered: dict = {}
        if take_undelivered:
            undelivered, self._undelivered = self._undelivered, {}
        return waves, undelivered, failures, retry_wait

    def take_failures(self) -> dict:
        """Hand over (and clear) the typed failures from resilient flushes:
        ``{rid: RequestFailed}``.  Each failure is delivered exactly once —
        callers own what they take."""
        out, self._failures = self._failures, {}
        return out

    def hold_failures(self, failures: dict) -> None:
        """Re-hold typed failures for a later :meth:`take_failures` — the
        async front end stashes failures for rids owned by the sync flush
        path here, mirroring :meth:`hold_undelivered`."""
        self._failures.update(failures)

    def _flush_resilient(self) -> dict:
        """The resilient flush body: rounds of drain + dispatch until every
        queue is empty.  Groups whose build failed retryably stay queued
        between rounds (backoff honored here, outside any lock); every
        drained rid ends as exactly one response (returned) or one
        :class:`RequestFailed` (held for :meth:`take_failures`)."""
        responses: dict = {}
        failures: dict = {}
        first = True
        while True:
            waves, undelivered, drain_failures, retry_wait = self.drain_resilient(
                take_undelivered=first
            )
            first = False
            responses.update(undelivered)
            failures.update(drain_failures)
            if waves:
                out, dispatch_failures = self.dispatch_resilient(waves)
                responses.update(out)
                failures.update(dispatch_failures)
            if not any(self._queues.values()):
                break
            if retry_wait > 0:
                time.sleep(retry_wait)
        if failures:
            self.hold_failures(failures)
        return responses

    def flush(self) -> dict:
        """Drain every group + dispatch; returns {rid: response}, including
        any responses computed by an earlier ``select`` call on behalf of
        requests it didn't own (nothing is ever dropped).

        On a mid-flush engine error, raises :class:`FlushError` AFTER
        restoring the server to a no-loss state: completed responses (this
        flush's and previously-held ones) are re-held for the next call,
        and every unserved request — the failed wave's and the
        never-dispatched ones — is re-enqueued at the front of its queue.
        ``e.failed_rids`` names the poisoned wave; ``cancel`` those before
        retrying if the requests themselves are at fault.

        When a :class:`~repro.launch.resilience.RetryPolicy` is in play
        (server-wide or on any pending spec) this switches to the resilient
        path instead: transient failures retry with backoff, the poison
        request is isolated, and exhausted requests resolve to typed
        failures via :meth:`take_failures` — :class:`FlushError` is never
        raised.
        """
        if self._resilience_active():
            return self._flush_resilient()
        waves, responses = self.drain()
        try:
            responses.update(self.dispatch_waves(waves))
        except FlushError as e:
            responses.update(e.completed)
            self.hold_undelivered(responses)
            # front-of-queue order: failed wave ahead of the undispatched
            # tail, matching original arrival order
            self.requeue(e.undispatched_requests)
            self.requeue(e.failed_requests)
            raise
        return responses

    def hold_undelivered(self, responses: dict) -> None:
        """Re-hold already-computed responses for delivery by a later
        ``flush()``.  Used by callers that drain ``flush()`` on behalf of a
        subset of requests (``select``, the async front end) so responses to
        everyone else's requests are never dropped."""
        self._undelivered.update(responses)

    def select(self, requests: Sequence) -> list[SelectionResponse]:
        """Convenience: submit specs — or (fn, budget) pairs, which become
        ``SelectionSpec(fn, budget)`` with family defaults — flush, and
        return responses in request order.  Responses to requests enqueued
        earlier via ``submit`` ride the same flush and are held for the next
        ``flush`` call."""
        with self.metrics.span("serve.select"):
            specs = [
                r if isinstance(r, SelectionSpec) else SelectionSpec(r[0], r[1])
                for r in requests
            ]
            rids = [self.submit_spec(s) for s in specs]
            out = self.flush()
            mine = [out.pop(r) for r in rids]
            self.hold_undelivered(out)
            return mine


# ---------------------------------------------------------------------------
# CLI: serve a random mixed workload and report throughput.
# ---------------------------------------------------------------------------

# dispersion families: the empty-set gain is 0.  SelectionSpec's per-family
# default table already sets stopIfZeroGain=False for them; the CLI
# additionally disables stopIfNegativeGain so long-budget requests keep
# selecting past the point where adding an element shrinks the dispersion
# objective
DISPERSION_FAMILIES = frozenset({"dsum", "dmin"})


def _random_function(kind: str, n: int, rng):
    """One random instance of a served family (shared by tests/benchmarks)."""
    from repro.core import (
        GCMI,
        FLQMI,
        FacilityLocation,
        FeatureBased,
        GraphCut,
        LogDet,
        ProbabilisticSetCover,
        SetCover,
        create_kernel,
    )

    def kernel():
        x = rng.normal(size=(n, 8)).astype(np.float32)
        return np.asarray(create_kernel(x, metric="euclidean"))

    if kind == "fl":
        return FacilityLocation.from_kernel(kernel())
    if kind == "gc":
        return GraphCut.from_kernel(kernel(), lam=0.3)
    if kind == "fb":
        feats = rng.uniform(0, 1, size=(n, 12)).astype(np.float32)
        return FeatureBased.from_features(feats, concave="sqrt")
    if kind == "sc":
        cover = rng.integers(0, 2, size=(n, 16)).astype(np.float32)
        return SetCover.from_cover(cover)
    if kind == "psc":
        probs = rng.uniform(0, 0.9, size=(n, 16)).astype(np.float32)
        return ProbabilisticSetCover.from_probs(probs)
    if kind == "dsum":
        from repro.core import DisparitySum

        return DisparitySum.from_distance(1.0 - kernel())
    if kind == "dmin":
        from repro.core import DisparityMin

        return DisparityMin.from_distance(1.0 - kernel())
    if kind == "flqmi":
        x = rng.normal(size=(n, 8)).astype(np.float32)
        q = rng.normal(size=(6, 8)).astype(np.float32)
        from repro.core import create_kernel as ck

        return FLQMI.build(np.asarray(ck(q, x, metric="euclidean")))
    if kind == "gcmi":
        x = rng.normal(size=(n, 8)).astype(np.float32)
        q = rng.normal(size=(5, 8)).astype(np.float32)
        from repro.core import create_kernel as ck

        return GCMI.build(np.asarray(ck(x, q, metric="euclidean")), lam=0.4)
    if kind == "logdet":
        S = kernel() + 0.5 * np.eye(n, dtype=np.float32)
        return LogDet.from_kernel(S, max_select=16)
    raise KeyError(kind)


def _random_requests(
    n_requests: int, seed: int = 0, families: Sequence[str] = ("fl", "gc", "fb")
):
    """A mixed workload with varying n, cycling through ``families`` (any of
    fl / gc / fb / sc / psc / dsum / dmin / flqmi / gcmi / logdet — every
    family here has a padder AND a ShardRule, so the workload serves on and
    off mesh; dsum/dmin requests get stopIfZeroGain=False by default at
    submit time, see :meth:`SelectionServer.submit`)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        n = int(rng.choice([24, 32, 48, 64]))
        budget = int(rng.integers(3, 9))
        fn = _random_function(families[i % len(families)], n, rng)
        reqs.append((fn, budget))
    return reqs


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument(
        "--mesh",
        default=None,
        help="BATCHxDATA device grid, e.g. 2x2 (requires "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU); "
        "default: single-device serving",
    )
    ap.add_argument("--max-wave", type=int, default=64)
    ap.add_argument(
        "--max-queue",
        type=int,
        default=None,
        help="backpressure cap on pending requests (default: unbounded)",
    )
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--families",
        default="fl,gc,fb",
        help="comma-separated families to mix into the workload "
        "(fl,gc,fb,sc,psc,flqmi,gcmi,logdet)",
    )
    ap.add_argument(
        "--metrics",
        action="store_true",
        help="print the full structured metrics snapshot (JSON) at the end",
    )
    a = ap.parse_args()

    import jax

    from repro.common import enable_compile_cache

    enable_compile_cache()
    mesh = None
    if a.mesh:
        b, d = (int(v) for v in a.mesh.lower().split("x"))
        mesh = jax.make_mesh((b, d), ("batch", "data"))

    server = SelectionServer(mesh=mesh, max_wave=a.max_wave, max_queue=a.max_queue)
    families = tuple(a.families.split(","))
    requests = _random_requests(a.requests, seed=a.seed, families=families)
    # same family indexing as _random_requests: dispersion requests ride with
    # stopping disabled, otherwise their selections are silently empty
    kinds = [families[i % len(families)] for i in range(len(requests))]

    for rnd in range(a.rounds):
        t0 = time.perf_counter()
        rids = [
            server.submit(
                SelectionSpec(
                    fn,
                    budget,
                    # the family table already defaults stopIfZeroGain=False
                    # for dispersion; the CLI additionally disables the
                    # negative-gain stop so long-budget dispersion requests
                    # keep selecting
                    stopIfNegativeGain=kind not in DISPERSION_FAMILIES,
                )
            )
            for (fn, budget), kind in zip(requests, kinds)
        ]
        out = server.flush()
        responses = [out[r] for r in rids]
        dt = time.perf_counter() - t0
        assert len(responses) == len(requests)
        assert all(r.selection for r in responses), "empty selection served"
        label = "warmup (compiles)" if rnd == 0 else "steady"
        print(
            f"round {rnd}: {len(requests)} requests in {dt:.3f}s "
            f"({len(requests) / dt:.1f} q/s)  [{label}]"
        )

    s = server.stats.summary()
    print(f"\nserver stats: {s}")
    r0 = responses[0]
    print(
        f"sample response: rid={r0.rid} wave={r0.wave_size} "
        f"n_bucket={r0.n_bucket} backend={r0.backend} "
        f"queue={r0.queue_s * 1e3:.2f}ms wave={r0.wave_s * 1e3:.2f}ms "
        f"latency={r0.latency_s * 1e3:.2f}ms "
        f"selection={[i for i, _ in r0.selection]}"
    )
    if a.metrics:
        print(json.dumps(server.stats.snapshot(), indent=2))


if __name__ == "__main__":
    main()

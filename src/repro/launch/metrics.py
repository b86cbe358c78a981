"""Structured serving metrics: bounded counters, histograms and spans.

The serving front door (`launch/serve.py` / `launch/async_serve.py`) used to
keep an unbounded ``wave_seconds`` list — linear memory in flush count on a
long-lived server.  This module is the replacement: every aggregate is
**bounded** (count / sum / min / max plus a fixed-size uniform reservoir for
percentiles), and the whole tree snapshots to one JSON-able dict consumed by
``benchmarks/serve_bench.py --json`` and the serve CLI.

Schema (``ServerMetrics.snapshot()``)::

    {
      "counters": {
        "requests": int,        # real requests served
        "waves": int,           # engine dispatches
        "slots": int,           # engine slots incl. batch pads
        "padded_slots": int,    # batch-pad slots (pad waste)
        "bytes_stacked": int,   # nbytes of the waves' padded functions
        "bytes_live": int,      # nbytes of the real requests' own functions
        "cols_stacked": int,    # candidate columns the waves sweep (B x n-bucket)
        "cols_live": int,       # the real requests' own candidates (sum of n)
        "steps_run": int,       # greedy steps the waves' loops ran
        "steps_bucket": int,    # the waves' budget buckets (output widths)
        "rejections": int,      # submits refused by backpressure
        "flush_errors": int,    # dispatch errors (FlushError raised)
        "requeued": int,        # requests re-enqueued after a flush error
        "deadline_misses": int, # responses delivered past their deadline_s
        "sessions_opened": int, # SelectionSessions opened on this server
        "sessions_closed": int, # SelectionSessions closed
        "session_deltas": int,  # extend() deltas absorbed across sessions
        "session_churn": int,   # total selection churn across all deltas
        "retries_total": int,   # wave re-dispatch attempts scheduled
        "fallbacks_total": int, # waves served degraded (breaker open)
        "quarantined_total": int, # requests failed typed after N attempts
      },
      "queue_s":  {count, sum, max, p50, p99},   # the serve.queue spans
      "wave_s":   {count, sum, max, p50, p99},   # the serve.wave spans
      "queue_depth": {count, sum, max, p50, p99},# depth sampled at enqueue
      "delta_s":  {count, sum, max, p50, p99},   # session extend -> update
      "breakers": {"<label>": "closed|open|half_open", ...},
      "groups": {                                 # per-(family, n-bucket,
        "<label>": {                              #  optimizer) queue
          "requests": int, "waves": int,
          "queue_s": {...}, "wave_s": {...},
        }, ...
      },
    }

Group labels are ``Family/n<bucket>/<Optimizer>`` — the same (family,
n-bucket) keys the coalescer groups waves by, promoted to queue identity.

Spans: :meth:`ServerMetrics.span` times one layer boundary of the served
path (``serve.select`` / ``serve.submit`` / ``serve.drain`` /
``serve.wave`` / ``engine.build`` / ``engine.launch`` / ``engine.fetch`` /
``serve.demux``, plus ``serve.queue`` per request).  Each finished span —
name, span id, parent span id, wave id, start and end in
``time.perf_counter_ns()`` nanoseconds, attributes — lands in one bounded,
process-wide ring (:data:`SPAN_CAPACITY` records, oldest dropped) that
:func:`spans` reads.  Every span but ``serve.queue`` also enters a
``jax.profiler.TraceAnnotation``, so a profiled run shows it on the host
plane, on the device trace's clock.  Spans are always on.  Request arrival
stamps (``SelectionRequest.enqueue_ns``) use the same clock.

Thread-safety: increments and histogram records are guarded by one internal
lock, the span ring by its own, so the flush thread, submitters, and a
metrics scraper can interleave freely; ``snapshot()`` and :func:`spans`
return detached copies.  Parent spans are tracked per thread.
"""
from __future__ import annotations

import collections
import itertools
import math
import random
import threading
import time
import zlib
from typing import NamedTuple

import jax


def _seed_for(name: str) -> int:
    """Per-histogram reservoir seed.  Seeding every reservoir identically
    would correlate their eviction patterns (all reservoirs replace the same
    slots on the same ticks for equal-length streams); hashing the metric
    name decorrelates them while staying reproducible across runs."""
    return zlib.crc32(name.encode("utf-8"))

__all__ = ["Reservoir", "Histogram", "ServerMetrics", "Span", "SPAN_CAPACITY", "spans"]


class Reservoir:
    """Fixed-size uniform sample of a stream (Vitter's algorithm R).

    Memory is O(capacity) no matter how many values are recorded; the
    percentile estimates converge on the stream's true quantiles.  The RNG
    is seeded per instance, so a server's metrics are reproducible for a
    deterministic workload.
    """

    def __init__(self, capacity: int = 512, seed: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._rng = random.Random(seed)
        self._sample: list[float] = []
        self._seen = 0

    def add(self, value: float) -> None:
        self._seen += 1
        if len(self._sample) < self.capacity:
            self._sample.append(float(value))
            return
        j = self._rng.randrange(self._seen)
        if j < self.capacity:
            self._sample[j] = float(value)

    @property
    def seen(self) -> int:
        return self._seen

    def percentile(self, q: float) -> float:
        """Empirical q-quantile (q in [0, 1]) of the retained sample; NaN
        when nothing was recorded."""
        if not self._sample:
            return float("nan")
        s = sorted(self._sample)
        idx = min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))
        return s[idx]


class Histogram:
    """Bounded aggregation of a stream: count / sum / min / max exactly,
    percentiles from a fixed-size :class:`Reservoir`."""

    def __init__(self, reservoir_size: int = 512, seed: int = 0):
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = 0.0
        self._reservoir = Reservoir(reservoir_size, seed=seed)

    def record(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        self.min = min(self.min, value)
        self.max = max(self.max, value)
        self._reservoir.add(value)

    def percentile(self, q: float) -> float:
        return self._reservoir.percentile(q)

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else float("nan")

    def snapshot(self, ndigits: int = 6) -> dict:
        if not self.count:
            return {"count": 0, "sum": 0.0, "max": 0.0, "p50": 0.0, "p99": 0.0}
        return {
            "count": self.count,
            "sum": round(self.total, ndigits),
            "max": round(self.max, ndigits),
            "p50": round(self.percentile(0.50), ndigits),
            "p99": round(self.percentile(0.99), ndigits),
        }


_COUNTERS = (
    "requests",
    "waves",
    "slots",
    "padded_slots",
    "bytes_stacked",
    "bytes_live",
    "cols_stacked",
    "cols_live",
    "steps_run",
    "steps_bucket",
    "rejections",
    "flush_errors",
    "requeued",
    "deadline_misses",
    "sessions_opened",
    "sessions_closed",
    "session_deltas",
    "session_churn",
    "retries_total",
    "fallbacks_total",
    "quarantined_total",
)


class _GroupMetrics:
    """Per-(family, n-bucket, optimizer) queue accounting."""

    __slots__ = ("requests", "waves", "queue_s", "wave_s")

    def __init__(self, reservoir_size: int, label: str = ""):
        self.requests = 0
        self.waves = 0
        self.queue_s = Histogram(reservoir_size, seed=_seed_for(f"{label}/queue_s"))
        self.wave_s = Histogram(reservoir_size, seed=_seed_for(f"{label}/wave_s"))


# -- spans -----------------------------------------------------------------

SPAN_CAPACITY = 65_536  # finished spans the process-wide ring keeps


class Span(NamedTuple):
    """One finished span.  Times are ``time.perf_counter_ns()``; a root span
    has ``parent_id`` 0, a span outside any wave ``wave_id`` 0."""

    name: str
    span_id: int
    parent_id: int
    wave_id: int
    start_ns: int
    end_ns: int
    attrs: dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class SpanRing:
    """The newest ``capacity`` finished spans, oldest dropped first."""

    _GUARDED_BY = {"_records": "_lock"}

    def __init__(self, capacity: int = SPAN_CAPACITY):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(maxlen=capacity)

    def add(self, span: Span) -> None:
        with self._lock:
            self._records.append(span)

    def snapshot(self) -> list[Span]:
        with self._lock:
            return list(self._records)


_RING = SpanRing()
_SPAN_IDS = itertools.count(1)
_WAVE_IDS = itertools.count(1)


class _OpenSpans(threading.local):
    def __init__(self):
        self.stack: list = []  # this thread's open spans, innermost last


_OPEN = _OpenSpans()


def spans() -> list[Span]:
    """Every span the process-wide ring still holds, oldest first."""
    return _RING.snapshot()


class ActiveSpan:
    """An open span (the context manager :meth:`ServerMetrics.span`
    returns).  Its parent is the thread's innermost open span, whose wave id
    it inherits unless it opens a wave of its own; ``attrs`` may grow until
    it closes, and ``seconds`` reads its duration once closed."""

    __slots__ = (
        "name", "span_id", "parent_id", "wave_id", "attrs",
        "start_ns", "end_ns", "_annotation",
    )

    def __init__(self, name: str, wave_id: int, attrs: dict):
        self.name = name
        self.span_id = next(_SPAN_IDS)
        self.parent_id = 0
        self.wave_id = wave_id
        self.attrs = attrs
        self.start_ns = self.end_ns = 0
        self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "ActiveSpan":
        stack = _OPEN.stack
        if stack:
            self.parent_id = stack[-1].span_id
            self.wave_id = self.wave_id or stack[-1].wave_id
        stack.append(self)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        _OPEN.stack.pop()
        if exc_type is not None:
            self.attrs["error"] = exc_type.__name__
        _RING.add(
            Span(self.name, self.span_id, self.parent_id, self.wave_id,
                 self.start_ns, self.end_ns, self.attrs)
        )
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class ServerMetrics:
    """The serving stack's metric tree (see module docstring for schema)."""

    _GUARDED_BY = {
        "counters": "_lock",
        "queue_s": "_lock",
        "wave_s": "_lock",
        "queue_depth": "_lock",
        "delta_s": "_lock",
        "groups": "_lock",
        "breaker_states": "_lock",
    }

    def __init__(self, reservoir_size: int = 512):
        self._reservoir_size = int(reservoir_size)
        self._lock = threading.Lock()
        self.counters: dict[str, int] = {name: 0 for name in _COUNTERS}
        self.queue_s = Histogram(reservoir_size, seed=_seed_for("queue_s"))
        self.wave_s = Histogram(reservoir_size, seed=_seed_for("wave_s"))
        self.queue_depth = Histogram(reservoir_size, seed=_seed_for("queue_depth"))
        self.delta_s = Histogram(reservoir_size, seed=_seed_for("delta_s"))
        self.groups: dict[str, _GroupMetrics] = {}
        self.breaker_states: dict[str, str] = {}

    # -- recording -----------------------------------------------------------

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + by

    def _group_locked(self, label: str) -> _GroupMetrics:
        g = self.groups.get(label)
        if g is None:
            g = self.groups[label] = _GroupMetrics(self._reservoir_size, label)
        return g

    def set_breaker(self, label: str, state: str) -> None:
        """Record a circuit breaker's current state (the server binds this
        to its :class:`~repro.launch.resilience.BreakerBoard`)."""
        with self._lock:
            self.breaker_states[label] = str(state)

    def observe_enqueue(self, label: str, depth: int) -> None:
        """One request admitted to ``label``'s queue, which now holds
        ``depth`` requests."""
        with self._lock:
            self.queue_depth.record(depth)
            self._group_locked(label)  # the group exists from first admission

    def observe_wave(
        self,
        label: str,
        wave_s: float,
        *,
        requests: int,
        slots: int,
        padded_slots: int,
        bytes_stacked: int = 0,
        bytes_live: int = 0,
        cols_stacked: int = 0,
        cols_live: int = 0,
        steps_run: int = 0,
        steps_bucket: int = 0,
    ) -> None:
        """One engine dispatch for ``label``'s group, ``wave_s`` long."""
        with self._lock:
            self.counters["waves"] += 1
            self.counters["requests"] += requests
            self.counters["slots"] += slots
            self.counters["padded_slots"] += padded_slots
            self.counters["bytes_stacked"] += bytes_stacked
            self.counters["bytes_live"] += bytes_live
            self.counters["cols_stacked"] += cols_stacked
            self.counters["cols_live"] += cols_live
            self.counters["steps_run"] += steps_run
            self.counters["steps_bucket"] += steps_bucket
            self.wave_s.record(wave_s)
            g = self._group_locked(label)
            g.waves += 1
            g.requests += requests
            g.wave_s.record(wave_s)

    def observe_served(
        self, label: str, queue_s: float, *, deadline_missed: bool = False
    ) -> None:
        """One request answered: it waited ``queue_s`` (its ``serve.queue``
        span) before its wave's dispatch began."""
        with self._lock:
            self.queue_s.record(queue_s)
            self._group_locked(label).queue_s.record(queue_s)
            if deadline_missed:
                self.counters["deadline_misses"] += 1

    def observe_delta(self, delta_s: float, *, churn: int = 0) -> None:
        """One session ``extend()`` absorbed: it took ``delta_s`` seconds
        submit-to-update and replaced ``churn`` members of the previous
        selection (symmetric difference of the id sets)."""
        with self._lock:
            self.counters["session_deltas"] += 1
            self.counters["session_churn"] += int(churn)
            self.delta_s.record(delta_s)

    # -- spans ---------------------------------------------------------------

    def span(self, name: str, *, wave: bool = False, **attrs) -> ActiveSpan:
        """A context manager that records one span into the process-wide
        ring (see the module docstring); ``wave=True`` gives it, and the
        spans opened inside it, a new wave id."""
        return ActiveSpan(name, next(_WAVE_IDS) if wave else 0, attrs)

    def record_span(
        self, name: str, start_ns: int, end_ns: int, *, parent: ActiveSpan, **attrs
    ) -> Span:
        """Record a span whose start was stamped earlier (a request's queue
        dwell) straight into the ring, as a child of ``parent`` in its wave;
        no profiler annotation."""
        span = Span(name, next(_SPAN_IDS), parent.span_id, parent.wave_id,
                    start_ns, end_ns, attrs)
        _RING.add(span)
        return span

    # -- reading -------------------------------------------------------------

    def snapshot(self) -> dict:
        """Detached JSON-able copy of every counter and histogram."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "queue_s": self.queue_s.snapshot(),
                "wave_s": self.wave_s.snapshot(),
                "queue_depth": self.queue_depth.snapshot(ndigits=1),
                "delta_s": self.delta_s.snapshot(),
                "breakers": dict(sorted(self.breaker_states.items())),
                "groups": {
                    label: {
                        "requests": g.requests,
                        "waves": g.waves,
                        "queue_s": g.queue_s.snapshot(),
                        "wave_s": g.wave_s.snapshot(),
                    }
                    for label, g in sorted(self.groups.items())
                },
            }

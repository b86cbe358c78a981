"""Long-lived selection sessions over the serving front doors.

A :class:`SelectionSession` is the serving shape for *growing* data: a
client opens a session around a :class:`~repro.core.optimizers.spec.
SelectionSpec`, then feeds **deltas** — new ground-set rows, or newly
unlocked indices of a fixed universe — and receives an updated selection
after every delta:

    session = server.open_session(SelectionSpec(fn0, budget=8))
    upd = session.extend(features=new_rows)   # sync: SessionUpdate
    fut = session.extend(features=more_rows)  # async server: Future
    session.close()

Replay semantics (the determinism contract): each ``extend`` rebuilds the
session's function over the FULL stream seen so far and submits one fresh
spec through the server's normal per-group queues — deltas coalesce with
everyone else's requests, ride padded waves, and obey backpressure and
deadlines exactly like one-shot requests.  Because the k-th update *is*
``solve()`` over the concatenated stream, a session fed N deltas returns a
final selection bit-identical (ids, gains, n_evals) to one direct
``solve()`` over the same data, on or off a mesh — there is no incremental
state to drift.

Two delta modes, fixed by the first ``extend``:

- **features mode** (``extend(features=rows)``): the spec's function seeds
  the stream and a registered *extender* appends rows.  Extenders MUST be
  concatenation-associative bit-for-bit — every built-in preprocesses rows
  independently (row-wise clamp / normalize / log1p), so one big extend
  equals many small ones exactly.
- **indices mode** (``extend(indices=ids)``): the spec's function is the
  fixed universe and a registered *restrictor* exposes the active subset.
  Restrictors preserve values — the restricted function agrees with the
  universe function on every subset of the active set — and updates report
  UNIVERSE ids, not positions in the active list.

Families opt in through :func:`register_feature_extender` /
:func:`register_restrictor` (MRO-resolved, like the coalescer's padders,
so the info-measure subclasses of SetCover/PSC inherit coverage for free).

Session metrics ride the server's :class:`~repro.launch.metrics.
ServerMetrics`: counters ``sessions_opened`` / ``sessions_closed`` /
``session_deltas`` / ``session_churn`` plus the ``delta_s`` histogram
(submit -> update latency per delta).  Each session also keeps its own
``deltas_absorbed`` / ``churn_total`` / ``last_update``.

Async edge discipline: ``extend`` on an :class:`~repro.launch.async_serve.
AsyncSelectionServer` returns a Future chained onto the server's — a
``close(flush=False)`` on the server cancels the in-flight delta's future,
engine errors propagate as exceptional futures, and a full queue raises
:class:`~repro.launch.serve.ServerOverloaded` synchronously at ``extend``
time (backpressure applies to deltas like any submit).

Crash safety: open a session with a :class:`SessionJournal` and every
COMMITTED delta's raw input (the float32 rows / the index array, exactly as
given) is appended to an atomic on-disk journal (one checkpoint step per
delta, riding ``repro/ckpt/checkpoint.py``'s tmp + os.replace discipline).
After a crash, :func:`restore_sessions` replays each journaled stream
through a fresh server's REAL ``extend`` path — re-preprocessing the raw
inputs identically — so the restored sessions' state (stream, active set,
selection, churn accounting) is bit-identical to the lost server's.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import time
from concurrent.futures import Future

import jax.numpy as jnp
import numpy as np

from repro.core.functions.facility_location import (
    FacilityLocation,
    FacilityLocationMF,
)
from repro.core.functions.feature_based import FeatureBased
from repro.core.functions.graph_cut import GraphCut
from repro.core.functions.set_cover import ProbabilisticSetCover, SetCover
from repro.core.optimizers.spec import SelectionSpec
from repro.core.sources import DenseSource, FeatureSource
from repro.launch import faults
from repro.launch.async_serve import AsyncSelectionServer

__all__ = [
    "SelectionSession",
    "SessionClosed",
    "SessionJournal",
    "SessionUpdate",
    "register_feature_extender",
    "register_restrictor",
    "resolve_extender",
    "resolve_restrictor",
    "restore_sessions",
]


class SessionClosed(RuntimeError):
    """``extend`` was called on a closed :class:`SelectionSession`."""


@dataclasses.dataclass
class SessionUpdate:
    """One absorbed delta: the refreshed selection plus its accounting.

    ``selection`` ids are always in the session's UNIVERSE space — stream
    positions for features mode, the caller's own indices for indices mode
    — so consecutive updates are directly comparable (``churn`` is the
    symmetric difference of consecutive id sets).
    """

    seq: int  # 1-based delta sequence number within the session
    selection: list  # [(universe_id, gain), ...] in pick order
    result: object  # the GreedyResult (== sequential solve over the stream)
    response: object  # the underlying SelectionResponse (wave accounting)
    n_total: int  # ground-set size after this delta
    n_delta: int  # elements this delta added
    churn: int  # |previous ids  ^  current ids|
    latency_s: float  # extend() -> update built (queue + wave + chaining)


# ---------------------------------------------------------------------------
# Family registries (MRO-resolved, like launch/coalesce.py's padders)
# ---------------------------------------------------------------------------

_EXTENDERS: dict[type, object] = {}
_RESTRICTORS: dict[type, object] = {}


def register_feature_extender(family: type):
    """Register ``extender(fn, rows) -> fn'`` for a function family.

    The extender appends ``rows`` (the family's natural raw input — feature
    rows, cover rows, probability rows) to ``fn``'s ground set.  It must be
    concatenation-associative bit-for-bit: preprocessing may only look at
    one row at a time, so feeding rows one-by-one builds the exact array
    one big concatenate would.
    """

    def deco(fn):
        _EXTENDERS[family] = fn
        return fn

    return deco


def register_restrictor(family: type):
    """Register ``restrictor(fn, active) -> fn'`` for a function family.

    ``active`` is an int32 array of universe ids; the restricted function
    must agree with ``fn`` on every subset of ``active`` (restrict the
    CANDIDATE axis only — the represented side stays the full universe)."""

    def deco(fn):
        _RESTRICTORS[family] = fn
        return fn

    return deco


def _resolve(registry: dict, cls: type, register_name: str):
    for base in cls.__mro__:
        hook = registry.get(base)
        if hook is not None:
            return hook
    raise NotImplementedError(
        f"{cls.__name__} has no session support for this delta mode; "
        f"register a hook with repro.launch.sessions.{register_name} "
        f"(supported: {sorted(c.__name__ for c in registry)})"
    )


def resolve_extender(cls: type):
    return _resolve(_EXTENDERS, cls, "register_feature_extender")


def resolve_restrictor(cls: type):
    return _resolve(_RESTRICTORS, cls, "register_restrictor")


# -- built-in extenders ------------------------------------------------------


@register_feature_extender(FeatureBased)
def _extend_feature_based(fn: FeatureBased, rows) -> FeatureBased:
    # same row-wise clamp as from_features, so session-grown == direct-built
    rows = jnp.maximum(jnp.asarray(rows, jnp.float32), 0.0)
    feats = jnp.concatenate([fn.feats, rows], axis=0)
    return dataclasses.replace(fn, feats=feats, n=int(feats.shape[0]))


@register_feature_extender(SetCover)
def _extend_set_cover(fn: SetCover, rows) -> SetCover:
    cover = jnp.concatenate([fn.cover, jnp.asarray(rows, jnp.float32)], axis=0)
    return dataclasses.replace(fn, cover=cover, n=int(cover.shape[0]))


@register_feature_extender(ProbabilisticSetCover)
def _extend_psc(fn: ProbabilisticSetCover, rows) -> ProbabilisticSetCover:
    # rows are raw coverage PROBABILITIES — the same clip + log1p as
    # from_probs, applied per row
    probs = jnp.clip(jnp.asarray(rows, jnp.float32), 0.0, 1.0 - 1e-7)
    log_miss = jnp.concatenate([fn.log_miss, jnp.log1p(-probs)], axis=0)
    return dataclasses.replace(fn, log_miss=log_miss, n=int(log_miss.shape[0]))


def _is_symmetric(src: FeatureSource) -> bool:
    """Self-represented source (feature_source(x, y=None))?  Identity is the
    fast path; after transformations fall back to an exact compare."""
    if src.x is src.y:
        return True
    return (
        src.n_rows == src.n_cols
        and src.x.shape == src.y.shape
        and bool(jnp.all(src.x == src.y))
    )


@register_feature_extender(FacilityLocationMF)
def _extend_fl_mf(fn: FacilityLocationMF, rows) -> FacilityLocationMF:
    src = fn.src
    if not isinstance(src, FeatureSource):
        raise NotImplementedError(
            "session extension of FacilityLocationMF needs a FeatureSource "
            f"(raw rows can be appended); got {type(src).__name__}"
        )
    if src.row_labels is not None or src.col_labels is not None:
        raise NotImplementedError(
            "clustered (label-masked) sources cannot be extended in a session"
        )
    # exactly feature_source's row-wise preprocessing (normalize for cosine,
    # then squared norms) — concat-associative by construction
    d32 = jnp.asarray(rows, jnp.float32)
    if src.metric == "cosine":
        d32 = d32 / jnp.maximum(jnp.linalg.norm(d32, axis=1, keepdims=True), 1e-12)
    dd = (d32 * d32).sum(axis=1)
    if _is_symmetric(src):
        new_src = src.append_points(d32, dd)
    else:  # fixed represented rows, growing candidate columns
        new_src = src.append_cols(d32, dd)
    return dataclasses.replace(fn, src=new_src, n=new_src.n_cols)


# -- built-in restrictors (candidate axis only: values are preserved) --------


@register_restrictor(FacilityLocation)
def _restrict_fl(fn: FacilityLocation, active) -> FacilityLocation:
    return dataclasses.replace(
        fn, sim=jnp.take(fn.sim, active, axis=1), n=int(active.shape[0])
    )


@register_restrictor(FacilityLocationMF)
def _restrict_fl_mf(fn: FacilityLocationMF, active) -> FacilityLocationMF:
    src = fn.src
    if isinstance(src, DenseSource):
        sub = dataclasses.replace(
            src, sim=jnp.take(src.sim, active, axis=1),
            n_cols=int(active.shape[0]),
        )
    elif isinstance(src, FeatureSource):
        sub = src.take_cols(jnp.asarray(active))
    else:
        raise NotImplementedError(
            "session restriction of FacilityLocationMF needs a FeatureSource "
            f"or DenseSource; got {type(src).__name__}"
        )
    return dataclasses.replace(fn, src=sub, n=int(active.shape[0]))


@register_restrictor(GraphCut)
def _restrict_gc(fn: GraphCut, active) -> GraphCut:
    # representation term stays over the full universe (total gathered),
    # the S x S penalty only ever reads active x active
    sub = jnp.take(jnp.take(fn.sim_ground, active, axis=0), active, axis=1)
    return dataclasses.replace(
        fn,
        sim_ground=sub,
        total=jnp.take(fn.total, active),
        n=int(active.shape[0]),
    )


@register_restrictor(FeatureBased)
def _restrict_fb(fn: FeatureBased, active) -> FeatureBased:
    return dataclasses.replace(
        fn, feats=jnp.take(fn.feats, active, axis=0), n=int(active.shape[0])
    )


@register_restrictor(SetCover)
def _restrict_sc(fn: SetCover, active) -> SetCover:
    return dataclasses.replace(
        fn, cover=jnp.take(fn.cover, active, axis=0), n=int(active.shape[0])
    )


@register_restrictor(ProbabilisticSetCover)
def _restrict_psc(fn: ProbabilisticSetCover, active) -> ProbabilisticSetCover:
    return dataclasses.replace(
        fn, log_miss=jnp.take(fn.log_miss, active, axis=0), n=int(active.shape[0])
    )


# ---------------------------------------------------------------------------
# Crash-safe journaling
# ---------------------------------------------------------------------------


class SessionJournal:
    """Append-only on-disk journal of session deltas.

    Layout: ``root/<sid>/step_<seq>/`` — one checkpoint step per committed
    delta, written through :mod:`repro.ckpt.checkpoint`'s atomic
    tmp + ``os.replace`` discipline, so a crash mid-append never corrupts
    an already-journaled delta.  What is journaled is the delta's RAW input
    (the float32 feature rows, or the index array exactly as the client
    gave it), NOT the preprocessed function state: replay re-runs the real
    ``extend`` path, so restored state is bit-identical by construction
    rather than by trusting a serialized snapshot.
    """

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)

    def append(self, sid: str, seq: int, mode: str, payload) -> None:
        """Journal one committed delta (``seq`` is the session's 1-based
        delta ordinal)."""
        from repro.ckpt import checkpoint

        checkpoint.save(
            os.path.join(self.root, sid),
            seq,
            {"payload": np.asarray(payload)},
            meta={"sid": sid, "seq": int(seq), "mode": mode},
            keep_last=10**9,  # a journal never prunes
        )

    def sessions(self) -> list[str]:
        """Session ids with at least one journaled delta, sorted."""
        try:
            names = os.listdir(self.root)
        except FileNotFoundError:
            return []
        out = []
        for name in sorted(names):
            sid_dir = os.path.join(self.root, name)
            if os.path.isdir(sid_dir) and any(
                d.startswith("step_") and not d.endswith(".tmp")
                for d in os.listdir(sid_dir)
            ):
                out.append(name)
        return out

    def deltas(self, sid: str) -> list[dict]:
        """The session's journaled deltas in commit order:
        ``[{"seq", "mode", "payload"}, ...]``."""
        from repro.ckpt import checkpoint

        sid_dir = os.path.join(self.root, sid)
        if not os.path.isdir(sid_dir):
            return []
        seqs = sorted(
            int(d.split("_")[1])
            for d in os.listdir(sid_dir)
            if d.startswith("step_") and not d.endswith(".tmp")
        )
        out = []
        for seq in seqs:
            tree, meta = checkpoint.restore(sid_dir, {"payload": 0}, step=seq)
            out.append(
                {
                    "seq": int(meta["seq"]),
                    "mode": meta["mode"],
                    "payload": np.asarray(tree["payload"]),
                }
            )
        return out


def restore_sessions(server, journal: SessionJournal, specs: dict) -> dict:
    """Rebuild every journaled session on a fresh ``server`` by replaying
    each stream through the REAL ``extend`` path.

    ``specs`` maps sid -> the base :class:`SelectionSpec` the session was
    opened around (specs hold live function objects, so the journal cannot
    reconstruct them itself; persist them with ``spec.to_dict()`` /
    ``from_dict`` or rebuild from your own config).  Returns
    ``{sid: SelectionSession}`` — each replayed to the exact state the lost
    server held: same stream, same selection (ids / gains / n_evals), same
    ``seq``.  Replayed deltas are NOT re-journaled (the journal already has
    them) and do not consult fault plans — recovery itself is not a fault
    boundary.
    """
    restored: dict = {}
    for sid in journal.sessions():
        if sid not in specs:
            raise KeyError(
                f"journal has session {sid!r} but specs= does not; pass its "
                f"base SelectionSpec to replay it"
            )
        session = SelectionSession(server, specs[sid], sid=sid, journal=journal)
        session._replaying = True
        try:
            with faults.suspended():  # recovery is not a fault boundary
                for delta in journal.deltas(sid):
                    if delta["seq"] != session._seq + 1:
                        raise RuntimeError(
                            f"journal for session {sid!r} is not contiguous: "
                            f"expected seq {session._seq + 1}, got {delta['seq']}"
                        )
                    kw = (
                        {"features": delta["payload"]}
                        if delta["mode"] == "features"
                        else {"indices": delta["payload"]}
                    )
                    upd = session.extend(**kw)
                    if isinstance(upd, Future):  # async: force the wave now
                        server.flush_now()
                        upd.result()
        finally:
            session._replaying = False
        restored[sid] = session
    return restored


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------


class SelectionSession:
    """Per-client state across waves: the stream so far, the id mapping,
    and churn accounting.  Build one with ``server.open_session(spec)``.

    Thread-safety: stream order is submission order — ``extend`` mutates
    the accumulated stream and submits under one lock, so concurrent
    extends serialize into a well-defined stream.  Async completions
    (churn bookkeeping) take the same lock.

    ``sid`` names the session (auto-generated when omitted); with a
    ``journal``, every committed delta's raw input is appended under that
    sid so :func:`restore_sessions` can replay the session after a crash.
    A delta is journaled when it COMMITS (enqueued into the server), before
    its dispatch resolves — a delta whose dispatch later fails stays both
    committed and journaled, matching the stream semantics (the failed
    extend raised, but the stream already advanced; replay reproduces that
    state exactly).
    """

    # the public accounting attrs (deltas_absorbed / churn_total /
    # last_update) are documented benign-snapshot reads and stay undeclared
    _GUARDED_BY = {
        "_mode": "_lock",
        "_fn": "_lock",
        "_active": "_lock",
        "_seen": "_lock",
        "_prev_ids": "_lock",
        "_seq": "_lock",
        "_closed": "_lock",
    }

    _SID_COUNTER = itertools.count()

    def __init__(
        self,
        server,
        spec: SelectionSpec,
        *,
        sid: str | None = None,
        journal: "SessionJournal | None" = None,
    ):
        if not isinstance(spec, SelectionSpec):
            raise TypeError(
                f"open_session() takes a SelectionSpec, got {type(spec).__name__!r}"
            )
        self.sid = (
            sid if sid is not None else f"s{next(SelectionSession._SID_COUNTER)}"
        )
        self._journal = journal
        self._replaying = False  # restore_sessions: suppress re-journaling
        self._server = server
        self._async = isinstance(server, AsyncSelectionServer)
        self._metrics = server.metrics
        self._spec = spec
        self._lock = threading.Lock()
        self._mode: str | None = None  # "features" | "indices", set by 1st extend
        self._fn = spec.fn  # features mode: the concatenated-stream function
        self._active: list[int] = []  # indices mode: universe ids, arrival order
        self._seen: set[int] = set()
        self._prev_ids: set = set()
        self._seq = 0
        self._closed = False
        self.deltas_absorbed = 0
        self.churn_total = 0
        self.last_update: SessionUpdate | None = None
        self._metrics.inc("sessions_opened")

    # -- client API ----------------------------------------------------------

    @property
    def mode(self) -> str | None:
        return self._mode  # lint: ok(LOCKDISC): benign racy snapshot read for observability

    @property
    def closed(self) -> bool:
        return self._closed  # lint: ok(LOCKDISC): benign racy snapshot read for observability

    def extend(self, features=None, indices=None):
        """Absorb one delta and re-select over the full stream.

        Exactly one of ``features`` (new raw rows for the session family's
        extender) or ``indices`` (universe ids to unlock; repeats are
        ignored) must be given; the first call fixes the session's mode.
        Returns a :class:`SessionUpdate` on a sync server, or a Future
        resolving to one on an async server (cancelled if the server drops
        the delta via ``close(flush=False)``).  Raises
        :class:`~repro.launch.serve.ServerOverloaded` synchronously when
        the server applies backpressure.
        """
        if (features is None) == (indices is None):
            raise TypeError("extend() takes exactly one of features= or indices=")
        want = "features" if features is not None else "indices"
        t0 = time.monotonic()
        with self._lock:
            if self._closed:
                raise SessionClosed("extend() on a closed SelectionSession")
            if self._mode is not None and self._mode != want:
                raise ValueError(
                    f"session is in {self._mode!r} mode; extend() cannot "
                    f"switch to {want!r} deltas"
                )
            # the "session-extend" fault boundary: fires BEFORE the delta is
            # built, so an injected fault leaves the stream untouched (the
            # retryable position — the client re-extends)
            faults.check(
                "session-extend",
                session=self.sid,
                seq=self._seq + 1,
                mode=want,
                family=type(self._spec.fn).__name__,
            )
            # build the delta WITHOUT committing, submit, then commit — so a
            # failed extend (unsupported family, ServerOverloaded) leaves the
            # stream untouched and a retry cannot double-append the delta
            if want == "features":
                rows = np.asarray(features, np.float32)
                n_delta = int(rows.shape[0])
                fn = (
                    resolve_extender(type(self._fn))(self._fn, rows)
                    if n_delta
                    else self._fn
                )
                active = None
                n_total = int(fn.n)
            else:
                raw_idx = np.asarray(indices, np.int64).reshape(-1)
                fresh = []
                for i in raw_idx:
                    i = int(i)
                    if not 0 <= i < self._spec.fn.n:
                        raise ValueError(
                            f"index {i} outside the universe "
                            f"[0, {self._spec.fn.n})"
                        )
                    if i not in self._seen and i not in fresh:
                        fresh.append(i)
                if not self._active and not fresh:
                    raise ValueError(
                        "the first indices delta must unlock at least one "
                        "universe element"
                    )
                n_delta = len(fresh)
                active = np.asarray(self._active + fresh, np.int32)
                fn = resolve_restrictor(type(self._spec.fn))(self._spec.fn, active)
                n_total = int(active.shape[0])
            spec = SelectionSpec(
                fn,
                min(self._spec.budget, n_total),
                self._spec.optimizer,
                stopIfZeroGain=self._spec.stop_if_zero,
                stopIfNegativeGain=self._spec.stop_if_negative,
                use_kernel=self._spec.use_kernel,
                deadline_s=self._spec.deadline_s,
                retry=self._spec.retry,  # deltas inherit the session's policy
            )
            if self._async:
                inner = self._server.submit(spec)  # may raise ServerOverloaded
            else:
                rid = self._server.submit_spec(spec)  # ditto
                inner = None
            # the delta is enqueued: commit it to the session's stream
            self._mode = want
            if want == "features":
                self._fn = fn
            else:
                self._seen.update(fresh)
                self._active.extend(fresh)
            seq = self._seq = self._seq + 1
            if self._journal is not None and not self._replaying:
                # journal the committed delta's RAW input — replay will
                # re-preprocess it through this same extend path
                self._journal.append(
                    self.sid, seq, want, rows if want == "features" else raw_idx
                )
        if not self._async:
            out = self._server.flush()
            resp = out.pop(rid, None)
            self._server.hold_undelivered(out)  # co-travellers' answers
            if resp is None:
                # resilient flush: the delta exhausted its retries and
                # resolved to a typed failure instead of a response
                fails = self._server.take_failures()
                err = fails.pop(rid, None)
                if fails:
                    self._server.hold_failures(fails)  # not ours to consume
                if err is None:
                    raise KeyError(
                        f"flush returned no response for session delta {rid!r}"
                    )
                raise err
            return self._absorb(resp, seq, n_total, n_delta, active, t0)

        out: Future = Future()

        def _chain(done: Future):
            if done.cancelled():
                out.cancel()
                return
            exc = done.exception()
            if exc is not None:
                out.set_exception(exc)
                return
            try:
                upd = self._absorb(done.result(), seq, n_total, n_delta, active, t0)
            except BaseException as e:  # never strand the chained future
                out.set_exception(e)
                return
            out.set_result(upd)

        inner.add_done_callback(_chain)
        return out

    def close(self) -> None:
        """Mark the session closed (idempotent).  In-flight async deltas
        still resolve; further ``extend`` calls raise
        :class:`SessionClosed`."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._metrics.inc("sessions_closed")

    def __enter__(self) -> "SelectionSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- internals -----------------------------------------------------------

    def _absorb(self, resp, seq, n_total, n_delta, active, t0) -> SessionUpdate:
        if active is None:  # features mode: ids are already stream positions
            selection = [(int(j), float(g)) for j, g in resp.selection]
        else:  # indices mode: map active-list positions back to universe ids
            selection = [(int(active[j]), float(g)) for j, g in resp.selection]
        latency = time.monotonic() - t0
        ids = {j for j, _ in selection}
        with self._lock:
            churn = len(self._prev_ids ^ ids)
            self._prev_ids = ids
            self.deltas_absorbed += 1
            self.churn_total += churn
            upd = SessionUpdate(
                seq=seq,
                selection=selection,
                result=resp.result,
                response=resp,
                n_total=n_total,
                n_delta=n_delta,
                churn=churn,
                latency_s=latency,
            )
            self.last_update = upd
        self._metrics.observe_delta(latency, churn=churn)
        return upd

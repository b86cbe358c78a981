"""Spans of the served selection path (``launch/metrics.py``): nesting,
parent and wave ids, the bounded process-wide ring, the pad-waste byte
and column counters, the histograms fed from span durations, and the spans' place on
a ``jax.profiler`` trace's host plane."""
import glob
import os
import threading

import jax
import numpy as np
import pytest

from repro.core import (
    FacilityLocation,
    FacilityLocationMF,
    GraphCut,
    SelectionSpec,
    create_kernel,
)
from repro.launch import metrics as metrics_mod
from repro.launch.async_serve import AsyncSelectionServer
from repro.launch.coalesce import SelectionRequest, waves_for_group
from repro.launch.metrics import ServerMetrics, SpanRing, spans
from repro.launch.serve import SelectionServer

# every span one served wave records, and the span each hangs under
PARENT_OF = {
    "serve.submit": "serve.select",
    "serve.drain": "serve.select",
    "serve.wave": "serve.select",
    "engine.build": "serve.wave",
    "engine.launch": "serve.wave",
    "engine.fetch": "serve.wave",
    "serve.demux": "serve.wave",
    "serve.queue": "serve.wave",
}


def _fl(rng, n=24):
    x = rng.normal(size=(n, 8)).astype(np.float32)
    return FacilityLocation.from_kernel(np.asarray(create_kernel(x, metric="euclidean")))


def _flmf(rng, n=24):
    x = rng.normal(size=(n, 8)).astype(np.float32)
    return FacilityLocationMF.from_features(x, metric="euclidean")


def _pad_share(stacked: int, live: int) -> float:
    return 1.0 - live / stacked


def _tree(root_id):
    """The spans of the ring under ``root_id``, root included."""
    ring = spans()
    keep = {root_id}
    changed = True
    while changed:
        changed = False
        for s in ring:
            if s.parent_id in keep and s.span_id not in keep:
                keep.add(s.span_id)
                changed = True
    return [s for s in ring if s.span_id in keep]


def _last_select():
    return [s for s in spans() if s.name == "serve.select"][-1]


def test_spans_nest_with_parent_and_wave_ids():
    m = ServerMetrics()
    with m.span("outer") as outer:
        with m.span("wave", wave=True, B=2) as wave:
            with m.span("inner") as inner:
                pass
            wave.attrs["late"] = 1

            def other_thread():  # another thread's spans start their own tree
                with m.span("elsewhere"):
                    pass

            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
    with pytest.raises(KeyError):
        with m.span("failing"):
            raise KeyError("boom")
    by_name = {s.name: s for s in spans()[-5:]}
    assert by_name["outer"].parent_id == 0 and by_name["outer"].wave_id == 0
    assert by_name["wave"].parent_id == outer.span_id
    assert by_name["wave"].wave_id == wave.wave_id > 0
    assert by_name["inner"].parent_id == wave.span_id
    assert by_name["inner"].wave_id == wave.wave_id  # inherited from the wave
    assert by_name["elsewhere"].parent_id == 0 and by_name["elsewhere"].wave_id == 0
    assert by_name["wave"].attrs == {"B": 2, "late": 1}
    assert by_name["failing"].attrs == {"error": "KeyError"}
    for child, parent in (("wave", "outer"), ("inner", "wave")):
        c, p = by_name[child], by_name[parent]
        assert p.start_ns <= c.start_ns <= c.end_ns <= p.end_ns
    assert inner.seconds == by_name["inner"].seconds >= 0


def test_ring_stays_bounded_under_the_async_flush_thread(rng, monkeypatch):
    """Submitters and the flush thread write one ring; it keeps the newest
    spans and drops the oldest."""
    monkeypatch.setattr(metrics_mod, "_RING", SpanRing(capacity=8))
    specs = [SelectionSpec(_fl(rng), 3) for _ in range(12)]
    with AsyncSelectionServer(max_pending=2, flush_interval=0.01) as server:
        futures = [server.submit(s, rid=i) for i, s in enumerate(specs)]
        for f in futures:
            f.result(timeout=300)
    held = spans()
    assert len(held) == 8 and len({s.span_id for s in held}) == 8
    # the first submit closed before the 11 later ones even began
    assert not any(s.name == "serve.submit" and s.attrs["rid"] == 0 for s in held)
    with ServerMetrics().span("after"):
        pass
    assert len(spans()) == 8 and spans()[-1].name == "after"


def test_one_served_fl_wave_records_every_span(rng):
    server = SelectionServer()
    responses = server.select([SelectionSpec(_fl(rng), 4), SelectionSpec(_fl(rng), 5)])
    select = _last_select()
    tree = _tree(select.span_id)
    names = {s.name for s in tree}
    assert names == set(PARENT_OF) | {"serve.select"}
    by_id = {s.span_id: s for s in tree}
    (wave,) = [s for s in tree if s.name == "serve.wave"]
    for s in tree:
        if s is select:
            continue
        assert by_id[s.parent_id].name == PARENT_OF[s.name], s
        if s.name != "serve.queue":  # a queue dwell starts before its wave
            p = by_id[s.parent_id]
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns, s
    for s in tree:
        expect = wave.wave_id if PARENT_OF.get(s.name) == "serve.wave" or s is wave else 0
        assert s.wave_id == expect, s
    assert wave.attrs["rids"] == tuple(r.rid for r in responses)
    assert wave.attrs["backend"] == responses[0].backend
    assert wave.attrs["B"] == 2 and wave.attrs["n_bucket"] == 32
    submits = [s for s in tree if s.name == "serve.submit"]
    queues = [s for s in tree if s.name == "serve.queue"]
    assert [s.attrs["rid"] for s in submits] == [s.attrs["rid"] for s in queues]
    assert all(q.end_ns == wave.start_ns for q in queues)
    (drain,) = [s for s in tree if s.name == "serve.drain"]
    assert drain.attrs["waves"] == 1


def test_wave_bytes_equal_nbytes_by_hand(rng):
    """FL pads columns only; GraphCut pads both axes of its kernel and its
    totals, and keeps its scalar lam; batch pads count in bytes_stacked."""
    fl = [SelectionRequest(rid=i, spec=SelectionSpec(_fl(rng, 24), 3)) for i in range(2)]
    (wave,) = waves_for_group(fl, b_multiple=4)
    assert wave.bytes_stacked == 4 * 24 * 32 * 4
    assert wave.bytes_live == 2 * 24 * 24 * 4
    x = rng.normal(size=(20, 8)).astype(np.float32)
    gc = GraphCut.from_kernel(np.asarray(create_kernel(x, metric="euclidean")), lam=0.3)
    (wave,) = waves_for_group([SelectionRequest(rid=0, spec=SelectionSpec(gc, 3))])
    assert wave.bytes_stacked == 32 * 32 * 4 + 32 * 4 + 4
    assert wave.bytes_live == 20 * 20 * 4 + 20 * 4 + 4
    server = SelectionServer(max_wave=2)
    server.select([SelectionSpec(_fl(rng, 24), 3) for _ in range(3)])
    c = server.metrics.snapshot()["counters"]
    assert c["bytes_stacked"] == 3 * 24 * 32 * 4
    assert c["bytes_live"] == 3 * 24 * 24 * 4
    recorded = [s for s in _tree(_last_select().span_id) if s.name == "serve.wave"]
    assert sum(s.attrs["bytes_stacked"] for s in recorded) == c["bytes_stacked"]
    assert sum(s.attrs["bytes_live"] for s in recorded) == c["bytes_live"]


@pytest.mark.parametrize("family", ["fl", "flmf"])
def test_wave_columns_count_every_swept_candidate(rng, family):
    """cols_stacked is B x n_bucket, batch pads included; cols_live the real
    requests' own n.  Dense FL pads no row, so its columns' pad share is its
    bytes'; a FacilityLocationMF wave's is 1 - n / bucket, which its bytes
    (one zero feature row a pad column) understate."""
    make = _fl if family == "fl" else _flmf
    reqs = [SelectionRequest(rid=i, spec=SelectionSpec(make(rng, 24), 3)) for i in range(2)]
    (wave,) = waves_for_group(reqs, b_multiple=4)
    assert (wave.cols_stacked, wave.cols_live) == (4 * 32, 2 * 24)
    (alone,) = waves_for_group(reqs[:1])
    assert (alone.cols_stacked, alone.cols_live) == (32, 24)
    share = _pad_share(alone.cols_stacked, alone.cols_live)
    assert share == 1 - 24 / 32
    byte_share = _pad_share(alone.bytes_stacked, alone.bytes_live)
    if family == "fl":
        assert byte_share == share
        assert _pad_share(wave.bytes_stacked, wave.bytes_live) == _pad_share(
            wave.cols_stacked, wave.cols_live
        )
    else:
        assert 0 < byte_share < share


def test_wave_spans_and_counters_carry_the_columns(rng):
    server = SelectionServer(max_wave=2)
    server.select([SelectionSpec(_flmf(rng, 24), 3) for _ in range(3)])
    c = server.metrics.snapshot()["counters"]
    assert c["cols_stacked"] == 2 * 32 + 32
    assert c["cols_live"] == 3 * 24
    recorded = [s for s in _tree(_last_select().span_id) if s.name == "serve.wave"]
    assert len(recorded) == 2
    assert sum(s.attrs["cols_stacked"] for s in recorded) == c["cols_stacked"]
    assert sum(s.attrs["cols_live"] for s in recorded) == c["cols_live"]


def test_wave_s_and_queue_s_equal_their_span_durations(rng):
    server = SelectionServer(max_wave=2)
    responses = server.select([SelectionSpec(_fl(rng), 3) for _ in range(3)])
    tree = _tree(_last_select().span_id)
    waves = {s.span_id: s for s in tree if s.name == "serve.wave"}
    queues = {s.attrs["rid"]: s for s in tree if s.name == "serve.queue"}
    assert len(waves) == 2 and len(queues) == 3
    for r in responses:
        q = queues[r.rid]
        assert r.queue_s == q.seconds
        assert r.wave_s == waves[q.parent_id].seconds
        assert r.latency_s == q.seconds + waves[q.parent_id].seconds
    m = server.metrics
    assert m.wave_s.count == 2
    assert m.wave_s.total == sum(s.seconds for s in waves.values())
    assert m.wave_s.max == max(s.seconds for s in waves.values())
    assert m.queue_s.count == 3
    assert m.queue_s.max == max(s.seconds for s in queues.values())
    assert m.queue_s.total == pytest.approx(sum(s.seconds for s in queues.values()), rel=1e-12)


def test_profiler_trace_holds_the_spans_on_its_host_plane(rng, tmp_path):
    server = SelectionServer()
    spec = SelectionSpec(_fl(rng), 3)
    server.select([spec])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        server.select([spec])
    (path,) = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb"))
    profile = jax.profiler.ProfileData.from_file(path)
    events = {
        ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in profile.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    for name in ("serve.select", "serve.wave", "engine.launch", "engine.fetch"):
        assert name in events, sorted(events)[:20]
    assert "serve.queue" not in events  # ring only
    s0, s1 = events["serve.select"]
    w0, w1 = events["serve.wave"]
    f0, f1 = events["engine.fetch"]
    assert s0 <= w0 <= f0 <= f1 <= w1 <= s1  # nested on the trace's clock

"""The trace reduction, on a hand-made trace whose answer is known and on
a small trace recorded on a TPU v5e by ``record_trace.py``."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce

RECORDED = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
SPANS = ("epoch.similarity", "epoch.select", "rerank.wait")


def _ev(name, start, end):
    return NS(name=name, start_ns=start, duration_ns=end - start)


def _profile():
    """One device: ops at [10, 30] and [25, 40] in program A, a while loop
    [58, 72] around a fusion [60, 70] in program B, one op outside the
    window; host spans around them."""
    device = NS(
        name="/device:TPU:0",
        lines=[
            NS(name="XLA Modules", events=[_ev("jit_bench_similarity(1)", 5, 45), _ev("jit_select(2)", 55, 75)]),
            NS(name="XLA Ops", events=[_ev("dot", 10, 30), _ev("fusion", 25, 40), _ev("while", 58, 72),
                                       _ev("fusion", 60, 70), _ev("late", 120, 130)]),
        ],
    )
    host = NS(
        name="/host:CPU",
        lines=[NS(name="python", events=[_ev("bench.window", 0, 100), _ev("epoch.similarity", 0, 45),
                                         _ev("rerank.wait", 45, 60), _ev("unrelated", 70, 100)])],
    )
    return NS(planes=[host, device])


def test_hand_made_trace():
    r = trace_reduce.reduce(_profile(), "bench.window", SPANS)
    ns = 1e-9
    assert r["devices"] == 1 and r["n_ops"] == 4
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx(44 * ns)  # [10, 40] and [58, 72]
    # the while loop is charged only the time its body's fusion leaves
    assert r["ops"] == pytest.approx({"dot": 20 * ns, "fusion": 25 * ns, "while": 4 * ns})
    assert r["modules"] == pytest.approx({"jit_bench_similarity(1)": 30 * ns, "jit_select(2)": 14 * ns})
    # gaps [0, 10], [40, 58], [72, 100]: the first under epoch.similarity,
    # the second split 5 / 13, the last under no span of the benchmark's
    assert r["idle_by_span"] == pytest.approx(
        {"epoch.similarity": 15 * ns, "rerank.wait": 13 * ns, trace_reduce.NO_SPAN: 28 * ns}
    )
    assert trace_reduce.top(r["ops"], 1) == [["fusion", pytest.approx(25 * ns)]]


def test_no_device_plane_reads_nothing():
    p = _profile()
    p.planes = p.planes[:1]
    assert trace_reduce.reduce(p, "bench.window", SPANS) is None


def test_recorded_tpu_trace():
    """Three rounds of a similarity program, a gain program and a 5 ms
    host sleep (record_trace.py), on one TPU v5e."""
    r = trace_reduce.reduce_file(str(RECORDED), "bench.window", SPANS)
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(sum(r["ops"].values()), rel=1e-6)
    similarity = sum(s for m, s in r["modules"].items() if "bench_similarity" in m)
    gains = sum(s for m, s in r["modules"].items() if m.startswith("jit_gains"))
    assert similarity > 0 and gains > 0
    assert r["idle_by_span"]["rerank.wait"] >= 3 * 0.004
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)

"""The readers of the program's own spans and kernel name
(``serve_host_ms``, ``pad_waste``, ``fl_kernel_roofline``), on a CPU run of
the small cell with the chip's peaks given and a hand-made device trace."""
import time

import pytest

import run
import trace_reduce

CELL = "craig-cifar10.naive"
PEAKS = {"hbm_bytes_per_s": 819e9}
READERS = ("serve_host_ms.craig", "pad_waste.craig", "fl_kernel_roofline.craig")
KERNEL_S = 0.002 + 0.001
# the trace's per-op table: the kernel under its fixed name (twice, as the
# compiler may number two calls), and ops the reader must not count
OPS = {
    "%fl_gains.11 = f32[2,128,128]{2,1,0} custom-call(f32[2,96,128]{2,1,0} %pad.5), custom_call_target=\"tpu_custom_call\"": 0.002,
    "%fl_gains = f32[2,128,128]{2,1,0} custom-call(f32[2,96,128]{2,1,0} %pad.6)": 0.001,
    "%fl_gains_pallas.11 = f32[2,128,128]{2,1,0} custom-call(f32[2,96,128]{2,1,0} %pad.7)": 0.5,
    "%pad.5 = f32[2,128,128]{2,1,0} pad(f32[2,96,128]{2,1,0} %p)": 0.25,
}


def _reduction(*_):
    return {"devices": 1, "window_s": 1.0, "busy_s": 0.9, "n_ops": 4, "ops": dict(OPS),
            "modules": {"jit__batched_naive(1)": 0.8}, "idle_by_span": {}}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run on the CPU: its result, and the run its readers read."""
    from conftest import small_checkout

    parts = run.load_cell(CELL, small_checkout(tmp_path_factory.mktemp("checkout")))
    seen = {}
    real_reader = run.reader

    def capturing_reader(metrics_dir, name):
        read = real_reader(metrics_dir, name)

        def wrapped(r):
            seen["run"] = r
            return read(r)

        return wrapped

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(trace_reduce, "find", lambda _: "trace.xplane.pb")
        mp.setattr(trace_reduce, "reduce_file", _reduction)
        mp.setattr(run, "reader", capturing_reader)
        result = run.run_cell(parts, 2**31 + 7, 1.0, True, PEAKS, time.perf_counter())
    return parts, result, seen["run"]


def test_readers_report_on_a_run_with_peaks(traced):
    _, result, _ = traced
    assert result["correct"], result["checks"]
    assert set(READERS) <= set(result["metrics"])
    for name in READERS:
        assert result["metrics"][name]["value"] > 0


def test_the_window_filter_drops_the_warm_up_epoch(traced):
    from repro.launch.metrics import spans

    parts, result, r = traced
    lo, hi = r["epochs"][0]["start"] * 1e9, r["epochs"][-1]["end"] * 1e9
    ring = spans()
    selects = [s for s in ring if s.name == "serve.select"]
    warm_up = [s for s in selects if s.end_ns < lo]
    in_window = [s for s in selects if lo <= s.start_ns and s.end_ns <= hi]
    assert warm_up, "the set-up epoch's select is in the ring"
    assert len(in_window) == len(r["epochs"])
    fetch_ns = {}
    by_id = {s.span_id: s for s in ring}
    for s in ring:
        if s.name == "engine.fetch":
            wave = by_id[s.parent_id]
            fetch_ns[wave.parent_id] = fetch_ns.get(wave.parent_id, 0) + s.end_ns - s.start_ns
    host_ms = [(s.end_ns - s.start_ns - fetch_ns[s.span_id]) * 1e-6 for s in in_window]
    assert result["metrics"]["serve_host_ms.craig"]["value"] == pytest.approx(sum(host_ms) / len(host_ms))


def test_pad_waste_matches_the_shapes(traced):
    parts, result, _ = traced
    n = parts["config"]["n"]  # each class's FL kernel is (n, n), its columns padded
    bucket = 1 << (n - 1).bit_length()
    assert result["metrics"]["pad_waste.craig"]["value"] == pytest.approx(100.0 * (1 - n / bucket))


def test_kernel_roofline_counts_the_fixed_name_only(traced):
    _, result, r = traced
    nbytes = sum(sum(e["n_evals"]) * e["rows"] * 4 for e in r["epochs"])
    expect = 100.0 * nbytes / PEAKS["hbm_bytes_per_s"] / KERNEL_S
    assert result["metrics"]["fl_kernel_roofline.craig"]["value"] == pytest.approx(expect)
    # a program whose kernel has no fixed name (its instruction is named
    # after a Python wrapper) reads nothing
    read = run.reader(traced[0]["metrics_dir"], "fl_kernel_roofline.craig")
    unnamed = {op: s for op, s in OPS.items() if "%fl_gains " not in op and "%fl_gains." not in op}
    assert read(dict(r, trace=dict(r["trace"], ops=unnamed))) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_nothing_without_a_chip(traced, name):
    parts, _, r = traced
    assert run.reader(parts["metrics_dir"], name)(dict(r, peaks={})) is None

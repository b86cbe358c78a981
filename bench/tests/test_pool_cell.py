"""The pool cell, ``cifar10-pool-mf.naive``: its files found by name and
run end to end on the CPU with a small pool, and its three readers
(``flmf_roofline.pool``, ``flmf_kernel_roofline.pool``, ``pad_cols.pool``)
on run dicts made by hand."""
import json
import types

import pytest

import run

CELL = "cifar10-pool-mf.naive"
READERS = ("flmf_roofline.pool", "flmf_kernel_roofline.pool", "pad_cols.pool")
PEAK = 197e12
CONFIG = {"dim": 512}
# two epochs of one selection each: 100 steps over a pool of 50,000
EPOCHS = [{"start": 1.0 + i, "end": 2.0 + i, "rows": 50_000, "n_evals": [100 * 50_000]} for i in range(2)]
WORK = 2 * 100 * 50_000 * 50_000 * 512 * 2
OPS = {
    "%flmf_gains.12 = f32[1,1,65536]{2,1,0} custom-call(f32[1,50176,512]{2,1,0} %pad.3)": 20.0,
    "%flmf_gains = f32[1,1,65536]{2,1,0} custom-call(f32[1,50176,512]{2,1,0} %pad.4)": 5.0,
    "%flmf_gains_pallas.3 = f32[1,1,65536]{2,1,0} custom-call(f32[1,50176,512]{2,1,0} %pad.5)": 7.0,
    "%fl_gains.2 = f32[1,128,65536]{2,1,0} custom-call(f32[1,50000,65536]{2,1,0} %p)": 11.0,
    "%pad.3 = f32[1,50176,512]{2,1,0} pad(f32[1,50000,512]{2,1,0} %p)": 0.5,
}
MODULES = {"jit__batched_naive(7)": 26.0, "jit_bench_features(3)": 2.0, "jit_pad(1)": 0.25}


def _run(**changes):
    trace = {"window_s": 30.0, "busy_s": 29.0, "ops": dict(OPS), "modules": dict(MODULES)}
    return dict({"config": CONFIG, "epochs": EPOCHS, "peaks": {"bf16_flops_per_s": PEAK}, "trace": trace}, **changes)


def _read(name, r):
    return run.reader(run.BENCH / "metrics", name)(r)


def test_the_cell_runs_end_to_end_by_name(checkout):
    """The committed cell with its pool cut to 256 points: the features
    builder, the epochs generator and the cell's own limits, found by name."""
    path = checkout / "bench" / "configs" / "cifar10-pool-mf.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), n=256, budget=12)))
    result = run.main(
        ["--workload", CELL, "--seed", str(2**32 + 17), "--seconds", "1", "--trace", "0"],
        root=checkout,
        chip=False,
    )
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"selections_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["checks"]["missing"]["value"] == 0


def test_flops_over_the_device_time_outside_the_builder():
    expect = 100.0 * WORK / PEAK / (26.0 + 0.25)
    assert _read("flmf_roofline.pool", _run()) == pytest.approx(expect)


def test_the_left_out_program_is_the_builders():
    builder = run.module(run.BENCH / "builders", "features")
    reader = run.module(run.BENCH / "metrics", "flmf_roofline.pool")
    assert reader.FEATURES_PROGRAM == builder.PROGRAM


def test_kernel_share_counts_the_fixed_name_only():
    expect = 100.0 * WORK / PEAK / (20.0 + 5.0)
    assert _read("flmf_kernel_roofline.pool", _run()) == pytest.approx(expect)
    unnamed = {op: s for op, s in OPS.items() if not op.startswith(("%flmf_gains ", "%flmf_gains."))}
    r = _run()
    r["trace"]["ops"] = unnamed
    assert _read("flmf_kernel_roofline.pool", r) is None


def _wave(start, end, **attrs):
    return types.SimpleNamespace(name="serve.wave", start_ns=int(start * 1e9), end_ns=int(end * 1e9), attrs=attrs)


def test_pad_cols_reads_the_window_waves(monkeypatch):
    import repro.launch.metrics as metrics

    ring = [
        _wave(0.2, 0.8, cols_stacked=65_536, cols_live=1),  # the warm-up epoch's
        _wave(1.1, 1.9, cols_stacked=65_536, cols_live=50_000),
        _wave(2.1, 2.9, cols_stacked=2 * 65_536, cols_live=2 * 50_000),
    ]
    monkeypatch.setattr(metrics, "spans", lambda: ring)
    assert _read("pad_cols.pool", _run()) == pytest.approx(100.0 * (1 - 50_000 / 65_536))
    # a program whose waves carry no column counters reads nothing
    ring[:] = [_wave(1.1, 1.9, bytes_stacked=8, bytes_live=4)]
    assert _read("pad_cols.pool", _run()) is None


@pytest.mark.parametrize("name", READERS)
def test_each_reader_reads_nothing_without_a_trace_or_a_chip(name, monkeypatch):
    import repro.launch.metrics as metrics

    monkeypatch.setattr(metrics, "spans", lambda: [_wave(1.1, 1.9, cols_stacked=4, cols_live=3)])
    assert _read(name, _run()) is not None
    assert _read(name, _run(peaks={})) is None
    if name != "pad_cols.pool":
        assert _read(name, _run(trace=None)) is None

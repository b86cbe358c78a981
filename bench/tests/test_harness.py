"""The harness off the chip: it refuses what is not a known TPU, finds a
new cell's files by name, and reports ``correct`` false when the timed path
alters an answer."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import run
from conftest import BENCH, ROOT


def _main(checkout, workload, *, seed=5, seconds=1.0, trace=0):
    return run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=checkout,
        chip=False,
    )


def test_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", "craig-cifar10.naive",
           "--seed", "1", "--seconds", "1", "--trace", "0"]
    out = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "platform=cpu" in out.stdout


@pytest.mark.parametrize(
    "kind, known", [("TPU v5 lite", True), ("TPU v5e", True), ("TPU v99 imagined", False)]
)
def test_a_tpu_kind_needs_its_peaks(monkeypatch, kind, known):
    import jax

    fake = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    if known:
        _, peaks = run.find_chip(1)
        assert peaks["hbm_bytes_per_s"] == 819e9
    else:
        with pytest.raises(SystemExit):
            run.find_chip(1)
    with pytest.raises(SystemExit):  # a one-chip machine cannot hold a four-chip cell
        run.find_chip(4)


# a generator of its own for a new mix: the same wave, built once in
# set-up, served again and again
ROUNDS = """
import time

import jax

import data

SPANS = ("round",)


def inputs(config, traffic, seed):
    xs = data.for_config(config, seed)
    return [(g, xs[g]) for g in range(config["groups"])]


class Rounds:
    spans = SPANS

    def __init__(self, config, traffic, seed, build):
        from repro.launch.serve import SelectionServer

        self.xs = data.for_config(config, seed)
        built = build.program(config)(self.xs)
        self.specs = [build.spec(config, traffic["optimizer"], item) for item in built]
        self.server = SelectionServer(max_wave=config["server"]["max_wave"])
        self.server.select(self.specs)
        self.rounds, self.answers = [], []

    def window(self, seconds):
        start = time.perf_counter()
        while time.perf_counter() - start < seconds:
            self.rounds.append(self.server.select(self.specs))
        for responses in self.rounds:
            self.answers += [(g, r.result.order, r.result.gains) for g, r in enumerate(responses)]
        return start, time.perf_counter()

    def record(self):
        n = sum(len(r) for r in self.rounds)
        return {"rounds": len(self.rounds), "attempted": n, "failed": 0, "log": {"rounds": len(self.rounds)}}

    def inputs(self, key):
        return self.xs[key]

    def release(self):
        self.server = None


def start(config, traffic, seed, seconds, build):
    return Rounds(config, traffic, seed, build)
"""

# a builder of its own for a new configuration: each group's kernel from
# the library's Pallas similarity route
PALLAS_KERNEL = """
import jax

PROGRAM = "bench_similarity"


def program(config):
    from repro.core import create_kernel

    def bench_similarity(xs):
        return tuple(create_kernel(x, metric=config["metric"], use_pallas=True) for x in xs)

    return jax.jit(bench_similarity)


def spec(config, optimizer, kernel):
    import repro.core as core

    fn = core.FacilityLocation.from_kernel(kernel, use_kernel=config["use_kernel"])
    return core.SelectionSpec(fn, config["budget"], optimizer)
"""


def test_new_files_are_found_by_name(checkout):
    """A configuration with a builder of its own, a mix with a generator of
    its own and a metric, dropped in as files with their entries in
    BENCHMARK.json, run with no edit to the harness."""
    bench = checkout / "bench"
    config = json.loads((bench / "configs" / "craig-cifar10.json").read_text())
    config.update(name="tiny-fl", metric="cosine", build="pallas_kernel", groups=3, n=40, budget=5)
    (bench / "configs" / "tiny-fl.json").write_text(json.dumps(config))
    (bench / "builders" / "pallas_kernel.py").write_text(PALLAS_KERNEL)
    (bench / "traffic" / "repeat.json").write_text(json.dumps({"generator": "rounds", "optimizer": "LazyGreedy"}))
    (bench / "traffic" / "rounds.py").write_text(ROUNDS)
    (bench / "metrics" / "rounds_run.py").write_text(
        "def read(run):\n    return float(run['rounds']) if run.get('rounds') else None\n"
    )
    limits = json.loads((bench / "limits" / "craig-cifar10.naive.json").read_text())
    (bench / "limits" / "tiny-fl.repeat.json").write_text(json.dumps(limits))
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-fl", "source": "test", "file": "bench/configs/tiny-fl.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-fl.repeat", "config": "tiny-fl",
                              "traffic": "repeat", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "rounds_run", "unit": "rounds", "better": "higher",
                               "bound": 0.25, "source": "host_clock",
                               "workloads": ["tiny-fl.repeat"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    result = _main(checkout, "tiny-fl.repeat")
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"rounds_run", "setup_s"}
    assert result["metrics"]["rounds_run"]["value"] >= 1
    assert result["attempted"] == 3 * result["metrics"]["rounds_run"]["value"]
    assert list(result)[-1] == "checks"


def test_a_metric_split_by_cell_shares_its_reader(tmp_path):
    (tmp_path / "idle.py").write_text("def read(run):\n    return run['x']\n")
    (tmp_path / "idle.b.py").write_text("def read(run):\n    return -run['x']\n")
    assert run.reader(tmp_path, "idle.a")({"x": 2.0}) == 2.0
    assert run.reader(tmp_path, "idle.b")({"x": 2.0}) == -2.0
    with pytest.raises(SystemExit):
        run.reader(tmp_path, "busy.a")


def test_closed_loop_run(checkout):
    result = _main(checkout, "craig-cifar10.naive", seed=2**31 + 12345)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"selections_per_s", "setup_s"}
    assert result["failed"] == 0 and result["attempted"] % 2 == 0
    assert result["checks"]["missing"]["value"] == 0


def test_traced_run_reports_per_layer_metrics_it_can_read(checkout):
    """On the CPU the trace has no TPU plane: the device metrics are left
    out, never written from a CPU run, and the host-side one remains."""
    result = _main(checkout, "craig-cifar10.naive", trace=1)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"similarity_ms.craig"}
    assert "busy_s" not in result["device"]


def _alter(kind):
    """A BatchedEngine.run that alters one answer where it is produced."""
    from repro.core.optimizers.batched import BatchedEngine

    real = BatchedEngine.run

    def run_altered(self, *args, **kwargs):
        results = real(self, *args, **kwargs)
        r = results[0]
        order, gains = np.array(r.order), np.array(r.gains)
        if kind == "pick":
            unpicked = np.setdiff1d(np.arange(self.n), order)
            order[len(order) // 2] = unpicked[-1]
        else:
            gains[0] = gains[0] * 1.01
        results[0] = type(r)(order=order, gains=gains, n_evals=r.n_evals, value=r.value)
        return results

    return run_altered


@pytest.mark.parametrize("kind", ["pick", "gain"])
def test_an_altered_answer_is_not_correct(checkout, monkeypatch, kind):
    from repro.core.optimizers.batched import BatchedEngine

    monkeypatch.setattr(BatchedEngine, "run", _alter(kind))
    result = _main(checkout, "craig-cifar10.naive")
    assert result["correct"] is False
    failed = [k for k, c in result["checks"].items() if not c["value"] <= c["limit"]]
    assert failed, result["checks"]

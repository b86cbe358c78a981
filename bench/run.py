#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is a file found by name from
``BENCHMARK.json``, so a new cell, configuration, mix, generator or metric
is new files and entries:

- its configuration, ``bench/configs/<config>.json``, which names the
  builder that makes the program's selection function from a group's
  embeddings, ``bench/builders/<build>.py``;
- its traffic mix, ``bench/traffic/<traffic>.json``, which names the
  generator that drives the program with it,
  ``bench/traffic/<generator>.py``;
- its metrics, one reader each: ``bench/metrics/<metric>.py``, or for a
  metric split by cell (``<metric>.<suffix>``) with no file of its own, the
  shared ``bench/metrics/<metric>.py``;
- the limits of its correctness check, ``bench/limits/<cell>.json``.

One run is one process.  It refuses any device but a TPU kind named in
``bench/peaks.json``, keeps JAX's compile cache at a fixed path inside the
checkout, makes its data on the device from ``--seed``, warms up the
cell's programs, measures for ``--seconds``, checks every answer the window
produced against the plain reference (``bench/reference.py``), and prints
one JSON line last.  With ``--trace 1`` the window is traced and the line
holds the per-layer metrics; with ``--trace 0`` the end-to-end ones.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache" / "bench"
WINDOW_SPAN = "bench.window"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """Everything the named cell is made of, read from its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    here = root / "bench"

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell,
        "config": json.loads((here / "configs" / f"{cell['config']}.json").read_text()),
        "traffic": json.loads((here / "traffic" / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((here / "limits" / f"{workload}.json").read_text())["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
        "bench_dir": here,
        "metrics_dir": here / "metrics",
    }


def module(directory: Path, name: str):
    """The Python file ``<directory>/<name>.py``, imported."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{directory.name}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metrics_dir: Path, name: str):
    """The ``read(run)`` function of metric ``name``: its own file, or the
    shared one of the quantity it splits by cell."""
    if not (metrics_dir / f"{name}.py").is_file() and "." in name:
        name = name.rsplit(".", 1)[0]
    return module(metrics_dir, name).read


def find_chip(chips: int):
    """The devices and their peaks; exits non-zero, with no result, on
    anything but a TPU kind in ``peaks.json`` or on too few chips."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; found {dev.platform} {dev.device_kind!r}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["kinds"]
    if dev.device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind {dev.device_kind!r} in peaks.json")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; found {len(devices)}")
    return devices, peaks[dev.device_kind]


def enable_compile_cache() -> None:
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)  # JAX writes no entry into a missing directory
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the programs lowered (compiled or loaded from the cache)."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


class GcPauses:
    """The garbage collector's pauses until ``stop``: milliseconds, by the
    generation collected.  Logged only: the program's collections are part
    of what the window measures."""

    def __init__(self):
        self.ms: dict[int, list[float]] = {0: [], 1: [], 2: []}
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.ms[info["generation"]].append((time.perf_counter() - self._t0) * 1e3)

    def log(self) -> dict:
        return {
            f"gc_gen{g}_in_window": {"count": len(ms), "ms_max": max(ms, default=0.0), "ms_sum": sum(ms)}
            for g, ms in self.ms.items()
        }

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)


def check(loop, config: dict, limits: dict, failed: int) -> tuple[bool, dict]:
    """Every distinct answer the window produced, against the reference.
    Returns ``correct`` and each number beside its limit."""
    import numpy as np

    import reference

    distinct: dict = {}
    for key, order, gains in loop.answers:
        distinct.setdefault(key, {})[(np.asarray(order).tobytes(), np.asarray(gains).tobytes())] = (
            order,
            gains,
        )
    readings = []
    for key, answers in distinct.items():
        x = loop.inputs(key)
        for order, gains in answers.values():
            readings.append(reference.compare(x, config["metric"], order, gains, config["budget"]))
    numbers = reference.worst(readings)
    numbers["missing"] = failed if readings else max(1, failed)
    checks = {
        name: {"value": numbers.get(name, float("nan")), "limit": limit}
        for name, limit in limits.items()
    }
    print(json.dumps({"log": {"not_compared": {k: v for k, v in numbers.items() if k not in limits}}}), flush=True)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def run_cell(
    parts: dict,
    seed: int,
    seconds: float,
    trace: bool,
    peaks: dict | None,
    started: float,
) -> dict:
    """One run of a cell: set-up, window, check.  Returns the result line."""
    import jax

    import trace_reduce

    config, traffic = parts["config"], parts["traffic"]
    compiles = CompileCounter()
    generator = module(parts["bench_dir"] / "traffic", traffic["generator"])
    build = module(parts["bench_dir"] / "builders", config["build"])
    loop = generator.start(config, traffic, seed, seconds, build)
    pauses = GcPauses()
    profile_dir = None
    if trace:
        profile_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir.name, profiler_options=opts)
    setup_s = time.perf_counter() - started
    before = compiles.count
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        w_start, w_end = loop.window(seconds)
    in_window = compiles.count - before
    pauses.stop()
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        path = trace_reduce.find(profile_dir.name)
        reduced = trace_reduce.reduce_file(path, WINDOW_SPAN, loop.spans) if path else None
        profile_dir.cleanup()
    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    record = loop.record()
    log = dict(
        record["log"],
        compiles_in_window=in_window,
        **pauses.log(),
        memory_peak_bytes=peak,
        setup_s=setup_s,
    )
    print(json.dumps({"log": log}), flush=True)
    run = dict(
        record,
        config=config,
        traffic=traffic,
        peaks=peaks or {},
        setup_s=setup_s,
        window_s=w_end - w_start,
        trace=reduced,
    )
    wanted = parts["per_layer"] if trace else parts["end_to_end"]
    metrics = {}
    for m in wanted:
        value = reader(parts["metrics_dir"], m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    loop.release()
    t_check = time.perf_counter()
    correct, checks = check(loop, config, parts["limits"], record["failed"])
    print(json.dumps({"log": {"check_s": time.perf_counter() - t_check}}), flush=True)
    dev = devices[0]
    result = {
        "correct": correct,
        "attempted": record["attempted"],
        "failed": record["failed"],
        "metrics": metrics,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(reduced["ops"]),
            "idle_gaps": trace_reduce.top(reduced["idle_by_span"]),
        }
    result["checks"] = checks
    return result


def main(argv=None, root: Path = ROOT, chip: bool = True) -> dict:
    """The command.  ``root`` is the checkout whose ``BENCHMARK.json`` and
    ``bench/`` files name the cell; ``chip=False`` skips the look for a
    chip and the compile cache, so that tests can drive a run on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    parts = load_cell(args.workload, root)
    peaks = None
    if chip:
        _, peaks = find_chip(parts["cell"]["chips"])
        enable_compile_cache()
    result = run_cell(parts, args.seed, args.seconds, bool(args.trace), peaks, PROCESS_START)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    main()

"""The pool deployment's served path held to the benchmark's plain
reference on the CPU.

Seeded post-ReLU embeddings at the cell's width (512), as the chip cell
``cifar10-pool-mf.naive`` makes them but for a pool of 800, are served as
``FacilityLocationMF.from_features`` through ``SelectionServer.select``,
and the answer is replayed under ``bench/reference.py`` and held to the
cell's own limits (``bench/limits/cifar10-pool-mf.naive.json``), on the
fused Pallas sweep (interpret mode here) and on the XLA ``FeatureSource``
sweep.  The harness's generator and reference are imported by path."""
import importlib.util
import json
from pathlib import Path

import pytest

from repro.core import FacilityLocationMF, SelectionSpec
from repro.launch.serve import SelectionServer

BENCH = Path(__file__).resolve().parents[1] / "bench"
CONFIG = "cifar10-pool-mf"
CELL = f"{CONFIG}.naive"
N = 800
BUDGET = 10
SEED = 2**32 + 29


def _bench_module(name: str):
    spec = importlib.util.spec_from_file_location(f"bench_{name}", BENCH / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("use_kernel, backend", [(True, "pallas-flmf"), (False, "xla")])
def test_served_pool_selection_holds_to_the_reference(use_kernel, backend):
    config = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    limits = json.loads((BENCH / "limits" / f"{CELL}.json").read_text())["limits"]
    data, reference = _bench_module("data"), _bench_module("reference")
    (x,) = data.for_config(dict(config, n=N), SEED)
    assert x.shape == (N, 512) and float(x.min()) == 0.0
    fn = FacilityLocationMF.from_features(x, metric=config["metric"], use_kernel=use_kernel)
    server = SelectionServer(max_wave=config["server"]["max_wave"])
    (response,) = server.select([SelectionSpec(fn, BUDGET, "NaiveGreedy")])
    assert response.backend == backend
    assert response.n_bucket == 896  # seven lane widths: the n-bucket ladder
    r = response.result
    reading = reference.compare(x, config["metric"], r.order, r.gains, BUDGET)
    reading["missing"] = 0  # the one request was answered
    assert set(reading) >= set(limits)
    failed = {k: (reading[k], limit) for k, limit in limits.items() if not reading[k] <= limit}
    assert not failed, reading

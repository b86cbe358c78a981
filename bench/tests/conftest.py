"""The benchmark's own tests, run on the CPU:

    python -m pytest bench/tests

They import the harness's modules (``bench/``) and the program (``src/``)
by path, and build small checkouts in temporary directories."""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

# small stand-ins for the committed configurations, for the CPU
SMALL = {"craig-cifar10": {"groups": 2, "n": 96, "budget": 8, "server": {"max_wave": 4}}}


def small_checkout(dest: Path) -> Path:
    """A checkout holding ``BENCHMARK.json`` and ``bench/``, with every
    configuration shrunk to a size the CPU runs in seconds."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name, small in SMALL.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()), **small}))
    return dest


@pytest.fixture
def checkout(tmp_path):
    return small_checkout(tmp_path)

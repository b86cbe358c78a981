"""``chip_smoke.py`` off the chip: it refuses to run, and its plain
reference and comparison agree with ``solve()`` at a tiny size."""
import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common import compile_cache_dir, enable_compile_cache
from repro.core import (
    FacilityLocation,
    FacilityLocationMF,
    SelectionSpec,
    create_kernel,
    solve,
)

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    """The script as a module (importing it touches no device)."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _run(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script)],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )


def test_refuses_the_cpu():
    out = _run(SCRIPT, ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "platform=cpu" in out.stdout  # it named the device it refused


def test_fails_outside_the_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    out = _run(lone, tmp_path)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.mark.parametrize("use_kernel", [False, True])
def test_reference_agrees_with_solve_dense(smoke, use_kernel):
    x = smoke.embeddings(0, 1, 64, 16)[0]
    fn = FacilityLocation.from_kernel(
        create_kernel(x, metric="euclidean"), use_kernel=use_kernel
    )
    res = solve(SelectionSpec(fn, 8))
    m = smoke.compare(smoke.reference_trace(x, res.order, 32), res.order, res.gains)
    assert m.ok, m
    assert m.clear_mismatches == 0 and m.steps == 8
    # the free-running reference greedy picks the same items
    picks, gains = smoke.reference_greedy(x, 8, 32)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(res.order))
    np.testing.assert_allclose(np.asarray(gains), np.asarray(res.gains), rtol=1e-5)


def test_reference_agrees_with_solve_matrix_free(smoke):
    pool = smoke.embeddings(1, 2, 64, 16).reshape(128, 16)
    fn = FacilityLocationMF.from_features(pool, metric="euclidean", use_kernel=True)
    res = solve(SelectionSpec(fn, 6, "LazyGreedy"))
    m = smoke.compare(smoke.reference_trace(pool, res.order, 64), res.order, res.gains)
    assert m.ok, m


def test_bounds_reject_a_bf16_similarity(smoke):
    """Greedy over bf16-rounded inputs (one default-precision MXU pass)
    must fail the reference bounds; the float32 greedy must pass them."""
    x = smoke.embeddings(1, 1, 128, 32)[0]
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    for data, ok in ((rounded, False), (x, True)):
        picks, gains = smoke.reference_greedy(data, 16, 64)
        m = smoke.compare(smoke.reference_trace(x, picks, 64), picks, gains)
        assert m.ok is ok, m


def test_compile_cache_dir_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads it itself


def test_compile_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache_dir()
    assert first == compile_cache_dir() == str(ROOT / ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_phases_run_at_a_tiny_size(smoke, capsys):
    """The dense waves (with every served/sequential/XLA == Pallas check)
    and the matrix-free pool pass at a tiny size; the times are reported
    before the checks."""
    sizes = smoke.Sizes(
        classes=2, per_class=64, dim=16, budget=6, pool_budget=4,
        block=32, pool_block=64,
    )
    xs = smoke.embeddings(2, sizes.classes, sizes.per_class, sizes.dim)
    smoke.dense_phase(xs, sizes)
    smoke.pool_phase(xs.reshape(-1, sizes.dim), sizes)
    phases = [
        line.split('"phase": "')[1].split('"')[0]
        for line in capsys.readouterr().out.splitlines()
        if line.startswith('{"phase"')
    ]
    assert phases.index("dense/naive-xla/times") < phases.index("dense/naive-xla")
    assert phases[-2:] == ["dense/server", "pool/lazy-flmf"]

"""Documentation and examples can't rot: run them.

- tools/check_docs.py executes every ```python block in README.md and
  docs/*.md (the `make docs-check` gate) — run here so the fast tier fails
  when a documented API drifts.
- every examples/*.py runs end-to-end as a subprocess smoke check
  (examples assert their own correctness claims internally).
"""
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

_ENV = {
    "PYTHONPATH": "src",
    "PATH": "/usr/bin:/bin:/usr/local/bin",
    "JAX_PLATFORMS": "cpu",
}


def _run(cmd, timeout):
    return subprocess.run(
        cmd, capture_output=True, text=True, timeout=timeout, env=_ENV, cwd=ROOT
    )


def test_docs_check():
    """`make docs-check` equivalent: all documented code blocks execute."""
    r = _run([sys.executable, "tools/check_docs.py"], timeout=500)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 failed" in r.stdout, r.stdout


_EXAMPLES = sorted(p.name for p in (ROOT / "examples").glob("*.py"))


def test_examples_are_all_covered():
    """Every example file has a smoke check below."""
    assert set(_EXAMPLES) == set(_FAST_EXAMPLES)


_FAST_EXAMPLES = [
    "quickstart.py",
    "coreset_training.py",
    "targeted_selection.py",
    "guided_summarization.py",
    "serving.py",
]


@pytest.mark.parametrize("example", _FAST_EXAMPLES)
def test_example_runs(example):
    r = _run([sys.executable, f"examples/{example}"], timeout=300)
    assert r.returncode == 0, f"{example} failed:\n{r.stdout}\n{r.stderr}"


def test_serving_example_on_mesh():
    """examples/serving.py --mesh 2x2 on 4 forced host devices."""
    env = dict(_ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run(
        [sys.executable, "examples/serving.py", "--mesh", "2x2"],
        capture_output=True,
        text=True,
        timeout=300,
        env=env,
        cwd=ROOT,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "bit-identical" in r.stdout

#!/usr/bin/env python3
"""Record the small TPU trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace.py <out.xplane.pb>

On the chip: three rounds of a named similarity program, a gain program
and a 5 ms host sleep, each in the benchmark's own span, inside a
``bench.window`` span.  Prints the reduction and the planes' layout.
"""
from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import trace_reduce  # noqa: E402


def bench_similarity(x):
    return jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGHEST)


def gains(s):
    return jnp.sum(jnp.maximum(s - 0.5, 0.0), axis=0)


def main(out: str) -> None:
    sim, sweep = jax.jit(bench_similarity), jax.jit(gains)
    x = jax.random.normal(jax.random.key(0), (2048, 512))
    jax.block_until_ready(sweep(sim(x)))
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("epoch.similarity"):
                    s = jax.block_until_ready(sim(x))
                with jax.profiler.TraceAnnotation("epoch.select"):
                    jax.block_until_ready(sweep(s))
                with jax.profiler.TraceAnnotation("rerank.wait"):
                    time.sleep(0.005)
        jax.profiler.stop_trace()
        path = trace_reduce.find(d)
        shutil.copy(path, out)
    profile = jax.profiler.ProfileData.from_file(out)
    for plane in profile.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
    spans = ("epoch.similarity", "epoch.select", "rerank.wait")
    print(json.dumps(trace_reduce.reduce(profile, "bench.window", spans), indent=1))


if __name__ == "__main__":
    main(sys.argv[1])

"""Generator ``epochs``: a job that runs epoch after epoch, in a closed loop.

Each epoch builds the selection function of every group of the
configuration (the configuration's builder, one call for all groups) and
serves one request per group as one wave through
``SelectionServer.select``.  Set-up makes ``distinct_epochs`` epochs of
embeddings from the seed and runs one epoch to warm up every program; the
window cycles the epochs, whole ones back to back, until its time has
passed.  The answers are kept for the reference to check.

Mix parameters: ``optimizer`` (a registered optimizer's name) and
``distinct_epochs``.
"""
from __future__ import annotations

import time

import jax

import data

SPANS = ("epoch.similarity", "epoch.select")


def inputs(config: dict, traffic: dict, seed: int) -> list[tuple]:
    """(key, embeddings) of every group that a run with ``seed`` serves."""
    out = []
    for slot in range(traffic["distinct_epochs"]):
        xs = data.for_config(config, seed, slot)
        out += [((slot, g), xs[g]) for g in range(config["groups"])]
    return out


class Epochs:
    spans = SPANS

    def __init__(self, config: dict, traffic: dict, seed: int, build):
        from repro.launch.serve import SelectionServer

        self.config = config
        self.optimizer = traffic["optimizer"]
        self.xs = [data.for_config(config, seed, slot) for slot in range(traffic["distinct_epochs"])]
        jax.block_until_ready(self.xs)
        self.build = build
        self.program = build.program(config)
        self.server = SelectionServer(max_wave=config["server"]["max_wave"])
        self.epochs: list[dict] = []
        self.answers: list[tuple] = []  # (key, order, gains) per selection
        self._epoch(0)  # warm-up: every program an epoch runs

    def _epoch(self, i: int) -> dict:
        slot = i % len(self.xs)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("epoch.similarity"):
            built = jax.block_until_ready(self.program(self.xs[slot]))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("epoch.select"):
            specs = [self.build.spec(self.config, self.optimizer, item) for item in built]
            responses = self.server.select(specs)
        t2 = time.perf_counter()
        return {
            "start": t0,
            "end": t2,
            "similarity_s": t1 - t0,
            "select_s": t2 - t1,
            "slot": slot,
            "responses": responses,
        }

    def window(self, seconds: float) -> tuple[float, float]:
        """Whole epochs back to back until ``seconds`` have passed.  Returns
        the window's start and end: the end of its last epoch."""
        start = time.perf_counter()
        i = 0
        while time.perf_counter() - start < seconds:
            self.epochs.append(self._epoch(i))
            i += 1
        for e in self.epochs:
            responses = e.pop("responses")
            for g, r in enumerate(responses):
                self.answers.append(((e["slot"], g), r.result.order, r.result.gains))
            e["selections"] = len(responses)
            e["rows"] = self.config["n"]
            e["n_evals"] = [int(r.result.n_evals) for r in responses]
            e["backends"] = sorted({r.backend for r in responses})
        return start, self.epochs[-1]["end"]

    def record(self) -> dict:
        return {
            "epochs": self.epochs,
            "attempted": sum(e["selections"] for e in self.epochs),
            "failed": 0,
            "log": {
                "epochs": len(self.epochs),
                "backends": sorted({b for e in self.epochs for b in e["backends"]}),
            },
        }

    def inputs(self, key) -> jax.Array:
        slot, g = key
        return self.xs[slot][g]

    def release(self) -> None:
        self.server = None


def start(config: dict, traffic: dict, seed: int, seconds: float, build) -> Epochs:
    """Set up the loop, warmed up and ready for its window."""
    return Epochs(config, traffic, seed, build)

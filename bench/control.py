#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check.

    python bench/control.py --workload <name> --seeds s1,...,s12 \\
        --control-seeds c1,c2,c3 --seconds <s> [--out readings.json]

Two kinds of reading, in one process:

- sound: full runs of the cell (set-up, window, check) on each of
  ``--seeds``; each prints its result line, and its checked numbers are the
  program's readings.  The lower reading of a number is their largest.
- control: the reference put in the program's place, on the inputs each of
  ``--control-seeds`` makes, computed in each lower precision of
  ``reference.PRECISIONS`` (``high``: three bf16 passes for the similarity
  at HIGHEST; ``bfloat16``: the kernel stored in bf16) and held to the
  reference at ``highest``, as a run holds the program.  The upper reading
  of a number is the smallest a control gives.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

import reference
import run


def control_readings(parts: dict, seed: int, precision: str) -> dict:
    """The reference computed in ``precision``, held to it at highest, on
    every group that a run of the cell with ``seed`` serves."""
    config, traffic = parts["config"], parts["traffic"]
    generator = run.module(parts["bench_dir"] / "traffic", traffic["generator"])
    readings = []
    for _, x in generator.inputs(config, traffic, seed):
        picks, gains = jax.device_get(reference.greedy(x, config["metric"], config["budget"], precision))
        readings.append(reference.compare(x, config["metric"], picks, gains, config["budget"]))
    return reference.worst(readings)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    parts = run.load_cell(args.workload)
    _, peaks = run.find_chip(parts["cell"]["chips"])
    run.enable_compile_cache()
    out = {"workload": args.workload, "sound": {}, "control": {}}
    for seed in (int(s) for s in args.seeds.split(",") if s):
        result = run.run_cell(parts, seed, args.seconds, False, peaks, time.perf_counter())
        print(json.dumps(result), flush=True)
        out["sound"][seed] = {k: c["value"] for k, c in result["checks"].items()}
        out["sound"][seed]["correct"] = result["correct"]
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        for precision in reference.PRECISIONS[1:]:
            r = control_readings(parts, seed, precision)
            out["control"].setdefault(precision, {})[seed] = r
            print(json.dumps({"control": precision, "seed": seed, **r}), flush=True)
    names = ("shortfall", "gain_err", "gain_err_f", "objective_rel", "invalid")
    summary = {"lower": {}, "upper": {}}
    for k in names:
        if out["sound"]:
            summary["lower"][k] = max(r.get(k, float("nan")) for r in out["sound"].values())
        for precision, by_seed in out["control"].items():
            summary["upper"].setdefault(precision, {})[k] = min(r[k] for r in by_seed.values())
    out["summary"] = summary
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    sys.path[:0] = [str(run.BENCH), str(run.ROOT / "src")]
    main()

"""Benchmark runner — one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Emits ``name,us_per_call,derived`` CSV lines at the end for machine
consumption.
"""
from __future__ import annotations

import time


def main() -> None:
    csv = []

    from benchmarks import (
        batched_bench,
        mi_bench,
        modeling_bench,
        optimizers_bench,
        timing_bench,
    )

    t0 = time.perf_counter()
    opt_rows = optimizers_bench.main()
    csv.append(("optimizers_bench(table2)", (time.perf_counter() - t0) * 1e6,
                f"best_obj={max(r['objective'] for r in opt_rows):.2f}"))
    for r in opt_rows:
        csv.append(
            (f"opt/{r['optimizer'].split('(')[0]}", r["ms_per_run"] * 1e3,
             f"evals={r['gain_evals']}")
        )

    t0 = time.perf_counter()
    bat_rows = batched_bench.main()
    eng_rows = [r for r in bat_rows if "engine_speedup" in r]
    csv.append(("batched_bench(engine)", (time.perf_counter() - t0) * 1e6,
                f"best_speedup={max(r['engine_speedup'] for r in eng_rows):.2f}x"))
    for r in eng_rows:
        csv.append(
            (f"batched/B={r['B']},n={r['n']}", r["engine_ms"] * 1e3,
             f"qps={r['engine_qps']:.0f};speedup={r['engine_speedup']:.2f}x")
        )
    for r in bat_rows:
        if r.get("section") == "naive_vs_lazy":
            csv.append(
                (f"batched_lazy/B={r['B']},n={r['n']},{r['gains']}",
                 r["lazy_ms"] * 1e3,
                 f"speedup={r['lazy_speedup']:.2f}x;evals={r['lazy_evals']}")
            )

    t0 = time.perf_counter()
    tim_rows = timing_bench.main()
    csv.append(("timing_bench(table5)", (time.perf_counter() - t0) * 1e6,
                f"n_max={tim_rows[-1]['n']}"))
    for r in tim_rows:
        csv.append((f"timing/n={r['n']}", r["total_s"] * 1e6,
                    f"kernel_share={r['kernel_share']:.2f}"))

    t0 = time.perf_counter()
    modeling_bench.main()
    csv.append(("modeling_bench(fig5)", (time.perf_counter() - t0) * 1e6, "claims_ok"))

    t0 = time.perf_counter()
    mi_bench.main()
    csv.append(("mi_bench(fig7-8-10)", (time.perf_counter() - t0) * 1e6, "claims_ok"))

    print("\nname,us_per_call,derived")
    for name, us, derived in csv:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()

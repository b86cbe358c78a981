"""The matrix-free FL gain sweep's share of the chip's matmul peak.

Work: every evaluated candidate's similarity column, computed once per
evaluation from the features: a (rows, dim) by (dim,) product, rows x dim
x 2 flops, so the sum of ``n_evals`` x rows x ``dim`` x 2 over the window's
selections (the distance epilogue and the column sum, O(rows) a column,
are left out).  Time: the device time of every program in the window but
the builder's ``bench_features``.  Share: work at the chip's bf16 peak
(``bf16_flops_per_s``) over that time.

The sweep multiplies float32 at ``Precision.HIGHEST``, which the MXU runs
as several bf16 passes (six in XLA's lowering), so a float32 sweep reads
at most about a sixth of this peak even with the MXU saturated.  The bf16
peak is the chip's hard ceiling, which no implementation can pass, and
the share reads the same live work whatever implements the sweep; a
change of the work (a lazy screen, fewer steps) needs a benchmark PR that
recounts."""

FEATURES_PROGRAM = "bench_features"  # builders/features.py's PROGRAM


def read(run: dict) -> float | None:
    trace, epochs = run.get("trace"), run.get("epochs")
    peak = (run.get("peaks") or {}).get("bf16_flops_per_s")
    if not trace or not epochs or not peak:
        return None
    seconds = sum(s for m, s in trace["modules"].items() if FEATURES_PROGRAM not in m)
    if seconds <= 0:
        return None
    dim = run["config"]["dim"]
    work = sum(sum(e["n_evals"]) * e["rows"] * dim * 2 for e in epochs)
    return 100.0 * work / peak / seconds

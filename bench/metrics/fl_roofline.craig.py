"""The gain sweep's share of its memory roofline.

Bytes: every evaluated candidate's column of the kernel, read once per
evaluation: sum of ``n_evals`` x rows x 4 bytes over the window's
selections.  Time: the device time of every operation in the window
outside the benchmark's similarity program.  Share: bytes at the chip's
HBM bandwidth over that time.  It reads the same work whatever implements
the sweep."""

SIMILARITY_PROGRAM = "bench_similarity"


def read(run: dict) -> float | None:
    trace, epochs = run.get("trace"), run.get("epochs")
    bandwidth = run["peaks"].get("hbm_bytes_per_s")
    if not trace or not epochs or not bandwidth:
        return None
    seconds = sum(s for m, s in trace["modules"].items() if SIMILARITY_PROGRAM not in m)
    if seconds <= 0:
        return None
    nbytes = sum(sum(e["n_evals"]) * e["rows"] * 4 for e in epochs)
    return 100.0 * nbytes / bandwidth / seconds

"""The FL gain kernel's own share of its memory roofline.

Bytes: as ``fl_roofline.craig``, every evaluated candidate's column of the
kernel read once per evaluation, sum of ``n_evals`` x rows x 4 bytes over
the window's selections.  Time: the self time of the device operations
whose instruction name is the program's fixed kernel name ``fl_gains``
(``fl_gains`` or ``fl_gains.<n>``), without the pads and copies around
it.  Share: bytes at the chip's HBM bandwidth over that time.  A trace
with no such operation reads nothing."""

KERNEL = "fl_gains"


def instruction(op: str) -> str:
    """The instruction name of a trace's operation, ``.<n>`` dropped:
    ``%fl_gains.11 = f32[...] custom-call(...)`` -> ``fl_gains``."""
    name = op.lstrip("%").split(" ", 1)[0]
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def read(run: dict) -> float | None:
    trace, epochs = run.get("trace"), run.get("epochs")
    bandwidth = (run.get("peaks") or {}).get("hbm_bytes_per_s")
    if not trace or not epochs or not bandwidth:
        return None
    seconds = sum(s for op, s in trace["ops"].items() if instruction(op) == KERNEL)
    if seconds <= 0:
        return None
    nbytes = sum(sum(e["n_evals"]) * e["rows"] * 4 for e in epochs)
    return 100.0 * nbytes / bandwidth / seconds

"""The matrix-free FL gain kernel's own share of the chip's matmul peak.

Work: as ``flmf_roofline.pool``, the sum of ``n_evals`` x rows x ``dim``
x 2 flops over the window's selections: the live work, not the kernel's
padded rows and columns.  Time: the self time of the device operations
whose instruction name is the program's fixed kernel name ``flmf_gains``
(``flmf_gains`` or ``flmf_gains.<n>``), without the pads, copies and
curmax updates around it.  Share: work at the chip's bf16 peak
(``bf16_flops_per_s``) over that time; a float32 product at
``Precision.HIGHEST`` takes several bf16 passes (six in XLA's lowering),
so a sixth of the peak is about what a saturated float32 sweep reads.  A
trace with no such operation reads nothing."""

KERNEL = "flmf_gains"


def instruction(op: str) -> str:
    """The instruction name of a trace's operation, ``.<n>`` dropped:
    ``%flmf_gains.12 = f32[...] custom-call(...)`` -> ``flmf_gains``."""
    name = op.lstrip("%").split(" ", 1)[0]
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def read(run: dict) -> float | None:
    trace, epochs = run.get("trace"), run.get("epochs")
    peak = (run.get("peaks") or {}).get("bf16_flops_per_s")
    if not trace or not epochs or not peak:
        return None
    seconds = sum(s for op, s in trace["ops"].items() if instruction(op) == KERNEL)
    if seconds <= 0:
        return None
    dim = run["config"]["dim"]
    work = sum(sum(e["n_evals"]) * e["rows"] * dim * 2 for e in epochs)
    return 100.0 * work / peak / seconds

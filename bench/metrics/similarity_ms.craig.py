"""Host time per epoch to build every class kernel (the benchmark's jitted
``create_kernel`` calls, ending in ``block_until_ready``), mean over the
window's epochs."""


def read(run: dict) -> float | None:
    epochs = run.get("epochs")
    if not epochs:
        return None
    return sum(e["similarity_s"] for e in epochs) / len(epochs) * 1e3

"""Selections completed in the window over the window's time.  The window
runs from its start to the end of its last whole epoch."""


def read(run: dict) -> float | None:
    epochs = run.get("epochs")
    if not epochs:
        return None
    return sum(e["selections"] for e in epochs) / run["window_s"]

"""Share of the bytes the coalescer stacks into the window's waves that
padding adds: 100 x (sum of ``bytes_stacked`` - sum of ``bytes_live``) /
sum of ``bytes_stacked``, over the ``serve.wave`` spans of the window.

Read from the program's span ring (``repro.launch.metrics.spans``), on the
clock of the epochs' stamps; the warm-up epoch, before the window, is left
out.  A program without the ring, or a run with no chip, reads nothing."""

WAVE = "serve.wave"


def read(run: dict) -> float | None:
    epochs = run.get("epochs")
    if not run.get("peaks") or not epochs:
        return None
    try:
        from repro.launch.metrics import spans
    except ImportError:
        return None
    lo, hi = epochs[0]["start"] * 1e9, epochs[-1]["end"] * 1e9
    waves = [s for s in spans() if s.name == WAVE and lo <= s.start_ns and s.end_ns <= hi]
    stacked = sum(s.attrs.get("bytes_stacked", 0) for s in waves)
    if stacked <= 0:
        return None
    live = sum(s.attrs.get("bytes_live", 0) for s in waves)
    return 100.0 * (stacked - live) / stacked

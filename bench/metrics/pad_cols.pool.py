"""Share of the candidate columns the window's waves sweep that padding
adds: 100 x (sum of ``cols_stacked`` - sum of ``cols_live``) / sum of
``cols_stacked``, over the ``serve.wave`` spans of the window.  A
matrix-free sweep computes every padded column as it does a live one, so
this is its share of wasted work, which the bytes the coalescer stacks
(``pad_waste``) understate: they pad one feature row a column.

Read from the program's span ring (``repro.launch.metrics.spans``), on the
clock of the epochs' stamps; the warm-up epoch, before the window, is left
out.  A program without the ring or without the column counters, or a run
with no chip, reads nothing."""

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
    stacked = sum(s.attrs.get("cols_stacked", 0) for s in waves)
    if stacked <= 0:
        return None
    live = sum(s.attrs.get("cols_live", 0) for s in waves)
    return 100.0 * (stacked - live) / stacked

"""Host time of a selection call in the server, the coalescer and the
engine that was not spent waiting for the device: each ``serve.select``
span of the window less the ``engine.fetch`` spans under it, mean in ms.

Read from the program's span ring (``repro.launch.metrics.spans``), on the
clock of the epochs' stamps; the warm-up epoch, before the window, is left
out.  A program without the ring, or a run with no chip, reads nothing."""

SELECT, FETCH = "serve.select", "engine.fetch"


def read(run: dict) -> float | None:
    epochs = run.get("epochs")
    if not run.get("peaks") or not epochs:
        return None
    try:
        from repro.launch.metrics import spans
    except ImportError:
        return None
    ring = spans()
    lo, hi = epochs[0]["start"] * 1e9, epochs[-1]["end"] * 1e9
    selects = {s.span_id: s for s in ring if s.name == SELECT and lo <= s.start_ns and s.end_ns <= hi}
    if not selects:
        return None
    parent = {s.span_id: s.parent_id for s in ring}
    waited = dict.fromkeys(selects, 0)
    for s in ring:
        if s.name != FETCH:
            continue
        p = s.parent_id
        while p and p not in selects:
            p = parent.get(p, 0)
        if p:
            waited[p] += s.end_ns - s.start_ns
    host_ns = sum(s.end_ns - s.start_ns - waited[i] for i, s in selects.items())
    return host_ns / len(selects) * 1e-6

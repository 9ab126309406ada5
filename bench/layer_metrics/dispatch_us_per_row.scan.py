"""Engine dispatch time per row of a group-by scan: the program's
``engine.dispatch`` spans (featurize, key upload, launch) inside the
window's latest queries that its tracer ring still holds whole
(``bench/host_spans.ring_window``; one query per entry of
``dispatched``), over the rows those queries aggregated.

Returns None where the run has nothing to read: no such span."""

from bench import host_spans


def read(ctx):
    spans, queries = host_spans.ring_window(len(ctx["dispatched"]), ("engine.dispatch",))
    rows = sum(n for _, n in ctx["dispatched"][len(ctx["dispatched"]) - queries:])
    if not spans or not rows:
        return None
    return 1e6 * sum(s.duration for s in spans) / rows

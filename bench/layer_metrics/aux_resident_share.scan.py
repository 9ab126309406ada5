"""Share of the keys probed in ``T_aux`` that the pool-resident sorted
view answered in a group-by scan, in percent: the ``resident`` arg of
the ``aux.get`` spans over their ``keys`` arg, over the window's latest
queries that the program's tracer ring still holds whole
(``bench/host_spans.ring_window``; one query per entry of
``dispatched``).

Returns None where the run has nothing to read: no ``aux.get`` span
with a ``resident`` arg."""

from bench import host_spans


def read(ctx):
    spans, _ = host_spans.ring_window(len(ctx["dispatched"]), ("aux.get",))
    spans = [s for s in spans if "resident" in s.args]
    keys = sum(s.args.get("keys", 0) for s in spans)
    if not keys:
        return None
    return 100.0 * sum(s.args["resident"] for s in spans) / keys

"""Keys the ``T_aux`` probe answers per partition visit in a group-by
scan: the keys probed over the partitions visited, as ``AuxTable.get``
counts them once per call on its ``aux.get`` span's args, over the
window's latest queries that the program's tracer ring still holds
whole (``bench/host_spans.ring_window``; one query per entry of
``dispatched``).

Returns None where the run has nothing to read: no such span."""

from bench import host_spans


def read(ctx):
    spans, _ = host_spans.ring_window(len(ctx["dispatched"]), ("aux.get",))
    visits = sum(s.args.get("visits", 0) for s in spans)
    if not visits:
        return None
    return sum(s.args.get("keys", 0) for s in spans) / visits

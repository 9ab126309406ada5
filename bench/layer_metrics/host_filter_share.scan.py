"""Share of a range scan's filtered rows whose predicate match the host
evaluated, in percent: the queries' ``ExplainStats.filter_host_rows``
(kernel match bits re-run on aux-corrected codes, or the host filter)
over the rows scanned in the window, every one of which the filter
takes.  100 means the in-kernel filter saved the host nothing.

Returns None where the run has nothing to read: no such count."""


def read(ctx):
    rows = ctx["spans"].get("scan.filter_host_rows")
    if rows is None or not ctx["work"]:
        return None
    return 100.0 * rows / ctx["work"]

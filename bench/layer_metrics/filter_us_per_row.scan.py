"""Predicate filter time per row of a range scan: the queries'
``ExplainStats.filter_s`` (the ``store.filter`` stage: the host's re-run
of kernel match bits on aux-corrected codes, or its own filter) over the
rows scanned in the window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("scan.filter_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

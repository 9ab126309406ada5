"""Host aux probe time per row of a group-by scan: the queries'
``ExplainStats.aux_s`` over the rows aggregated in the window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("scan.aux_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

"""Server merge time per key: the ``serve.merge`` spans (the step's
requests concatenated, then ``np.unique``), summed by the server as
``ServeStats.merge_s``, over the keys requested in the window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("serve.merge_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

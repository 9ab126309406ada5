"""Server scatter time per key: the ``serve.scatter`` spans (the
per-column concatenation and the gather back to each caller), summed by
the server as ``ServeStats.gather_s``, over the keys requested in the
window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("serve.gather_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

"""Engine time per key: ``ServeStats.infer_s`` (dispatch plus the
host's wait on the device) over the keys requested in the window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("serve.infer_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

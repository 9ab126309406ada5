"""Engine wait time per key: the ``engine.wait`` spans (the host
blocked on the device's outputs and their copy to the host), summed by
the server as ``ServeStats.wait_s``, over the keys requested in the
window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("serve.wait_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

"""Engine dispatch time per key: the ``engine.dispatch`` spans
(featurize, key upload, launch), summed by the server as
``ServeStats.dispatch_s``, over the keys requested in the window.

Returns None where the run has nothing to read."""


def read(ctx):
    seconds = ctx["spans"].get("serve.dispatch_s")
    if seconds is None or not ctx["work"]:
        return None
    return 1e6 * seconds / ctx["work"]

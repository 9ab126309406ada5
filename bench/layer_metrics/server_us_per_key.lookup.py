"""Server self time per key: ``ServeStats.total_s`` less its route,
infer, exist, aux, filter and decode seconds (what is left is the
merge, ``np.unique`` and the scatter back to the requests), over the
keys requested in the window.

Returns None where the run has nothing to read."""

STAGES = ("route_s", "infer_s", "exist_s", "aux_s", "filter_s", "decode_s")


def read(ctx):
    s = ctx["spans"]
    if "serve.total_s" not in s or not ctx["work"]:
        return None
    own = s["serve.total_s"] - sum(s["serve." + k] for k in STAGES)
    return 1e6 * own / ctx["work"]

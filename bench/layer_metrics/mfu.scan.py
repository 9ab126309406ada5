"""Whole-step share of the chip's peak: the operations of the model
over the keys it was handed in the window (``bench/flops.py``), over
the window's time and the bf16 peak.  The kernel computes in float32,
for which the v5e publishes no peak, so this understates float32
utilization.

Returns None where the run has nothing to read."""


def read(ctx):
    if ctx["values"].get("scan_rows_per_s") is None or not ctx["elapsed_s"] or ctx["peak"] is None:
        return None
    return 100.0 * ctx["model_ops"] / ctx["elapsed_s"] / ctx["peak"]["flops_bf16"]

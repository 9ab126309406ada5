"""Share of its roofline that the ``jit_fused_lookup_call`` program
reaches (the Pallas kernel and its existence gather): the least time that the keys handed to the model
need (``bench/flops.py``, larger of operations over the bf16 peak and
bytes over HBM bandwidth) over the program's device time in the trace.

Returns None where the run has nothing to read: no launch of the
program in the traced window, or model calls in the window that took
another tier than ``fused_calls``, whose keys this program did not see."""

from bench import flops

MODULE = "jit_fused_lookup_call"
TIER = "fused_calls"


def read(ctx):
    trace, engine = ctx["trace"], ctx["engine"]
    seconds, calls = trace.modules.get(MODULE, (0.0, 0)) if trace else (0.0, 0)
    other = sum(v for k, v in engine.items() if k.endswith("_calls") and k != TIER)
    if not calls or not engine.get(TIER) or other or ctx["peak"] is None:
        return None
    least, _ = flops.roofline_seconds(ctx["model"], ctx["dispatched"], calls, ctx["peak"])
    return 100.0 * least / seconds

"""Kernels / XLA programs: the forward programs' (``jit_fwd``) share of
their roofline, in percent. The least time each forward needs on this
chip, from its shapes and the peaks (``bench/flops.py``: compute-bound at
the long prompts, memory-bound at the short ones), summed over the
window's forwards, over their device time in the trace. Nothing where
the trace is missing or its forward programs do not match the calls."""
from bench import flops


def read(result):
    ctx = result.context
    summary = ctx.get("trace")
    if summary is None or ctx.get("peak") is None:
        return None
    device = [p.seconds for p in summary.programs if p.label.endswith(".forward")]
    calls = ctx["calls"]
    if len(device) != len(calls) or not calls:
        return None
    stages, seq = ctx["config"]["stages"], ctx["mix"]["seq_len"]
    least = sum(flops.roofline_s(stages[c.stage], c.batch, seq, ctx["peak"])[0]
                for c in calls)
    return 100.0 * least / sum(device)

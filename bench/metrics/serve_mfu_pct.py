"""Model step (``models/``, ``nn/``): the FLOPs of every live forward in
the window, counted from shapes (``bench/flops.py``), over the window's
wall time times the chip's peak, in percent."""
from bench import flops


def read(result):
    ctx = result.context
    if not ctx.get("calls") or ctx.get("peak") is None:
        return None
    stages, seq = ctx["config"]["stages"], ctx["mix"]["seq_len"]
    work = sum(flops.forward_cost(stages[c.stage], c.batch, seq)[0] for c in ctx["calls"])
    return 100.0 * work / (ctx["window_s"] * ctx["peak"]["bf16_flops_per_s"])

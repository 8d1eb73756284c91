"""Model step (``core/policy.py``, ``core/features.py``): the FLOPs of
every policy-and-value network pass of the window's episodes (the
rollout's forwards, the update's forwards and backwards, the
behaviour-cloning batch's included), counted from shapes
(``bench/flops_policy.py``), over the window's wall time times the chip's
bf16 peak, in percent: the whole training step's share of the peak."""


def read(result):
    ctx = result.context
    if not ctx.get("episodes") or ctx.get("peak") is None:
        return None
    work = ctx["episodes"] * ctx["episode_flops"]["total"]
    return 100.0 * work / (ctx["window_s"] * ctx["peak"]["bf16_flops_per_s"])

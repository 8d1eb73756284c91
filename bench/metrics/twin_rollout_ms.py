"""Runtime twin (``core/runtime_vec.py``): device time of the rollout
program (``jit_vec_rollout``: the twin's event loop with the policy's
sampling, all environments of one episode) per episode of the traced
window, in milliseconds (``bench/trace.py``)."""

PROGRAM = "jit_vec_rollout"


def read(result):
    ctx = result.context
    summary = ctx.get("trace")
    if summary is None or not ctx.get("traced_episodes"):
        return None
    device = [p.seconds for p in summary.programs if p.name == PROGRAM]
    if not device:
        return None
    return 1e3 * sum(device) / ctx["traced_episodes"]

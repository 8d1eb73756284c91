"""PPO (``core/ppo.py``): device time of the minibatch update programs
(``jit_ppo_minibatch_update``: forward, backward, clipping and AdamW)
per episode of the traced part of the window, in milliseconds (``bench/trace.py``)."""

PROGRAM = "jit_ppo_minibatch_update"


def read(result):
    ctx = result.context
    summary = ctx.get("trace")
    if summary is None or not ctx.get("traced_episodes"):
        return None
    device = [p.seconds for p in summary.programs if p.name == PROGRAM]
    if not device:
        return None
    return 1e3 * sum(device) / ctx["traced_episodes"]

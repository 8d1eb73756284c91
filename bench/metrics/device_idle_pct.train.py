"""Device: the share of the traced window in which no operation ran on
the chip, in percent (``bench/trace.py``), in a training cell."""


def read(result):
    summary = result.context.get("trace")
    if summary is None or not result.context.get("traced_episodes"):
        return None
    return 100.0 * (1.0 - summary.busy_s / summary.window_s)

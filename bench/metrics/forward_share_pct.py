"""Stage-execution layer (``serving/engine.py``): the window's wall time
spent inside the stage executor calls (dispatch to the host read of the
output), in percent, from the harness's spans around each call."""


def read(result):
    calls = result.context.get("calls")
    if not calls:
        return None
    return 100.0 * sum(c.end - c.start for c in calls) / result.context["window_s"]

"""Runtime layer (``serving/runtime.py``, ``serving/batcher.py``): the mean
batch size of the stage calls in the window, as a share of the batch cap,
in percent. Counted at each stage executor call."""


def read(result):
    calls = result.context.get("calls")
    if not calls:
        return None
    return 100.0 * sum(c.batch for c in calls) / (len(calls) * result.context["cap"])

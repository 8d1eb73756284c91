"""Stage execution (``serving/engine.py``): the share of the traced
window in which the chip was idle and the host was inside
``stage.prepare`` (the stage's inputs made ready), in percent, from the
program's spans on the trace's clock (``bench/spans.py``). Nothing where
the trace holds no program span."""


def read(result):
    host = result.context.get("spans")
    if host is None:
        return None
    return 100.0 * host.prepare_s / host.window_s

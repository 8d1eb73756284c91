"""Runtime layer (``serving/runtime.py``, ``api/session.py``): the share
of the traced window in which the chip was idle and the host was in no
``stage.call`` span (event loop, batcher, controller, harness), in
percent, from the program's spans on the trace's clock
(``bench/spans.py``). Nothing where the trace holds no program span."""


def read(result):
    host = result.context.get("spans")
    if host is None:
        return None
    return 100.0 * host.runtime_s / host.window_s

"""Checkify sanitizer for the jitted twins (the dynamic half of reprolint).

Static rules (RPL001–RPL005) catch contract violations visible in source;
this module catches the ones only visible at run time — NaNs, division by
zero, out-of-bounds gathers — by wrapping the twin entry points
(``vecenv.rollout``/``vec_rollout``, ``runtime_vec.vec_rollout``/``replay``)
in ``jax.experimental.checkify``. Divergence bugs then surface as typed
``JaxRuntimeError``s at the offending op instead of silent reward drift.

Off by default (checkify adds error-state plumbing through every scan and
while_loop). Enable with either:

- the environment flag ``REPRO_CHECKIFY=1`` (also ``true``/``on``/``yes``),
  e.g. for a CI smoke episode; or
- programmatically: ``sanitize.enable()``, ``with sanitize.enabled_scope():``
  or ``Session(..., debug_checkify=True)``.

The programmatic override wins over the environment in both directions.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
from jax.experimental import checkify

# NaN production, 0/0, and out-of-bounds gather/scatter indices — the three
# ways a twin quietly stops matching its Python reference.
ERRORS = checkify.nan_checks | checkify.index_checks | checkify.div_checks

# For entry points where the OOB rule cannot be applied (see ``checked``).
NAN_DIV_ERRORS = checkify.nan_checks | checkify.div_checks

ENV_FLAG = "REPRO_CHECKIFY"

_OVERRIDE: bool | None = None


def enabled() -> bool:
    """Is the sanitizer active? Programmatic override first, then env."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    return os.environ.get(ENV_FLAG, "").strip().lower() in (
        "1", "true", "on", "yes")


def enable(on: bool | None = True) -> None:
    """Force the sanitizer on/off; ``enable(None)`` restores env control."""
    global _OVERRIDE
    _OVERRIDE = on


@contextlib.contextmanager
def enabled_scope(on: bool = True):
    """Temporarily force the sanitizer on (or off) for a block."""
    global _OVERRIDE
    prev = _OVERRIDE
    _OVERRIDE = on
    try:
        yield
    finally:
        _OVERRIDE = prev


def checked(fn=None, *, errors=None):
    """Wrap a twin entry point with a checkified twin-of-the-twin.

    When the sanitizer is off (the default) the wrapper is a passthrough —
    the original jitted ``fn`` runs untouched, so production speed is
    unaffected. When on, calls route through a cached
    ``jit(checkify(fn))`` instance and raise ``JaxRuntimeError`` on any
    NaN / div-by-zero / out-of-bounds index anywhere in the episode.

    ``errors`` narrows the check set for functions where part of the
    default instrumentation cannot be applied (checkify's OOB rule fails
    to transform the batched ``dynamic_update_slice`` in the runtime
    twin's vmapped event loop — those entry points keep
    NaN + div checks and note why inline).

    Works with the twins' calling convention: positional args are arrays,
    keyword args are jit-static (``n_steps``/``weights``/``greedy``/
    ``max_wait``) and become part of the cache key, closure-captured so
    they never flow through checkify's flattening. Nested calls (e.g.
    ``vec_rollout`` vmapping ``rollout``) short-circuit to the raw
    function — only the outermost entry pays for error plumbing.
    """
    if fn is None:
        return functools.partial(checked, errors=errors)
    error_set = ERRORS if errors is None else errors
    cache: dict = {}

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        if not enabled() or not jax.core.trace_ctx.is_top_level():
            return fn(*args, **kwargs)
        try:
            cache_key = tuple(sorted(kwargs.items()))
            run = cache.get(cache_key)
        except TypeError:               # unhashable static — don't cache
            cache_key = run = None
        if run is None:
            def call(*arrays):
                return fn(*arrays, **kwargs)

            run = jax.jit(checkify.checkify(call, errors=error_set))
            if cache_key is not None:
                cache[cache_key] = run
        err, out = run(*args)
        checkify.check_error(err)       # raises JaxRuntimeError if tripped
        return out

    wrapper.__wrapped__ = fn
    return wrapper

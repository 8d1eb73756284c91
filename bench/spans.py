"""Puts the device's idle time in the window down to what the host was
doing: the program's own spans (``jax.profiler.TraceAnnotation``, named
in ``PROGRAM_SPANS``) on the host plane of the trace that ``trace.reduce``
read, on the profiler's clock.

- The shift: the device's events are moved by the least shift that starts
  no execution of the forward program before the ``stage.dispatch`` span
  that ran it (``trace``'s own rule, paired with the program's dispatch
  spans, the k-th span with the k-th execution). ``trace.reduce`` fits
  the same rule to the harness's ``bench.stage*`` span, which opens before
  ``stage.prepare``; its shift falls short by the least time from that
  span's start to a forward's start (prepare and dispatch), and would put
  each forward's device time into the host's prepare span and leave as
  much idle time at the end of its wait. Where the spans cannot be
  paired, ``trace.reduce``'s shift is used.
- Device idle time: the window less the union of the ``XLA Ops``
  intervals of each device, moved and clipped to the window, averaged
  over the devices, as ``trace.reduce`` counts it; the split sums to
  ``device_idle_pct.serve`` but for device time that the two shifts put
  on different sides of the window's ends.
- ``idle_by_span``: that idle time under each innermost program span open
  on the host at the instant, ``(none)`` outside all of them. The
  harness's own spans (``bench.*``) are not program spans.
- The three-way split: ``prepare`` while the host is in ``stage.prepare``;
  ``call`` while it is in ``stage.call`` but not in ``stage.prepare``;
  ``runtime`` the rest.
- ``outside``: forward executions that, moved, start before their
  ``stage.dispatch`` span starts or end after their ``stage.wait`` span
  ends; a forward with no span to pair counts too. ``slack_s``: how much
  later the device could sit with no forward ending after its wait, the
  room within which the split between dispatch and wait is uncertain.
"""
from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

from bench import trace

PROGRAM_SPANS = ("session.serve", "controller.decide", "runtime.advance",
                 "stage.call", "stage.prepare", "stage.dispatch",
                 "stage.compile", "stage.wait")
NONE = "(none)"


@dataclass
class Split:
    window_s: float
    idle_s: float
    runtime_s: float
    prepare_s: float
    call_s: float
    idle_by_span: list[list]  # [span, seconds], largest first
    skew_s: float           # the shift the device's events were moved by
    slack_s: float
    forwards: int           # forward executions in the trace
    outside: int            # of them outside their dispatch-to-wait spans


def _bucket(active: tuple[str, ...]) -> str:
    if "stage.prepare" in active:
        return "prepare"
    return "call" if "stage.call" in active else "runtime"


def segments(spans: list[tuple[float, float, str]], w0: float, w1: float):
    """[w0, w1) cut where a span starts or ends: (start, end, names of the
    spans open there, outermost first)."""
    cuts = sorted({w0, w1, *(t for s, e, _ in spans for t in (s, e)
                             if w0 < t < w1)})
    order = sorted(spans, key=lambda x: (x[0], -x[1]))
    out, k, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:], strict=False):
        while k < len(order) and order[k][0] <= a:
            open_.append(order[k])
            k += 1
        open_ = [x for x in open_ if x[1] > a]
        out.append((a, b, tuple(n for _, _, n in open_)))
    return out


def _idle(busy: list[list[float]], w0: float, w1: float):
    cursor = w0
    for s, e in busy + [[w1, w1]]:
        if s > cursor:
            yield cursor, s
        cursor = max(cursor, e)


def reduce(path: str | Path, summary: trace.Summary | None, *,
           program: str | None = None,
           span: str = trace.WINDOW_SPAN) -> Split | None:
    """The split of ``summary``'s idle time, or None where there is no
    summary or the trace holds no program span in the window. ``program``
    names the forward program that each ``stage.dispatch`` runs once."""
    if summary is None:
        return None
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    window, _ = trace._host_spans(planes, span, trace.DISPATCH_PREFIX)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for p in planes if p.name.startswith("/host:")
             for ln in p.lines for ev in ln.events if ev.name in PROGRAM_SPANS]
    if window is None:
        return None
    w0, w1 = window
    if not any(s < w1 and e > w0 for s, e, _ in spans):
        return None
    devices = sorted((p for p in planes if p.name.startswith("/device:")
                      and any(ln.name == "XLA Ops" for ln in p.lines)),
                     key=lambda p: p.name)
    first = devices[0]
    runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                  for ev in trace._events(first, "XLA Modules")
                  if trace.program_name(ev.name) == program)
    dispatch = sorted(s for s, _, n in spans if n == "stage.dispatch")
    wait = sorted(e for _, e, n in spans if n == "stage.wait")
    paired = len(runs) == len(dispatch) == len(wait) > 0
    shift = (trace._skew(first, dispatch, program) if paired
             else summary.skew_s * 1e9)

    by_span: dict[str, float] = defaultdict(float)
    buckets: dict[str, float] = defaultdict(float)
    cut = segments(spans, w0, w1)
    for plane in devices:
        ops = ((ev.start_ns + shift, ev.start_ns + ev.duration_ns + shift)
               for ev in trace._events(plane, "XLA Ops"))
        busy = trace.merge([(max(s, w0), min(e, w1)) for s, e in ops
                            if e > w0 and s < w1])
        k = 0
        for s, e in _idle(busy, w0, w1):
            while cut[k][1] <= s:
                k += 1
            j = k
            while j < len(cut) and cut[j][0] < e:
                a, b, names = cut[j]
                t = (min(b, e) - max(a, s)) * 1e-9 / len(devices)
                by_span[names[-1] if names else NONE] += t
                buckets[_bucket(names)] += t
                j += 1

    outside = max(0, len(runs) - min(len(dispatch), len(wait)))
    outside += sum(s + shift < d or e + shift > w for (s, e), d, w
                   in zip(runs, dispatch, wait, strict=False))
    slack = min((w - e - shift for (_, e), w in zip(runs, wait, strict=False)),
                default=0.0)
    return Split(window_s=(w1 - w0) * 1e-9, idle_s=sum(buckets.values()),
                 runtime_s=buckets["runtime"], prepare_s=buckets["prepare"],
                 call_s=buckets["call"],
                 idle_by_span=trace._top(by_span, len(by_span)),
                 skew_s=shift * 1e-9, slack_s=slack * 1e-9,
                 forwards=len(runs), outside=outside)

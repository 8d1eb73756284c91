"""Reduces a profiler trace (an ``.xplane.pb``) to what the per-layer
metrics read, for the window the harness marked with a host span.

- The window: the host span named ``bench.window`` (a
  ``jax.profiler.TraceAnnotation`` around the measured window).
- Device busy time: the union of the intervals of the ``XLA Ops`` line of
  each device plane (``/device:...``), clipped to the window, averaged
  over the devices. Asynchronous copies (``Async XLA Ops``) overlap the
  ops and are not counted.
- Programs: the ``XLA Modules`` line of the first device, one event per
  execution of a compiled program, in order; the caller names them.
- Top operations: device time per (program name, HLO op name without its
  number), the ten largest. An op that another op starts inside (a
  ``while`` loop, whose body's ops follow it) is not counted twice.
- Idle gaps: the stretches of the window in which no op ran, each named by
  the programs on either side of it (what the host was doing between
  them), summed per name, the ten largest.

Device and host timestamps come from clocks that the profiler aligns to
about a millisecond on a TPU v5e host (device earlier). The reduction
moves the device's events by the least shift that starts no execution of
the caller's program before the host span that dispatched it (the k-th
span whose name starts with ``dispatch_prefix`` dispatched the k-th
execution), and then clips them to the window.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

WINDOW_SPAN = "bench.window"
DISPATCH_PREFIX = "bench.stage"


@dataclass
class Program:
    """One execution of a compiled program on the device (ns)."""
    name: str
    start: float
    end: float
    label: str = ""

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class Summary:
    window_s: float
    skew_s: float
    busy_s: float
    devices: int
    programs: list[Program]
    top_ops: list[list]
    idle_gaps: list[list]


def program_name(event_name: str) -> str:
    """``jit_fwd(6836809064968452356)`` -> ``jit_fwd``."""
    return event_name.split("(", 1)[0]


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[8]{0} fusion(...)`` -> ``fusion``."""
    head = hlo_text.split(" ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def _host_spans(planes, span: str, prefix: str):
    """The window span and the starts of the dispatching spans (ns)."""
    window, starts = None, []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == span:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(prefix):
                    starts.append(ev.start_ns)
    return window, sorted(starts)


def _skew(device, starts: list[float], program: str | None) -> float:
    """Least shift that puts each execution of ``program`` after the start
    of the host span that dispatched it; 0 where they cannot be paired."""
    runs = sorted(ev.start_ns for ev in _events(device, "XLA Modules")
                  if program_name(ev.name) == program)
    if not runs or len(runs) != len(starts):
        return 0.0
    return max(0.0, max(h - d for h, d in zip(starts, runs, strict=True)))


def _top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(path: str | Path, *, name_programs=None, program: str | None = None,
           span: str = WINDOW_SPAN,
           dispatch_prefix: str = DISPATCH_PREFIX) -> Summary | None:
    """The window's device activity, or None where the trace holds no
    window span or no device plane. ``name_programs(programs)`` returns a
    label for each program execution, in order; ``program`` names the
    program that each dispatching host span runs once."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(path)).planes)
    window, starts = _host_spans(planes, span, dispatch_prefix)
    devices = sorted((p for p in planes if p.name.startswith("/device:")
                      and any(ln.name == "XLA Ops" for ln in p.lines)),
                     key=lambda p: p.name)
    if window is None or not devices:
        return None
    w0, w1 = window
    shift = _skew(devices[0], starts, program)

    def clipped(plane, line_name):
        for ev in _events(plane, line_name):
            s, e = ev.start_ns + shift, ev.start_ns + ev.duration_ns + shift
            if e > w0 and s < w1:
                yield max(s, w0), min(e, w1), ev.name

    busy = []
    for plane in devices:
        busy.append(sum(e - s for s, e in merge([(s, e) for s, e, _ in
                                                 clipped(plane, "XLA Ops")])))
    first = devices[0]
    programs = [Program(program_name(n), s, e)
                for s, e, n in clipped(first, "XLA Modules")]
    programs.sort(key=lambda p: p.start)
    labels = (name_programs(programs) if name_programs
              else [p.name for p in programs])
    for p, label in zip(programs, labels, strict=True):
        p.label = label

    run_starts = [p.start for p in programs]

    def around(t: float) -> int:
        return bisect.bisect_right(run_starts, t) - 1

    op_totals: dict[str, float] = defaultdict(float)
    ops = sorted(clipped(first, "XLA Ops"))
    for k, (s, e, text) in enumerate(ops):
        if k + 1 < len(ops) and ops[k + 1][0] < e:
            continue            # a loop or call whose body's ops follow it
        i = around(s)
        where = programs[i].label if i >= 0 and s < programs[i].end else "outside programs"
        op_totals[f"{where}/{op_name(text)}"] += (e - s) * 1e-9

    gap_totals: dict[str, float] = defaultdict(float)
    cursor = w0
    for s, e in merge([(s, e) for s, e, _ in ops]) + [[w1, w1]]:
        if s > cursor:
            i = around(cursor)
            before = programs[i].label if i >= 0 else "window start"
            after = programs[i + 1].label if i + 1 < len(programs) else "window end"
            gap_totals[f"{before} -> {after}"] += (s - cursor) * 1e-9
        cursor = max(cursor, e)
    return Summary(window_s=(w1 - w0) * 1e-9, skew_s=shift * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9, devices=len(devices),
                   programs=programs, top_ops=_top(op_totals),
                   idle_gaps=_top(gap_totals))

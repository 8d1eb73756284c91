"""CI perf gate: fail when a fresh benchmark result regresses against a
fraction of the committed baseline.

    python -m benchmarks.gate CURRENT.json \\
        --baseline experiments/results/stage_calibration.json \\
        --metric cache.hit_rate_repeat --min-ratio 0.9 \\
        --metric fit_mre_mean --max-ratio 4.0

``--metric`` is a dotted path into the JSON payload; repeat it to gate
several metrics in one invocation (one comparison per pair, every failure
reported before exiting). ``--min-ratio`` gates higher-is-better metrics
(throughput): pass when current >= ratio * baseline. ``--max-ratio`` gates
lower-is-better metrics (calibration error, latency percentiles): pass
when current <= ratio * baseline. Thresholds pair positionally with the
metrics in command-line order; give exactly one threshold total to
broadcast it across all metrics. Null, NaN and zero metric values are hard
errors — each would otherwise make the ratio comparison silently
meaningless.
"""

import argparse
import json
import math
import sys

DEFAULT_METRIC = "vectorized.32.steps_per_s"


class _Ordered(argparse.Action):
    """Append (dest, value) to a shared event list so --min-ratio and
    --max-ratio keep their command-line order relative to the metrics."""

    def __call__(self, parser, namespace, values, option_string=None):
        events = getattr(namespace, "events", None)
        if events is None:
            events = []
            namespace.events = events
        events.append((self.dest, values))


def lookup(payload: dict, dotted: str) -> float:
    node = payload
    for part in dotted.split("."):
        node = node[part]
    if node is None:
        raise SystemExit(
            f"GATE ERROR: metric {dotted!r} is null (nothing was measured)"
        )
    value = float(node)
    if math.isnan(value):
        # a NaN silently loses every comparison — fail loudly instead of
        # letting `ratio >= min_ratio` pass or fail by accident
        raise SystemExit(f"GATE ERROR: metric {dotted!r} is NaN")
    if value == 0.0:
        # a zero baseline makes every candidate pass (ratio = inf) and a
        # zero candidate can only mean nothing ran — both are measurement
        # bugs, not regressions; refuse to compare
        raise SystemExit(f"GATE ERROR: metric {dotted!r} is zero")
    return value


def pair_events(events) -> list[tuple[str, str, float]]:
    """-> [(metric, kind, threshold)] with kind in {"min", "max"}.

    The i-th threshold event (of either kind) pairs with the i-th metric;
    a single threshold broadcasts across all metrics; no thresholds means
    --min-ratio 0.5 on everything (the historical default).
    """
    metrics = [v for d, v in events if d == "metric"] or [DEFAULT_METRIC]
    thresholds = [(("min" if d == "min_ratio" else "max"), v)
                  for d, v in events if d in ("min_ratio", "max_ratio")]
    if not thresholds:
        thresholds = [("min", 0.5)]
    if len(thresholds) == 1:
        thresholds = thresholds * len(metrics)
    if len(thresholds) != len(metrics):
        raise SystemExit(
            f"GATE ERROR: {len(metrics)} --metric but {len(thresholds)} "
            f"--min-ratio/--max-ratio (give one per metric, or one total)"
        )
    return [(m, k, v) for m, (k, v) in zip(metrics, thresholds, strict=True)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("current", help="fresh result JSON (e.g. from --out DIR)")
    ap.add_argument("--baseline", required=True, help="committed baseline JSON")
    ap.add_argument(
        "--metric",
        action=_Ordered,
        help="dotted metric path (repeatable)",
    )
    ap.add_argument(
        "--min-ratio",
        action=_Ordered,
        type=float,
        help="higher-is-better threshold: fail when current < ratio * "
        "baseline; one per --metric, or a single value broadcast across "
        "all metrics (default 0.5)",
    )
    ap.add_argument(
        "--max-ratio",
        action=_Ordered,
        type=float,
        help="lower-is-better threshold: fail when current > ratio * "
        "baseline; pairs with --metric like --min-ratio",
    )
    args = ap.parse_args(argv)
    comparisons = pair_events(getattr(args, "events", None) or [])

    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)

    failed = 0
    for metric, kind, threshold in comparisons:
        cur = lookup(current, metric)
        base = lookup(baseline, metric)
        ratio = cur / base
        ok = ratio >= threshold if kind == "min" else ratio <= threshold
        failed += 0 if ok else 1
        status = "OK" if ok else "REGRESSION"
        print(
            f"{status}: {metric} current={cur:.4g} baseline={base:.4g} "
            f"ratio={ratio:.2f} vs {kind}-ratio={threshold}"
        )
    return 0 if failed == 0 else 1


if __name__ == "__main__":
    sys.exit(main())

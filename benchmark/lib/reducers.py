"""The fixed set of reducers a per-layer metric file may name.

A metric is `benchmark/layers/<name>.json`: name, layer, unit, better,
source, moves, and `reducer` + `args` from the table below.  A reducer
gets the run's context and returns a number, or None when it finds
nothing to read (the harness then leaves the metric out of the line):

    spans        program spans that ended inside the measured interval
    boot_spans   program spans from Node(cfg) to the window's opening
    hists        {histogram: {label: (count, sum)}} moved in the window
    harness      numbers the harness took itself (host clock, counters)
    trace        the reduced device trace, or None when none was taken
                 (no `--trace 1`, or no chip)
"""

from __future__ import annotations

import json
import os

from benchmark.lib import devtrace, roofline


def _named(ctx, interval, name, where=None):
    spans = ctx["boot_spans" if interval == "boot" else "spans"]
    out = [s for s in spans if s["name"] == name]
    for k, v in (where or {}).items():
        out = [s for s in out if s.get("args", {}).get(k) == v]
    return out


def span_ms_per(ctx, total, per, interval="window"):
    """1000 x total duration of the spans named in `total` / count of
    the spans named `per`."""
    n = len(_named(ctx, interval, per))
    if not n:
        return None
    return 1e3 * sum(s["dur"] for name in total
                     for s in _named(ctx, interval, name)) / n


def span_count(ctx, span, where=None, interval="window"):
    return float(len(_named(ctx, interval, span, where)))


def span_sum_s(ctx, span, where=None, interval="window"):
    return float(sum(s["dur"] for s in _named(ctx, interval, span, where)))


def span_hit_pct(ctx, miss, of):
    """100 x (1 - count(miss) / count(of))."""
    n = len(_named(ctx, "window", of))
    if not n:
        return None
    return 100.0 * (1.0 - len(_named(ctx, "window", miss)) / n)


def hist_mean_ms(ctx, hist, label):
    count, total = ctx["hists"].get(hist, {}).get(label, (0, 0.0))
    return 1e3 * total / count if count else None


def harness(ctx, key):
    return ctx["harness"].get(key)


def trace_idle_pct(ctx):
    return ctx["trace"]["idle_pct"] if ctx["trace"] else None


def trace_kernel_ms_per_window(ctx, kernel):
    """Device time of one jitted program per reactor window of the
    traced part."""
    if not ctx["trace"]:
        return None
    k = devtrace.kernel(ctx["trace"], kernel)
    n = ctx["trace"].get("reactor_windows")
    return 1e3 * k[1] / n if k and n else None


def trace_kernel_roofline_pct(ctx, kernel):
    """Least time of the calls (from shapes) over their device time."""
    if not ctx["trace"]:
        return None
    k = devtrace.kernel(ctx["trace"], kernel)
    if not k or k[1] <= 0:
        return None
    calls, secs = k
    pct, bound = roofline.roofline_pct(
        ctx["harness"]["device_kind"], ctx["harness"]["bucket_lanes"],
        ctx["harness"]["bucket_templates"], secs / calls)
    ctx["notes"].append(f"{kernel}_roofline bound by {bound}")
    return pct


REDUCERS = {f.__name__: f for f in (
    span_ms_per, span_count, span_sum_s, span_hit_pct, hist_mean_ms,
    harness, trace_idle_pct, trace_kernel_ms_per_window,
    trace_kernel_roofline_pct)}


def load_layer(root: str, name: str) -> dict:
    with open(os.path.join(root, "benchmark", "layers", name + ".json")) as f:
        spec = json.load(f)
    if spec["name"] != name or spec["reducer"] not in REDUCERS:
        raise ValueError(f"layer file of {name!r} names {spec['name']!r} / "
                         f"an unknown reducer {spec['reducer']!r}")
    return spec


def read_metric(spec: dict, ctx: dict):
    return REDUCERS[spec["reducer"]](ctx, **spec.get("args", {}))

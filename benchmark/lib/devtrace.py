"""From the profiler's trace to device metrics: the yardstick's part.

`read_events` turns an `.xplane.pb` into plain tuples with nothing but
jax; everything after that works on the tuples, so it is tested on a
small recorded trace (`tests/benchmark/data/`).  An event is
(plane, line, name, start_s, dur_s), times in seconds on the trace's own
clock (it starts near 0 when the trace starts).

Busy time is the union of the intervals in which an operation ran on a
device plane's operation line; idle is the rest of the traced window.
Each long idle gap is named by the innermost program span (flight
recorder) that covers its middle: the harness writes one
`tmbench.anchor` annotation into the trace at a known instant of the
recorder's clock, which ties the two clocks together.
"""

from __future__ import annotations

import glob
import os

ANCHOR = "tmbench.anchor"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# spans that cover nearly everything say nothing about a gap
TOO_WIDE = ("fastsync.window",)


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """An operation's event is named by its whole HLO line
    (`%while.811 = (s32[]{...}) while(...)`): keep `while.811`."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def read_events(path: str, keep_host=(ANCHOR,)) -> list[tuple]:
    """Device-plane events, plus the host events named in `keep_host`."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for e in line.events:
                if device or e.name in keep_host:
                    out.append((plane.name, line.name, short_name(e.name),
                                e.start_ns / 1e9, e.duration_ns / 1e9))
    return out


def by_line(events: list[tuple], prefix: str = DEVICE_PREFIX) -> dict:
    """{plane: {line: [events that took time]}} of the device planes."""
    out: dict[str, dict[str, list]] = {}
    for e in events:
        if e[0].startswith(prefix):
            out.setdefault(e[0], {}).setdefault(e[1], []).append(e)
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clock_offset(events: list[tuple], anchor_epoch: float) -> float | None:
    """Seconds to ADD to a trace time to get the recorder's clock."""
    a = [e for e in events if e[2] == ANCHOR]
    return anchor_epoch - min(e[3] for e in a) if a else None


def name_gap(mid_epoch: float, spans: list[dict]) -> str:
    """The innermost program span covering an instant, else 'no span'."""
    best = None
    for s in spans:
        dur = s.get("dur", 0.0)
        if dur <= 0 or s["name"] in TOO_WIDE:
            continue
        if s["ts"] <= mid_epoch <= s["ts"] + dur and \
                (best is None or dur < best.get("dur")):
            best = s
    return best["name"] if best else "no span"


def reduce(events: list[tuple], t0: float, t1: float,
           spans: list[dict] | None = None,
           offset: float | None = None, prefix: str = DEVICE_PREFIX,
           top: int = 10) -> dict:
    """Device metrics of the traced window [t0, t1] (trace clock).

    busy_s is averaged over the device planes; the breakdown and the
    gaps are the first plane's.  `kernels` maps each program of the
    modules line to (calls, seconds)."""
    grouped = by_line(events, prefix)
    planes = sorted(grouped)
    if not planes:
        raise ValueError("the trace has no device plane: nothing ran on "
                         "the device")
    window = t1 - t0

    def busy_intervals(plane):
        return union([(max(e[3], t0), min(e[3] + e[4], t1))
                      for e in grouped[plane].get(OPS_LINE, ())
                      if e[4] > 0 and e[3] < t1 and e[3] + e[4] > t0])

    busy = [sum(b - a for a, b in busy_intervals(p)) for p in planes]
    first = planes[0]
    by_op: dict[str, float] = {}
    for e in grouped[first].get(OPS_LINE, ()):
        if t0 <= e[3] <= t1:
            by_op[e[2]] = by_op.get(e[2], 0.0) + e[4]
    kernels: dict[str, list] = {}
    for e in grouped[first].get(MODULES_LINE, ()):
        if t0 <= e[3] <= t1:
            k = kernels.setdefault(e[2].split("(")[0], [0, 0.0])
            k[0] += 1
            k[1] += e[4]
    edges = [t0] + [x for ab in busy_intervals(first) for x in ab] + [t1]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top]
    named = []
    for dur, start in gaps:
        label = (name_gap(start + dur / 2 + offset, spans)
                 if offset is not None and spans is not None
                 else "clock not tied")
        named.append([label, dur])
    busy_s = sum(busy) / len(busy)
    return {
        "planes": planes,
        "lines": {line: len(evs) for line, evs in grouped[first].items()}, "window_s": window, "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window) if window > 0 else None,
        "device_ops": [[n, s] for n, s in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
        "kernels": {k: (v[0], v[1]) for k, v in kernels.items()},
    }


def kernel(reduced: dict, fn: str):
    """(calls, seconds) of the jitted program `fn` on the modules line."""
    for name, (calls, secs) in reduced["kernels"].items():
        if name in (f"jit_{fn}", f"jit({fn})", fn):
            return calls, secs
    return None

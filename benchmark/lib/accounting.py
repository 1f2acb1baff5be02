"""What counts as an operation, and what as a failure.

An OPERATION is a block height the node applied inside the measured
interval.  The interval runs from the first to the last completion of a
whole reactor window (`fastsync.window` span) inside [t_open, t_close]:
the rate is taken over whole windows, so a part of a window at either
edge is counted neither as work nor as time.  A height requested,
downloaded, verified or half applied at either edge is NOT attempted.

`attempted` = heights applied in the interval + heights the node REFUSED
in it.  The served chain is valid by construction, so every refusal
(`pool.redo`: the reactor dropped a delivered block and banned its
deliverer) is a FAILURE, and so is an applied height whose stored block
hash differs from the builder's.  A request that timed out and was asked
for again, a slow peer evicted and redialled, are the protocol working:
they cost throughput, which the rate shows, and are counted per layer
(`pool.evictions`), never as failures.  On sound code `failed` is 0 by
construction, in every run.
"""

from __future__ import annotations

WINDOW_SPAN = "fastsync.window"
REDO_SPAN = "pool.redo"


class WindowTooShort(Exception):
    """Fewer than two reactor windows completed inside the window: there
    is no whole window to take a rate over."""


def span_end(s: dict) -> float:
    return s["ts"] + s.get("dur", 0.0)


def measured_interval(spans: list[dict], t_open: float, t_close: float):
    """(t_first, t_last, windows): the completions of the first and the
    last reactor window inside [t_open, t_close], and the window spans
    that completed after the first, up to and including the last."""
    done = sorted((s for s in spans if s["name"] == WINDOW_SPAN and
                   t_open <= span_end(s) <= t_close), key=span_end)
    if len(done) < 2:
        raise WindowTooShort(
            f"{len(done)} reactor window(s) completed inside the measured "
            "window; a rate needs two completions")
    return span_end(done[0]), span_end(done[-1]), done[1:]


def in_interval(spans: list[dict], t_first: float, t_last: float,
                name: str | None = None) -> list[dict]:
    """Spans (or instants) that ENDED inside (t_first, t_last]."""
    return [s for s in spans if (name is None or s["name"] == name) and
            t_first < span_end(s) <= t_last]


def applied_heights(windows: list[dict]) -> list[int]:
    out: list[int] = []
    for w in windows:
        a = w["args"]
        out.extend(range(a["window"], a["window"] + a["blocks"]))
    return out


def account(spans: list[dict], t_open: float, t_close: float,
            stored_hash, builder_hash) -> dict:
    """The run's operations.  `stored_hash(h)` is the block hash the node
    persisted at height h (None if it has none), `builder_hash(h)` the
    chain builder's."""
    t_first, t_last, windows = measured_interval(spans, t_open, t_close)
    heights = applied_heights(windows)
    refused = [s["args"]["height"]
               for s in in_interval(spans, t_first, t_last, REDO_SPAN)]
    wrong = [h for h in heights if stored_hash(h) != builder_hash(h)]
    return {
        "t_first": t_first, "t_last": t_last, "elapsed_s": t_last - t_first,
        "windows": len(windows), "applied": len(heights),
        "heights": heights, "refused": refused, "wrong_hash": wrong,
        "attempted": len(heights) + len(refused),
        "failed": len(refused) + len(wrong),
        "blocks_per_s": len(heights) / (t_last - t_first),
    }


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    v = sorted(values)
    k = max(0, min(len(v) - 1, -(-len(v) * q // 100) - 1))
    return v[int(k)]

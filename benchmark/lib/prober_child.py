"""The prober child: `/status` on a schedule, timed from the client's side.

Open loop: one probe every `interval_s`, sent when it is DUE whether or
not earlier ones were answered, each timed from its due instant, so a
stall of the node is charged to every probe it delays.  A probe still
unanswered when the window closes enters the result at the time it had
waited by then.  The process imports no jax and nothing of the node.

    python -m benchmark.lib.prober_child

stdin, one JSON line: {"url", "t_open", "t_close", "interval_s"} with
both instants on `time.monotonic()` (CLOCK_MONOTONIC is shared by the
processes of one machine).  stdout, one JSON line: the latencies in
seconds, how late each probe was sent, and how many were unanswered.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.request

MAX_IN_FLIGHT = 64


def probe(url: str, due: float, slot: list) -> None:
    slot[1] = time.monotonic() - due            # how late it was sent
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            body = r.read()
        ok = b"latest_block_height" in body
    except OSError:
        ok = False
    slot[0] = time.monotonic() - due
    slot[2] = ok


def run(url: str, t_open: float, t_close: float, interval_s: float) -> dict:
    slots: list[list] = []
    threads = []
    k = 0
    while True:
        due = t_open + k * interval_s
        if due >= t_close:
            break
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        slot = [None, None, None, due]
        slots.append(slot)
        if sum(t.is_alive() for t in threads) < MAX_IN_FLIGHT:
            t = threading.Thread(target=probe, args=(url, due, slot),
                                 daemon=True)
            t.start()
            threads.append(t)
        k += 1
    delay = t_close - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    lat, late, unanswered, errors = [], [], 0, 0
    for done, sent_late, ok, due in slots:
        if done is None:
            unanswered += 1
            lat.append(t_close - due)
        else:
            lat.append(done)
            errors += 0 if ok else 1
        if sent_late is not None:
            late.append(sent_late)
    return {"latency_s": lat, "late_s": late, "unanswered": unanswered,
            "errors": errors}


def main() -> int:
    if "jax" in sys.modules:
        return 3
    line = sys.stdin.readline()
    if not line.strip():
        return 0
    req = json.loads(line)
    print(json.dumps(run(req["url"], req["t_open"], req["t_close"],
                         req["interval_s"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""`benchmark/run.py`'s run rehearsed on the CPU over a chain whose
commits miss precommits (the mix `data/absent-test.json`: 4 validators,
one down for 96 heights at a time and some late, so every commit holds 3
precommits and one nil entry).  One boot: that the two checks which read
the index's `signed` would fail the same node under an index that says
every commit was full is computed beside the run, by the function the run
itself calls."""

import json
import os

import benchutil

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "absent-test.json")) as _f:
    MIX = json.load(_f)
NAMES = ["refused", "wrong_hash", "tip_hash_differs", "app_hash_differs",
         "rpc_answers_differ", "fallback_calls", "scalar_verify_spans",
         "sigs_verified", "kernel_programs_in_window", "ring_overflowed",
         "probe_errors", "control_lanes_differ", "control_programs"]
# beside the chain's own limits the run prints what `precommit_limits`
# gives for an index that says every commit was full (the parent's limits),
# and what the program counted of commits decoded, by either decoder
BESIDE = """
from benchmark.lib import cell as _cell
from tendermint_tpu.utils.metrics import REGISTRY as _registry
_limits = _cell.precommit_limits

def _both(index, synced, tip):
    full = dict(index, signed=[4] * len(index["signed"]))
    print("[test] limits: the chain's", *_limits(index, synced, tip),
          "a full chain's", *_limits(full, synced, tip), "decoded by vote",
          _registry.commits_decoded_objects.value, "by wire",
          _registry.commits_decoded_wire.value, flush=True)
    return _limits(index, synced, tip)

_cell.precommit_limits = _both
"""


def _line(out: str, start: str) -> str:
    return next(ln for ln in out.splitlines() if ln.startswith(start))


def test_absent_rehearsal_is_correct_by_the_chains_own_count():
    assert MIX["absent"] == {"late_per_1000": 150, "down": 1,
                             "down_for_blocks": 96}
    result, out = benchutil.rehearse(
        seed=2**31 + 431, trace=False, traffic={"absent": MIX["absent"]},
        prelude=BESIDE, timeout=600)
    checks = result["checks"]
    assert list(checks) == NAMES and len(checks) == 13
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 64
    assert all(c["ok"] for c in checks.values())
    assert ("builder's []: 0 "
            in _line(out, "[bench] check /status, /block, /validators"))
    # the one line more: 3 of every 4 lanes signed, every commit and
    # every window with a nil entry
    heights = result["attempted"]
    assert _line(out, "[bench] precommits:") == (
        f"[bench] precommits: {3 * heights} of {4 * heights} commit lanes of "
        f"the interval signed (75.000 %); {heights} of {heights} commits and "
        f"{heights // 64} of {heights // 64} 64-block windows hold an absent "
        "precommit")
    # the program decoded every served commit, by one of its two
    # decoders (which one a commit with a nil entry takes is not held)
    beside = _line(out, "[test] limits:").split()
    held, at_tip, full_held, full_at_tip, by_vote, by_wire = (
        int(beside[i]) for i in (4, 5, 9, 10, 14, 17))
    assert by_vote + by_wire >= held // 3 > 0
    # the floor is the chain's own count, three a height, and the node,
    # look-ahead and all, verified at least that and not four a height
    verified = checks["sigs_verified"]
    assert verified["at_least"] == held and held % 3 == 0
    assert (at_tip, full_at_tip) == (3, 4)
    assert full_held == 4 * held // 3
    assert held <= verified["value"] < full_held
    # so under the parent's limits this sound run is `correct: false` by
    # `sigs_verified` (a floor it cannot reach) and by `/block precommits`
    # (3 answered, 4 expected); no other check reads `signed`
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(out))

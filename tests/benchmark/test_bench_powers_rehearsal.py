"""`benchmark/run.py`'s run rehearsed on the CPU over a chain whose
voting powers move (the mix `data/powers-test.json`: 4 validators, one of
them redrawn to a power of 1 to 30 at every height, the program's
`valset_kvstore` named by the rehearsal's configuration).  One boot: that
the two terms of `rpc_answers_differ` which hold the node to the
builder's set would fail the same node under a plan with another seed's
powers over the same keys is computed beside the run, by the function the
run itself calls.

On the program as it stands every header with another `validators_hash`
cuts a window, so the run makes a block a window (0.2 s each on the CPU
backend) and warms up over one 64-block stretch instead of three; that is
the program's path, and this test holds nothing to it."""

import json
import os

import benchutil

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "powers-test.json")) as _f:
    MIX = json.load(_f)
NAMES = ["refused", "wrong_hash", "tip_hash_differs", "app_hash_differs",
         "rpc_answers_differ", "fallback_calls", "scalar_verify_spans",
         "sigs_verified", "kernel_programs_in_window", "ring_overflowed",
         "probe_errors", "control_lanes_differ", "control_programs"]
# beside the run's own comparison with the builder's set: the same node
# against the set of the same height and the same keys under the powers
# another seed draws, and what a comparison of the keys alone would say
BESIDE = """
from benchmark.lib import cell as _cell, chain as _chain
_cell.WARM_WINDOWS = 1
_valset_at, _differ, _asked = _chain.valset_at, _cell.set_answers_differ, []

def _noting(seed, n, plan, h, powers=None):
    _asked.append((seed, n, plan, h, powers))
    return _valset_at(seed, n, plan, h, powers)

def _both(answered, state_validators, vs):
    seed, n, plan, h, powers = _asked[-1]
    seeds = [_chain.val_seed(seed, i)
             for i in _chain.valset_members(seed, n, plan, h)]
    _s, other = _chain._set_of(seeds, _chain.powers_at(seed + 1, n, plan,
                                                       powers, h))
    print("[test] set of height", h, "powers",
          [v.voting_power for v in vs.validators], "under another seed's",
          [v.voting_power for v in other.validators], "differ",
          _differ(answered, state_validators, other), "keys alone differ",
          [v["pub_key"] for v in answered] !=
          [v.pub_key.bytes_.hex() for v in other.validators], flush=True)
    return _differ(answered, state_validators, vs)

_chain.valset_at, _cell.set_answers_differ = _noting, _both
"""


def _line(out: str, start: str) -> str:
    return next(ln for ln in out.splitlines() if ln.startswith(start))


def test_powers_rehearsal_is_correct_and_holds_the_node_to_the_plans_powers():
    assert MIX["powers"] == {"change_every_blocks": 1, "members": 1,
                             "min": 1, "max": 30}
    result, out = benchutil.rehearse(
        seed=2**31 + 471, trace=False, config={"app": "valset_kvstore"},
        traffic={"powers": MIX["powers"]}, prelude=BESIDE, timeout=600)
    checks = result["checks"]
    assert list(checks) == NAMES and len(checks) == 13
    assert result["correct"] is True, out[-3000:]
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert all(c["ok"] for c in checks.values())
    assert ("builder's []: 0 "
            in _line(out, "[bench] check /status, /block, /validators"))
    # one set of members for the whole chain, whatever its powers did
    assert _line(out, "[bench] validators:").startswith(
        "[bench] validators: the builder's set 1 of 1 holds at height ")
    # the one line more: (nearly) every height of the interval moves a
    # power; a redraw that lands on the member's old power moves none
    heights = result["attempted"]
    words = _line(out, "[bench] powers:").split()
    assert words[2:] == (
        f"{words[2]} of {heights} heights of the interval change a voting "
        f"power (plan {MIX['powers']})").split()
    assert 0.8 * heights <= int(words[2]) <= heights
    # every commit is full: the floor is four precommits a height synced
    assert checks["sigs_verified"]["at_least"] % 4 == 0
    assert _line(out, "[bench] precommits:").split()[2:5] == [
        str(4 * heights), "of", str(4 * heights)]
    # beside the run: the powers the node was held to are the plan's, not
    # genesis's; under another seed's powers over the SAME keys the same
    # node differs by both terms, where the keys alone would pass it
    beside = _line(out, "[test] set of height")
    ours, theirs = (json.loads(beside[beside.index(w) + len(w):].split(
        "]")[0] + "]") for w in (" powers ", "another seed's "))
    assert len(ours) == len(theirs) == 4 and ours != theirs
    assert ours != [10] * 4 and all(1 <= p <= 30 for p in ours)
    assert ("differ ['/validators', 'state validators hash'] keys alone "
            "differ False") in beside
    assert not any(benchutil.alive(p) for p in benchutil.child_pids(out))

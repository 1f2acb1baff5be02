"""The served chain, made from the seed by code that is not under test.

Copied from `chip_smoke.build_chain` (listed in PERF.md for a later PR to
merge) and changed where the benchmark needs it: every signature is
OpenSSL's (`cryptography`), with ONE key object per validator kept for
the whole build, and a height's signatures fanned out to worker
processes when the set is large; hashes are hashlib's (no crypto backend
is installed in the process that builds); app hashes come from a plain
reference of the kvstore app (`RefKVStore`) the chain is applied to here.
The program's own types only give the blocks their wire format.  What the builder keeps per height
is what a source peer serves (the encoded block) and what the check
needs (block hash, app hash after the height, encoded size).

A traffic mix may state a validator-set plan (`valset`, README): then
the set that signs changes where the plan says, by `val:` txs the blocks
carry and the reference app returns as `EndBlock` diffs.  `valset_at` is
the one function that says which set a height has; the builder, the
source child's index and the harness's check all read it.

A traffic mix may state a plan of absent precommits (`absent`, README):
then a commit holds upstream's nil entry where a member's precommit
missed `timeout_commit` or the member is down.  `absent_at` is the one
function that says who is silent at a height: the builder reads it, the
source child's index holds its count a height (`signed`), and the
harness's check sums that.

A traffic mix may state a plan of voting powers (`powers`, README): then
members' powers move where the plan says, by `val:` txs as the `valset`
plan's, and every header holds the hash of its own height's powers.
`powers_at` is the one function that says which power each member of a
height has; `valset_at` hands it on, so the builder, the index and the
check read it too.

Nothing in this module may import jax: it runs in the source child.
"""

from __future__ import annotations

import functools
import hashlib
import os
import subprocess
import sys

from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey

from benchmark.lib import signer

POWER = 10
GENESIS_TIME_NS = 1_000_000_000
# below this many validators a pipe round trip costs more than signing
MIN_VALS_FOR_WORKERS = 32
# upstream persistent_dummy's validator tx: `val:<pubkey hex>/<power>`
VAL_TX_PREFIX = b"val:"


def val_seed(seed: int, i: int) -> bytes:
    return hashlib.sha256(b"tm-bench/%d/val/%d" % (seed, i)).digest()


@functools.lru_cache(maxsize=4096)
def pub_of(key_seed: bytes) -> bytes:
    return (Ed25519PrivateKey.from_private_bytes(key_seed).public_key()
            .public_bytes_raw())


@functools.lru_cache(maxsize=4096)
def _pub_key_of(key_seed: bytes):
    """The program's `PubKey` of a seed: immutable, and it keeps its
    address, so a chain that makes a set a height hashes each key once."""
    from tendermint_tpu.types.keys import PubKey
    return PubKey(pub_of(key_seed))


def _set_of(seeds: list[bytes], powers=None):
    """(seeds aligned with the set's validator order, ValidatorSet);
    `powers` is aligned with `seeds`, POWER each where none are given.
    Public keys are OpenSSL's; the program's ValidatorSet only orders
    them (by address) and hashes the set for the headers."""
    from tendermint_tpu.types import Validator, ValidatorSet
    vs = ValidatorSet([Validator(_pub_key_of(s), p) for s, p in zip(
        seeds, powers or [POWER] * len(seeds), strict=True)])
    by_addr = {_pub_key_of(s).address: s for s in seeds}
    return [by_addr[v.address] for v in vs.validators], vs


def make_validators(seed: int, n: int):
    """The genesis set: keys 0..n-1 of the seed, as `_set_of` gives it."""
    return _set_of([val_seed(seed, i) for i in range(n)])


@functools.lru_cache(maxsize=None)
def _members(seed: int, n: int, swap: int, epoch: int) -> tuple[int, ...]:
    """Key indices of the set after `epoch` changes.  At change c the
    `swap` members whose sha256(seed, c, index) is least leave, and the
    keys n + (c-1) x swap + j, j < swap, never used before, join."""
    if epoch == 0:
        return tuple(range(n))
    prev = _members(seed, n, swap, epoch - 1)
    out = set(sorted(prev, key=lambda i: hashlib.sha256(
        b"tm-bench/%d/out/%d/%d" % (seed, epoch, i)).digest())[:swap])
    first_new = n + (epoch - 1) * swap
    return tuple(i for i in prev if i not in out) + tuple(
        range(first_new, first_new + swap))


def valset_members(seed: int, n: int, plan: dict | None,
                   height: int) -> tuple[int, ...]:
    """Key indices of the set of `height` (>= 1) under a traffic mix's
    `valset` plan: the diffs of a height h with h % change_every_blocks
    == 0 make the set of h + 1.  No plan: the genesis set throughout."""
    if not plan:
        return tuple(range(n))
    every, swap = plan["change_every_blocks"], plan["swap"]
    if every < 1 or not 1 <= swap <= n:
        raise ValueError(f"valset plan {plan!r} for {n} validators: needs "
                         "change_every_blocks >= 1 and 1 <= swap <= n")
    members = ()
    # epoch by epoch, so that the cached recursion is one call deep
    for epoch in range((height - 1) // every + 1):
        members = _members(seed, n, swap, epoch)
    return members


POWERS_KEYS = ("change_every_blocks", "members", "min", "max")


def _powers_numbers(plan: dict, n: int) -> tuple[int, int, int, int]:
    """(change_every_blocks, members, min, max) of a `powers` plan for
    sets of n members, or a ValueError that names the plan."""
    numbers = tuple(plan.get(k) for k in POWERS_KEYS)
    if (set(plan) != set(POWERS_KEYS)
            or not all(type(x) is int for x in numbers)
            or numbers[0] < 1 or not 1 <= numbers[1] <= n
            or not 1 <= numbers[2] <= numbers[3] < 2**32):
        raise ValueError(
            f"powers plan {plan!r} for {n} validators: needs "
            "change_every_blocks >= 1, 1 <= members <= n and 1 <= min <= "
            "max < 2**32 (a power is drawn from four bytes of a hash), "
            f"whole numbers all, and the keys {POWERS_KEYS}, no other")
    return numbers


def _frozen(plan: dict | None) -> tuple | None:
    return tuple(sorted(plan.items())) if plan else None


@functools.lru_cache(maxsize=8)
def _power_epochs(seed: int, n: int, valset: tuple | None,
                  powers: tuple) -> list[dict[int, int]]:
    """A chain's memo of `powers_at`: entry c is key index -> power for
    the set of height c x change_every_blocks + 1, the set that the c-th
    change made.  `powers_at` grows it a change at a time and never
    edits an entry; the genesis set, entry 0, is POWER throughout."""
    return [dict.fromkeys(range(n), POWER)]


def powers_at(seed: int, n: int, valset_plan: dict | None,
              powers_plan: dict | None, height: int) -> tuple[int, ...]:
    """The voting powers of the set of `height` (>= 1), aligned with
    `valset_members(seed, n, valset_plan, height)`, under a traffic mix's
    `powers` plan.  The genesis set is POWER throughout.  The diffs of a
    height h with h % change_every_blocks == 0 make the powers of h + 1:
    the `members` members of the set of h whose
    sha256(seed, "power-pick", h, index) is least are redrawn to min +
    sha256(seed, "power", h, index)[:4] % (max - min + 1) (one that the
    `valset` plan takes out at h leaves all the same), every other
    member keeps what it had, and a key that joins joins at POWER.  No
    plan: POWER for every member at every height."""
    members = valset_members(seed, n, valset_plan, height)
    if not powers_plan:
        return (POWER,) * len(members)
    every, redrawn, lo, hi = _powers_numbers(powers_plan, n)
    epochs = _power_epochs(seed, n, _frozen(valset_plan),
                           _frozen(powers_plan))
    for c in range(len(epochs), (height - 1) // every + 1):
        h = c * every
        now = valset_members(seed, n, valset_plan, h)
        picked = sorted(now, key=lambda i: hashlib.sha256(
            b"tm-bench/%d/power-pick/%d/%d" % (seed, h, i)).digest())[:redrawn]
        after = dict(epochs[-1])
        for i in picked:
            after[i] = lo + int.from_bytes(hashlib.sha256(
                b"tm-bench/%d/power/%d/%d" % (seed, h, i)).digest()[:4],
                "big") % (hi - lo + 1)
        epochs.append({i: after.get(i, POWER) for i in valset_members(
            seed, n, valset_plan, h + 1)})
    held = epochs[(height - 1) // every]
    return tuple(held.get(i, POWER) for i in members)


ABSENT_KEYS = ("late_per_1000", "down", "down_for_blocks")


def _absent_numbers(plan: dict) -> tuple[int, int, int]:
    """(late_per_1000, down, down_for_blocks) of an `absent` plan, or a
    ValueError that names the plan."""
    late, down = plan.get("late_per_1000", 0), plan.get("down", 0)
    every = plan.get("down_for_blocks", 1 if not down else None)
    if (set(plan) - set(ABSENT_KEYS)
            or not all(type(x) is int for x in (late, down, every))
            or not 0 <= late <= 1000 or down < 0 or every < 1):
        raise ValueError(
            f"absent plan {plan!r}: needs 0 <= late_per_1000 <= 1000, "
            "down >= 0 and, where any is down, down_for_blocks >= 1, whole "
            f"numbers all, and no key besides {ABSENT_KEYS}")
    return late, down, every


@functools.lru_cache(maxsize=64)
def _down(seed: int, members: tuple[int, ...], k: int,
          epoch: int) -> tuple[int, ...]:
    """The k of `members` that are offline in `epoch`: those whose
    sha256(seed, epoch, index) is least, least first."""
    return tuple(sorted(members, key=lambda i: hashlib.sha256(
        b"tm-bench/%d/down/%d/%d" % (seed, epoch, i)).digest())[:k])


def absent_at(seed: int, n: int, valset_plan: dict | None,
              absent_plan: dict | None, height: int,
              powers_plan: dict | None = None) -> tuple[int, ...]:
    """Key indices, in rising order, of the members of the set of
    `height` whose precommit the commit of `height` does not hold, under
    a traffic mix's `absent` plan: a subset of `valset_members(seed, n,
    valset_plan, height)`.  The members that are down in the epoch
    `(height - 1) // down_for_blocks` come first, then the late ones
    (sha256(seed, height, index)[:4] % 1000 < late_per_1000) in the order
    of their hash, and the list ends at the largest count that leaves
    MORE than 2/3 of the set's power signed (every power is POWER: the
    cut counts heads, so a mix that states a `powers` plan beside the
    `absent` plan is refused).  No plan: nobody."""
    if not absent_plan:
        return ()
    if powers_plan:
        raise ValueError(
            f"absent plan {absent_plan!r} together with powers plan "
            f"{powers_plan!r}: the +2/3 cut of the absent plan counts heads "
            "of equal power, so a traffic mix may state one of the two")
    late, down, every = _absent_numbers(absent_plan)
    members = valset_members(seed, n, valset_plan, height)
    # 3 x (len - silent) > 2 x len, in units of POWER
    most = -(-len(members) // 3) - 1
    if down > most:
        raise ValueError(
            f"absent plan {absent_plan!r} for {len(members)} validators: "
            f"{down} down leave no more than 2/3 of the power to sign "
            f"(at most {most} may be silent)")
    silent = list(_down(seed, members, down, (height - 1) // every))
    if late:
        draws = sorted((hashlib.sha256(b"tm-bench/%d/late/%d/%d" % (
            seed, height, i)).digest(), i) for i in members)
        silent += [i for d, i in draws if i not in silent and
                   int.from_bytes(d[:4], "big") % 1000 < late]
    return tuple(sorted(silent[:most]))


def check_plans(seed: int, n: int, valset_plan: dict | None,
                absent_plan: dict | None, powers_plan: dict | None) -> None:
    """A ValueError that names the plan where a traffic mix states one
    that cannot be run, or two that do not go together: what the builder
    and `run_cell` call before anything is started."""
    absent_at(seed, n, valset_plan, absent_plan, 1, powers_plan)
    powers_at(seed, n, valset_plan, powers_plan, 1)


def valset_at(seed: int, n: int, plan: dict | None, height: int,
              powers_plan: dict | None = None):
    """(signing seeds in set order, ValidatorSet) of `height`: the set
    that signs the commit of `height` and whose hash its header holds,
    with the powers `powers_at` gives its members."""
    return _set_of(
        [val_seed(seed, i) for i in valset_members(seed, n, plan, height)],
        powers_at(seed, n, plan, powers_plan, height))


def _val_tx(seed: int, i: int, power: int) -> bytes:
    return VAL_TX_PREFIX + b"%s/%d" % (
        pub_of(val_seed(seed, i)).hex().encode(), power)


def power_txs(seed: int, n: int, plan: dict | None,
              powers_plan: dict | None, h: int) -> list[bytes]:
    """The `val:` txs of height h that move a power: its new power for
    each member of the sets of both h and h + 1 whose power `powers_at`
    changes between them, by key index; none where it changes none."""
    if not powers_plan or h % powers_plan["change_every_blocks"]:
        return []
    was = dict(zip(valset_members(seed, n, plan, h),
                   powers_at(seed, n, plan, powers_plan, h)))
    return [_val_tx(seed, i, power) for i, power in sorted(zip(
        valset_members(seed, n, plan, h + 1),
        powers_at(seed, n, plan, powers_plan, h + 1)))
        if was.get(i, power) != power]


def valset_txs(seed: int, n: int, plan: dict | None, h: int,
               powers_plan: dict | None = None) -> list[bytes]:
    """The `val:` txs height h carries under the two plans: power 0 for
    each member that leaves (by key index), then POWER for each key that
    joins, then `power_txs`; none where neither plan changes anything at
    h."""
    txs = []
    if plan and not h % plan["change_every_blocks"]:
        old = valset_members(seed, n, plan, h)
        new = valset_members(seed, n, plan, h + 1)
        txs = [_val_tx(seed, i, power)
               for idxs, power in ((sorted(set(old) - set(new)), 0),
                                   (sorted(set(new) - set(old)), POWER))
               for i in idxs]
    return txs + power_txs(seed, n, plan, powers_plan, h)


def parse_val_tx(tx: bytes) -> tuple[bytes, int]:
    """(public key, power) of a `val:<pubkey hex>/<power>` tx."""
    pub, _, power = tx[len(VAL_TX_PREFIX):].partition(b"/")
    pub = bytes.fromhex(pub.decode())
    if len(pub) != 32 or not power.isdigit():
        raise ValueError(f"malformed validator tx {tx[:80]!r}")
    return pub, int(power)


def genesis_dict(chain_id: str, vs) -> dict:
    return {"chain_id": chain_id, "genesis_time_ns": GENESIS_TIME_NS,
            "power": POWER,
            "validators": [v.pub_key.bytes_.hex() for v in vs.validators]}


def genesis_doc(g: dict):
    from tendermint_tpu.types import GenesisDoc, GenesisValidator
    return GenesisDoc(chain_id=g["chain_id"],
                      genesis_time_ns=g["genesis_time_ns"],
                      validators=[GenesisValidator(bytes.fromhex(p),
                                                   g["power"])
                                  for p in g["validators"]])


class RefKVStore:
    """The plain reference of the kvstore app's state commitment, written
    from its description (`abci/apps/kvstore.py`: keys shard into 256
    buckets by the first byte of sha256(key); a bucket's digest is
    sha256 over its sorted length-prefixed pairs, never-written buckets
    are 32 zero bytes; the app hash is the first 20 bytes of sha256 over
    the 256 digests and the height).  It re-hashes a bucket once per
    commit, not once per write, and shares no code with the program.

    A `val:` tx is stored as today's kvstore stores any tx without `=`
    (key = value = the tx) and is kept besides as one of the block's
    validator `diffs`, which `EndBlock` returns and `commit` clears."""

    def __init__(self):
        self.height = 0
        self.diffs: list[tuple[bytes, int]] = []
        self._buckets = [{} for _ in range(256)]
        self._digests = [bytes(32)] * 256
        self._dirty: set[int] = set()

    def deliver_tx(self, tx: bytes) -> None:
        if tx.startswith(VAL_TX_PREFIX):
            self.diffs.append(parse_val_tx(tx))
        k, _, v = tx.partition(b"=") if b"=" in tx else (tx, b"", tx)
        b = hashlib.sha256(k).digest()[0]
        self._buckets[b][k] = v
        self._dirty.add(b)

    def commit(self) -> bytes:
        for b in self._dirty:
            h = hashlib.sha256()
            for k in sorted(self._buckets[b]):
                v = self._buckets[b][k]
                h.update(len(k).to_bytes(4, "big") + k +
                         len(v).to_bytes(4, "big") + v)
            self._digests[b] = h.digest()
        self._dirty.clear()
        self.diffs = []
        self.height += 1
        return hashlib.sha256(b"".join(self._digests) +
                              self.height.to_bytes(8, "big")).digest()[:20]


class Signers:
    """Signs one message with every key of the set in use, in set order.
    It keeps every key it was ever given (the union of a churning
    chain's sets); `use` says which of them are the set from now on."""

    def __init__(self, seeds: list[bytes], workers: int | None = None):
        self._n = len(seeds)
        if workers is None:
            workers = (min(8, max(1, (os.cpu_count() or 2) - 3))
                       if self._n >= MIN_VALS_FOR_WORKERS else 0)
        self._procs: list[subprocess.Popen] = []
        self._keys = None
        self._key_of: dict[bytes, Ed25519PrivateKey] = {}
        if workers <= 1:
            self.use(seeds)
            return
        # contiguous slices, so the answers concatenate in set order
        self._per = -(-self._n // workers)
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        try:
            for _ in range(0, self._n, self._per):
                self._procs.append(subprocess.Popen(
                    [sys.executable, os.path.abspath(signer.__file__)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE, cwd=root))
            self.use(seeds)
        except BaseException:
            self.close()
            raise

    def use(self, seeds: list[bytes]) -> None:
        """The set that signs from now on: as many seeds as before, in
        set order."""
        if len(seeds) != self._n:
            raise ValueError(f"a set of {len(seeds)} keys where the signers "
                             f"hold {self._n}")
        if not self._procs:
            self._keys = signer.keys_for(self._key_of, seeds)
            return
        for i, p in enumerate(self._procs):
            signer.write_frame(p.stdin, signer.KEYS + b"".join(
                seeds[i * self._per:(i + 1) * self._per]))
        for p in self._procs:
            if signer.read_frame(p.stdout) != b"ok":
                raise RuntimeError("a signing worker did not take its keys")

    def sign_all(self, msg: bytes) -> list[bytes]:
        if self._keys is not None:
            return [k.sign(msg) for k in self._keys]
        for p in self._procs:
            signer.write_frame(p.stdin, signer.SIGN + msg)
        out = b"".join(signer.read_frame(p.stdout) or b""
                       for p in self._procs)
        if len(out) != 64 * self._n:
            raise RuntimeError("a signing worker died")
        return [out[i:i + 64] for i in range(0, len(out), 64)]

    def close(self) -> None:
        for p in self._procs:
            try:
                p.stdin.close()
            except OSError:
                pass
        for p in self._procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        self._procs = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def block_txs(block: dict, seed: int, h: int) -> list[bytes]:
    """The txs of height h under a traffic mix's `block`: `txs_per_block`
    kvstore txs `k<key>=v<height>.<filler>` of `tx_bytes` bytes over
    `keys` reused keys (constant app state, so a block costs the same to
    apply at every height).  The filler is one seeded stream per block."""
    n, size, keys = block["txs_per_block"], block["tx_bytes"], block["keys"]
    heads = [b"k%d=v%d." % ((h * n + i) % keys, h) for i in range(n)]
    need = max(0, size - min(len(x) for x in heads))
    fill = b"".join(
        hashlib.sha256(b"%d/%d/%d" % (seed, h, j)).hexdigest().encode()
        for j in range(-(-(need + n) // 64)))
    return [x + fill[i:i + size - len(x)] if len(x) < size else x
            for i, x in enumerate(heads)]


def _next_set(vs, diffs, seed: int, n: int, plan: dict | None, h: int,
              powers_plan: dict | None = None):
    """The set of h + 1 as `valset_at` gives it, which has to be the set
    of h with the app's diffs of h applied, as the program will have it."""
    seeds, new = valset_at(seed, n, plan, h + 1, powers_plan)
    pubs = {v.pub_key.bytes_: v.voting_power for v in vs.validators}
    for pub, power in diffs:
        if power:
            pubs[pub] = power
        elif pubs.pop(pub, None) is None:
            raise RuntimeError(f"height {h} removes a validator not in "
                               "the set")
    if pubs != {v.pub_key.bytes_: v.voting_power for v in new.validators}:
        raise RuntimeError(f"the diffs of height {h} do not make the set "
                           f"the plan gives height {h + 1}")
    return seeds, new


def _silent_positions(seed: int, n: int, valset: dict | None,
                      absent: dict | None, h: int, seeds) -> set[int]:
    """Where in the set of h (`seeds`, in set order) the members sit
    that `absent_at` names."""
    gone = {val_seed(seed, i) for i in absent_at(seed, n, valset, absent, h)}
    return {p for p, s in enumerate(seeds) if s in gone} if gone else set()


def build_chain(chain_id: str, seeds, vs, n_blocks: int, block_spec: dict,
                seed: int, signers: Signers | None = None,
                keep_objects: bool = False, valset: dict | None = None,
                absent: dict | None = None, powers: dict | None = None):
    """Heights 1..n_blocks, each block embedding the +2/3 LastCommit of
    its predecessor.  `seeds`, `vs` are the genesis set; under a `valset`
    plan a height's header holds the hash of ITS set, its commit is
    signed by that set's members only, and the set moves after a height
    whose `val:` txs the reference app returned as diffs.  Under an
    `absent` plan the commit of h holds None (upstream's nil entry) at
    the positions of the members `absent_at` names: every member signs,
    as without the plan, and the silent ones' signatures are dropped.
    Under a `powers` plan the set of a height holds the powers
    `powers_at` gives it: the genesis set (`vs`) is POWER throughout, and
    a height whose `val:` txs move a power is followed by a set of the
    same members, and signing keys, with another hash.
    Returns a dict of per-height lists (index h-1): `encoded`,
    `block_hash`, `app_hash` (state after h), `signed` (the precommits
    the commit of h holds), with `keep_objects` also `objects` = (block,
    part_set, seen_commit); and `valsets`, a (first height,
    ValidatorSet as it stands at that height) for every set of MEMBERS
    that signs: a change of powers alone adds none."""
    from tendermint_tpu.types import (TYPE_PRECOMMIT, Block, BlockID, Commit,
                                      EMPTY_COMMIT, Vote, ZERO_BLOCK_ID,
                                      canonical)
    from tendermint_tpu.types.part_set import PartSet
    n = len(seeds)
    check_plans(seed, n, valset, absent, powers)    # a malformed plan: here
    own = signers is None
    signers = signers or Signers(seeds)
    app = RefKVStore()
    vals_hash = vs.hash()
    addrs = [v.address for v in vs.validators]
    out = {"encoded": [], "block_hash": [], "app_hash": [], "signed": [],
           "objects": [], "valsets": [(1, vs)]}
    last_commit, last_block_id, app_hash = EMPTY_COMMIT, ZERO_BLOCK_ID, b""
    try:
        for h in range(1, n_blocks + 1):
            txs = block_txs(block_spec, seed, h) + valset_txs(
                seed, n, valset, h, powers)
            block = Block.make(chain_id=chain_id, height=h,
                               time_ns=GENESIS_TIME_NS + h, txs=txs,
                               last_commit=last_commit,
                               last_block_id=last_block_id,
                               validators_hash=vals_hash, app_hash=app_hash)
            enc = block.encode()
            ps = PartSet.from_data(enc)
            bid = BlockID(block.hash(), ps.header)
            msg = canonical.sign_bytes(
                chain_id, TYPE_PRECOMMIT, h, 0, block_hash=bid.hash,
                parts_hash=bid.parts.hash, parts_total=bid.parts.total)
            sigs = signers.sign_all(msg)
            silent = _silent_positions(seed, n, valset, absent, h, seeds)
            seen = Commit(block_id=bid, precommits=[
                None if i in silent else
                Vote(validator_address=addrs[i], validator_index=i, height=h,
                     round=0, type=TYPE_PRECOMMIT, block_id=bid, signature=s)
                for i, s in enumerate(sigs)])
            for tx in txs:
                app.deliver_tx(tx)
            diffs = app.diffs             # EndBlock's, before the commit
            app_hash = app.commit()
            out["encoded"].append(enc)
            out["block_hash"].append(bid.hash)
            out["app_hash"].append(app_hash)
            out["signed"].append(len(sigs) - len(silent))
            if keep_objects:
                out["objects"].append((block, ps, seen))
            last_commit, last_block_id = seen, bid
            if diffs:
                was = seeds
                seeds, vs = _next_set(vs, diffs, seed, n, valset, h, powers)
                vals_hash = vs.hash()
                if seeds != was:          # other members, not powers alone
                    signers.use(seeds)
                    addrs = [v.address for v in vs.validators]
                    out["valsets"].append((h + 1, vs))
    finally:
        if own:
            signers.close()
    return out

"""The plain reference of commit verification for a chain whose commits
miss precommits: upstream tendermint v0.10.3's `ValidatorSet.VerifyCommit`
(`types/validator_set.go:220-264`) written as the loop it is, over a
commit that the reference reads FROM THE SERVED BLOCK'S BYTES with a
decoder of its own.

The decoder walks a block as upstream's go-wire reads a struct, one field
after the other (`int.from_bytes` and slices; nothing of the program's
decoders: no `Commit`, `Vote`, `Block` or `Reader`; the layout is the one
`Block.encode`, `Commit.encode` and `Vote.encode` write):

    header: chain id (u32 length + bytes), height u64, time i64, txs u64,
        last block id, then four length-prefixed hashes;
    txs: u32 count, each length-prefixed;
    commit: block id, u32 count, then per entry ONE MARKER BYTE, 0 for a
        nil precommit, 1 when a vote follows, anything else a decode
        error (as go-wire's pointer byte is; stated from memory, no
        network here), and the vote's fields in order: address
        (length-prefixed), index u32, height u64, round u32, type u8,
        block id, signature (length-prefixed);
    a block id: hash (length-prefixed), parts total u32, parts hash
        (length-prefixed);
    and nothing may be left over.

It yields plain records (`RefCommit`, `RefVote`: tuples), never a `Vote`:
an entry is bound to its POSITION in the bytes, and what its `index`
field says is checked against that position below.

    the set's size against the commit's, the height against the commit's;
    per entry: a nil precommit is SKIPPED, neither verified nor tallied;
    height, round, type, index and address of a precommit that is there;
    its signature over its sign bytes under the member's key (OpenSSL);
    the member's power tallied when the vote is for the block id;
    accepted only if the tally is MORE than 2/3 of the set's WHOLE power.

The verdict rules read those records and nothing of what the program
verifies with: no lane builder of `types/validator.py`, no
`Commit.wire_columns`, no batch plane, no crypto backend.  The sign bytes
come from `canonical.sign_bytes`, the one function the chain's builder
signs with.  Departures from upstream, noted: upstream's loop does not
compare a precommit's `validator_index` and address with its position;
the program refuses both as malformed, and so does this.  A vote whose
block or parts hash is neither empty nor 32 bytes has no sign bytes in
this framework's fixed layout: malformed, for both.  The commit's OWN
block id is not looked at, as upstream's loop does not: each precommit's
is.  The loop keeps upstream's ORDER, entry by entry, form then
signature; a program that checks every entry's form before any signature
names another class where a commit is forged at one entry and malformed
at a later one (the tests say where).

A commit that a test BUILT from votes reaches the reference as its
`Commit(...).encode()`: inside a block's bytes (`ref_commit`) wherever
the program is handed the same bytes, or alone (`ref_commit_alone`)
where a test hands the program the `Commit` object itself.  No adapter
reads `precommits`.

A verdict is a tuple the tests compare with the program's exceptions:
`None` (accepted), `("format", height)` (a decode error is one),
`("signature", height, validator index)` or `("power", height)`.
"""

from typing import NamedTuple

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PublicKey

from tendermint_tpu.types import canonical

PRECOMMIT = 2                      # upstream's VoteTypePrecommit


class RefDecodeError(ValueError):
    """The bytes are no block (or no commit) of this layout."""


class RefVote(NamedTuple):
    address: bytes
    index: int
    height: int
    round: int
    type: int
    block_id: tuple                # (hash, parts total, parts hash)
    signature: bytes


class RefCommit(NamedTuple):
    block_id: tuple
    entries: tuple                 # a RefVote, or None for a nil entry


class RefBlock(NamedTuple):
    """A served block as far as the reference needs it, and where its
    walk found the commit: `commit_at` the commit's first byte (it runs to
    the block's end), `count_at` its u32 count, `entry_at[k]` the marker
    byte of entry k, and one offset more, the block's end."""
    height: int
    commit: RefCommit
    commit_at: int
    count_at: int
    entry_at: tuple


class _Walk:
    """One pass over one buffer; every read is checked against its end."""

    def __init__(self, buf: bytes):
        self.buf, self.at = bytes(buf), 0

    def take(self, n: int) -> bytes:
        if n > len(self.buf) - self.at:
            raise RefDecodeError(f"{n} bytes wanted at {self.at}, "
                                 f"{len(self.buf) - self.at} left")
        self.at += n
        return self.buf[self.at - n:self.at]

    def u8(self) -> int:
        return self.take(1)[0]

    def u32(self) -> int:
        return int.from_bytes(self.take(4), "big")

    def u64(self) -> int:
        return int.from_bytes(self.take(8), "big")

    def lp(self) -> bytes:
        return self.take(self.u32())

    def block_id(self) -> tuple:
        return (self.lp(), self.u32(), self.lp())

    def commit(self) -> tuple:
        """(the commit, the offset of its count, the offsets of its
        entries' marker bytes and of its end)."""
        block_id = self.block_id()
        count_at = self.at
        entries, entry_at = [], []
        # a count the body cannot hold runs into the buffer's end
        for k in range(self.u32()):
            entry_at.append(self.at)
            marker = self.u8()
            if marker == 0:
                entries.append(None)
            elif marker == 1:
                entries.append(RefVote(
                    address=self.lp(), index=self.u32(), height=self.u64(),
                    round=self.u32(), type=self.u8(),
                    block_id=self.block_id(), signature=self.lp()))
            else:
                raise RefDecodeError(f"entry {k}: marker byte {marker}")
        entry_at.append(self.at)
        return RefCommit(block_id, tuple(entries)), count_at, tuple(entry_at)

    def end(self):
        if self.at != len(self.buf):
            raise RefDecodeError(f"{len(self.buf) - self.at} bytes left over")


def ref_decode_block(data: bytes) -> RefBlock:
    """The commit a served block carries (the one of the height before
    its own), or `RefDecodeError`."""
    w = _Walk(data)
    w.lp()                                     # chain id
    height = w.u64()
    w.take(8)                                  # time, i64
    w.u64()                                    # number of txs, as stated
    w.block_id()                               # the last block's
    for _hash in ("last commit", "data", "validators", "app"):
        w.lp()
    for _tx in range(w.u32()):
        w.lp()
    commit_at = w.at
    commit, count_at, entry_at = w.commit()
    w.end()
    return RefBlock(height, commit, commit_at, count_at, entry_at)


def ref_commit(block_bytes: bytes):
    """What `ref_verify_commit` takes for a served block: its commit, or
    the decode error itself, which is the verdict `format`."""
    try:
        return ref_decode_block(block_bytes).commit
    except RefDecodeError as e:
        return e


def ref_commit_alone(commit_bytes: bytes):
    """The same for a commit's bytes with no block around them."""
    w = _Walk(commit_bytes)
    try:
        commit = w.commit()[0]
        w.end()
    except RefDecodeError as e:
        return e
    return commit


def key_of(block_id) -> tuple:
    """A block id as the records hold one; the program's `BlockID`
    (which the chain's builder hands the tests) is read by its fields."""
    if isinstance(block_id, tuple):
        return block_id
    return (block_id.hash, block_id.parts.total, block_id.parts.hash)


def ref_verify_commit(chain_id: str, members: list[tuple], block_id,
                      height: int, commit):
    """`members`: (address, 32-byte public key, power) in set order;
    `commit`: a `RefCommit`, or the `RefDecodeError` its bytes raised."""
    if isinstance(commit, RefDecodeError):
        return ("format", height)
    entries = commit.entries
    if len(members) != len(entries):
        return ("format", height)
    present = [v for v in entries if v is not None]
    if not present or present[0].height != height:
        return ("format", height)
    round_ = present[0].round
    want = key_of(block_id)
    tallied = 0
    for idx, vote in enumerate(entries):
        if vote is None:
            continue                  # may be nil if the validator skipped
        address, pub, power = members[idx]
        block_hash, parts_total, parts_hash = vote.block_id
        if (vote.height != height or vote.round != round_
                or vote.type != PRECOMMIT
                or vote.index != idx or vote.address != address
                or len(vote.signature) != 64
                or len(block_hash) not in (0, 32)
                or len(parts_hash) not in (0, 32)):
            return ("format", height)
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(
                vote.signature, canonical.sign_bytes(
                    chain_id, vote.type, vote.height, vote.round,
                    block_hash=block_hash, parts_hash=parts_hash,
                    parts_total=parts_total))
        except InvalidSignature:
            return ("signature", height, idx)
        if vote.block_id == want:
            tallied += power          # else: no error, but it counts not
    total = sum(power for _a, _k, power in members)
    if 3 * tallied > 2 * total:
        return None
    return ("power", height)


def ref_verify_window(chain_id: str, members: list[tuple],
                      items: list[tuple]):
    """The first verdict that is not `None` over `items` = [(block id,
    height, commit)] in order, as upstream's sync loop meets them."""
    for block_id, height, commit in items:
        verdict = ref_verify_commit(chain_id, members, block_id, height,
                                    commit)
        if verdict is not None:
            return verdict
    return None


def members_of(val_set) -> list[tuple]:
    return [(v.address, v.pub_key.bytes_, v.voting_power)
            for v in val_set.validators]

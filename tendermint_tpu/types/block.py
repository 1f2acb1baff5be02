"""Block, Header, Commit, BlockID — the replicated data structures.

Reference: `types/block.go` — Block = Header + Data(Txs) + LastCommit
(`:23-27`), `Header.Hash` = Merkle-of-map over fields (`:178-193`),
`Commit.Hash` = Merkle over precommit signatures (`:345-354`),
`ValidateBasic` structural checks (`:53-90`).
"""

from __future__ import annotations

import struct
import time
from dataclasses import dataclass, field
from functools import lru_cache

from tendermint_tpu.types import merkle
from tendermint_tpu.types.codec import (Reader, i64, lp_bytes, u32, u64, u8,
                                        without)
from tendermint_tpu.types.part_set import PartSet, PartSetHeader, ZERO_PSH
from tendermint_tpu.types.tx import txs_hash
from tendermint_tpu.types.vote import Vote
from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.metrics import REGISTRY

MAX_BLOCK_SIZE_TXS = 10_000   # reference config/config.go:373


@dataclass(frozen=True)
class BlockID:
    hash: bytes = b""
    parts: PartSetHeader = ZERO_PSH

    def is_zero(self) -> bool:
        return not self.hash and self.parts.is_zero()

    def key(self) -> tuple:
        return (self.hash, self.parts.total, self.parts.hash)

    def encode(self) -> bytes:
        return lp_bytes(self.hash) + self.parts.encode()

    @classmethod
    def decode(cls, r: Reader) -> "BlockID":
        return cls(hash=r.lp_bytes(), parts=PartSetHeader.decode(r))

    def __str__(self):
        return f"{self.hash.hex()[:12]}@{self.parts}"


ZERO_BLOCK_ID = BlockID()


@dataclass(frozen=True)
class Header:
    chain_id: str
    height: int
    time_ns: int                    # unix nanos; proposer's clock
    num_txs: int
    last_block_id: BlockID
    last_commit_hash: bytes
    data_hash: bytes
    validators_hash: bytes
    app_hash: bytes

    def hash(self) -> bytes:
        """Merkle-of-map over the fields (reference `types/block.go:178-193`).
        Empty for the pre-genesis header (no validators hash yet)."""
        if not self.validators_hash:
            return b""
        return merkle.root_of_map({
            "app": self.app_hash,
            "chain_id": self.chain_id.encode(),
            "data": self.data_hash,
            "height": u64(self.height),
            "last_block_id": self.last_block_id.encode(),
            "last_commit": self.last_commit_hash,
            "num_txs": u64(self.num_txs),
            "time": i64(self.time_ns),
            "validators": self.validators_hash,
        })

    def encode(self) -> bytes:
        cid = self.chain_id.encode()
        return (lp_bytes(cid) + u64(self.height) + i64(self.time_ns) +
                u64(self.num_txs) + self.last_block_id.encode() +
                lp_bytes(self.last_commit_hash) + lp_bytes(self.data_hash) +
                lp_bytes(self.validators_hash) + lp_bytes(self.app_hash))

    @classmethod
    def decode(cls, r: Reader) -> "Header":
        return cls(chain_id=r.lp_bytes().decode(), height=r.u64(),
                   time_ns=r.i64(), num_txs=r.u64(),
                   last_block_id=BlockID.decode(r),
                   last_commit_hash=r.lp_bytes(), data_hash=r.lp_bytes(),
                   validators_hash=r.lp_bytes(), app_hash=r.lp_bytes())


# A present precommit's record on the wire without its block id: marker
# (1), address (4 + 20), index (4), height (8), round (4), type (1) and
# signature (4 + 64).  The block id sits at _OFF_BID, the signature last.
# A nil precommit's entry is its marker alone, one byte of 0.
_REC_FIXED = 110
_OFF_ADDR, _OFF_INDEX, _OFF_HRT, _OFF_BID = 5, 25, 29, 42
_REC_HEAD = u8(1) + u32(20)
_SIG_PREFIX = u32(64)
# The most entries the walk of a body with nil entries takes on: upstream's
# `MaxVotesCount` (types/vote_set.go, v0.31 on), its own guard against a
# count a peer made up.  The walk keeps a position a nil entry, so a
# 32 MB message of zeros would otherwise cost it gigabytes; a longer
# commit decodes vote by vote, at the cost it always had.
_MAX_WALKED = 10_000


@lru_cache(maxsize=32)
def _pinned(n: int, bid_len: int) -> tuple[int, int]:
    """A regular body of n records read as ONE big-endian integer: the
    mask of its pinned bytes (all but the address and signature columns)
    and what its `validator_index` columns read when every vote is
    present (0 .. n-1), every other byte zero."""
    width = _REC_FIXED + bid_len
    mask = bytearray(b"\xff" * width)
    mask[_OFF_ADDR:_OFF_INDEX] = bytes(20)
    mask[-64:] = bytes(64)
    index = b"".join(bytes(_OFF_INDEX) + u32(i) + bytes(width - _OFF_HRT)
                     for i in range(n))
    return (int.from_bytes(bytes(mask) * n, "big"),
            int.from_bytes(index, "big"))


def _positions_pinned(absent: tuple, n: int, bid_len: int) -> int:
    """What the `validator_index` columns of the present records read
    when, of n entries, those at `absent` (ascending) are nil, as
    `_pinned`'s second integer: each record's rank among the present
    ones plus the nil entries before it, its POSITION.  A record is held
    to where it sits in the bytes, never placed by what its own index
    field says: a marker swapped with a record, or two records swapped
    whole, would otherwise mend themselves.

    One pass, linear in the body whatever the bytes: the records of the
    run after the k-th nil entry all sit k places past their ranks, so
    the shifts are (nils + 1) runs of one record's pattern, joined and
    converted ONCE.  The bytes are a peer's to choose and nothing is
    verified yet: an integer of the body's size a nil entry would let
    one message of alternating markers hold a receive thread for an
    hour."""
    tail = bytes(_REC_FIXED + bid_len - _OFF_HRT)
    shifts, prev = [], -1
    for k, p in enumerate(absent + (n,)):
        shifts.append((bytes(_OFF_INDEX) + u32(k) + tail) * (p - prev - 1))
        prev = p
    return (_pinned(n - len(absent), bid_len)[1]
            + int.from_bytes(b"".join(shifts), "big"))


@lru_cache(maxsize=32)
def _columns(n: int, bid_len: int) -> struct.Struct:
    """Unpacks a commit's block id, count and n records (no nil marker
    among them) into 2n columns, an address then a signature a record,
    in one C call."""
    return struct.Struct(f"{bid_len + 4}x" + (
        f"{_OFF_ADDR}x20s{_REC_FIXED + bid_len - _OFF_INDEX - 64}x64s" * n))


def _irregular(wire: bytes, n: int, bid_len: int,
               index: int | None = None) -> str | None:
    """None when the n records of a commit (`wire` after its block id
    and count, no nil marker among them) are REGULAR: every record of
    the one width, every vote's (height, round, type, block id) the
    first's and its block id the commit's, every `validator_index` its
    position: 0 .. n-1 for a body with every vote present, and for the
    present records of a body with nil entries what `index` reads (the
    positions they sit at among ALL the entries, not their ranks).
    Such records parse to the same votes under the sequential decoder
    (each length prefix is pinned, so each record ends where the next
    entry starts).  Otherwise the reason, in a word, and the caller
    decodes vote by vote.

    The records are compared as one big integer, an AND and an `==`: a
    numpy compare of 18,600 bytes drops the GIL, and a receive thread
    that dropped it beside the apply thread waits up to a switch
    interval to get it back (PERF.md, PR 34)."""
    width = _REC_FIXED + bid_len
    body = bid_len + 4
    if len(wire) - body != n * width:
        return "length"
    sig_at = _OFF_BID + bid_len
    first = bytearray(wire[body:body + width])
    if (first[:_OFF_ADDR] != _REC_HEAD
            or first[_OFF_BID:sig_at] != wire[:bid_len]
            or first[sig_at:sig_at + 4] != _SIG_PREFIX):
        return "record"
    first[_OFF_ADDR:_OFF_HRT] = bytes(24)       # address and index
    first[-64:] = bytes(64)
    mask, ranks = _pinned(n, bid_len)
    if index is None:
        index = ranks
    if (int.from_bytes(memoryview(wire)[body:], "big") & mask
            != int.from_bytes(bytes(first) * n, "big") | index):
        return "votes"
    return None


def _nil_entries(buf: bytes, at: int, n: int, width: int) -> tuple | None:
    """The walk of a body that is not n full records, by its marker
    bytes alone: 0 is a nil entry of one byte, 1 a record of `width`
    bytes.  Returns (the nil entries' positions among the n, their
    offsets in `buf`, the body's end), or None where a marker is
    neither, the body runs past the buffer, no record is present or n
    is past `_MAX_WALKED`: the walk may have lost its footing on a
    record of another width, so it refuses nothing itself and the
    caller decodes vote by vote."""
    if n > _MAX_WALKED:
        return None
    absent, nil_at = [], []
    try:
        for i in range(n):
            marker = buf[at]
            if marker == 1:
                at += width
            elif marker == 0:
                absent.append(i)
                nil_at.append(at)
                at += 1
            else:
                return None
    except IndexError:
        return None
    if at > len(buf) or len(absent) == n:
        return None
    return tuple(absent), nil_at, at


def _wire_with_nil_entries(buf: bytes, start: int, n: int,
                           bid_len: int) -> tuple | None:
    """A commit (at `buf[start:]`: block id, count n, body) whose body is
    NOT n full records, kept in its bytes all the same: the body walked
    once by its marker bytes, and its present records, taken as the runs
    between the nil markers, held to what `_irregular` holds a full
    body to.  Returns (the commit's bytes, nil markers included; the nil
    entries' positions; their offsets in those bytes), or None, and the
    caller decodes vote by vote."""
    walked = _nil_entries(buf, start + bid_len + 4, n, _REC_FIXED + bid_len)
    if walked is None:
        return None
    absent, nil_at, end = walked
    wire = bytes(buf[start:end])
    nil_at = tuple(at - start for at in nil_at)
    if _irregular(without(wire, nil_at, 1), n - len(absent), bid_len,
                  _positions_pinned(absent, n, bid_len)) is not None:
        return None
    return wire, absent, nil_at


def _first_present(absent: tuple) -> int:
    """The position of the first entry that is not nil: the nil entries
    before it, each one byte on the wire."""
    first = 0
    for p in absent:
        if p != first:
            break
        first += 1
    return first


class Commit:
    """+2/3 precommits for one block (reference `types/block.go:288-354`).

    `precommits` is validator-index-aligned with the validator set that
    signed it; absent votes are None.

    One type, two backings.  A commit built from votes holds the list.
    A commit decoded from the wire whose present records are regular
    (the common case: see `_irregular`) stays in the bytes it was read
    from, nil entries and all (upstream's nil precommit, a marker byte
    of 0 and nothing after it: nearly every commit of a live chain
    holds some): `encode()` is those bytes, the signature columns
    joined are the verify plane's `sigs[N, 64]`, the nil entries'
    positions say which members the N lanes are, and the `Vote` objects
    are made when somebody asks for `precommits`.  A full commit is the
    case of no nil entry.  Any other body decodes vote by vote as
    before.  Like `Block`, a commit is a value object: nobody edits one
    that was decoded.
    """

    def __init__(self, block_id: BlockID,
                 precommits: list[Vote | None] | None = None):
        self.block_id = block_id
        self._votes = precommits
        # wire-backed: the bytes, the entry count, the nil entries'
        # positions among the entries and offsets in the bytes (both
        # empty in a full commit), the unpacker of the present records'
        # address and signature columns, their (height, round, type)
        self._wire: bytes | None = None
        self._n = 0
        self._absent: tuple = ()
        self._nil_at: tuple = ()
        self._cols: struct.Struct | None = None
        self._hrt = (0, 0, 0)
        self._hash: bytes | None = None
        self._bit_array: list[bool] | None = None

    def _unpacked(self) -> tuple:
        """A wire-backed commit's present records as columns: an address
        then a signature a record, in entry order."""
        wire = self._wire
        if self._absent:
            wire = without(wire, self._nil_at, 1)
        return self._cols.unpack(wire)

    @property
    def precommits(self) -> list[Vote | None]:
        votes = self._votes
        if votes is None:
            cols = self._unpacked()
            height, round_, type_ = self._hrt
            bid = self.block_id
            nil = set(self._absent)
            votes = [None] * self._n
            for i, addr, sig in zip(
                    (i for i in range(self._n) if i not in nil),
                    cols[0::2], cols[1::2]):
                votes[i] = Vote(validator_address=addr, validator_index=i,
                                height=height, round=round_, type=type_,
                                block_id=bid, signature=sig)
            self._votes = votes
        return votes

    def wire_columns(self) -> tuple | None:
        """(the present records' addresses joined, their signatures
        joined, height, round, type, the nil entries' positions) of a
        wire-backed commit, in entry order; None for a commit that
        holds votes.  The positions are an empty tuple for a full
        commit; a present record's member is its position among ALL the
        entries.  Bytes and no arrays: a numpy copy of more than 500
        elements drops the GIL, and the look-ahead that dropped it beside
        the apply thread waits to get it back (PERF.md, PR 34)."""
        if self._wire is None:
            return None
        cols = self._unpacked()
        return ((b"".join(cols[0::2]), b"".join(cols[1::2])) + self._hrt
                + (self._absent,))

    def wire_backed(self) -> bool:
        """Whether the commit is still the bytes it was decoded from."""
        return self._wire is not None

    def __eq__(self, other):
        if not isinstance(other, Commit):
            return NotImplemented
        return (self.block_id == other.block_id
                and self.precommits == other.precommits)

    __hash__ = None

    def __repr__(self):
        return (f"Commit(block_id={self.block_id!r}, "
                f"precommits={self.precommits!r})")

    def height(self) -> int:
        if self._wire is not None:
            return self._hrt[0]
        for v in self._votes:
            if v is not None:
                return v.height
        return 0

    def round(self) -> int:
        if self._wire is not None:
            return self._hrt[1]
        for v in self._votes:
            if v is not None:
                return v.round
        return 0

    def size(self) -> int:
        if self._wire is not None:
            return self._n
        return len(self._votes)

    def num_sigs(self) -> int:
        if self._wire is not None:
            return self._n - len(self._absent)
        return sum(1 for v in self._votes if v is not None)

    def is_commit(self) -> bool:
        return self.size() > 0

    def bit_array(self) -> list[bool]:
        if self._bit_array is None:
            if self._wire is not None:
                bits = [True] * self._n
                for p in self._absent:
                    bits[p] = False
            else:
                bits = [v is not None for v in self._votes]
            self._bit_array = bits
        return self._bit_array

    def hash(self) -> bytes:
        """Merkle over the precommit signatures, a nil entry's leaf empty
        (reference `types/block.go:345-354`)."""
        if self._hash is None:
            if self._wire is not None:
                items = list(self._unpacked()[1::2])
                if self._absent:        # one pass, however many are nil
                    sigs = iter(items)
                    items = [next(sigs) if present else b""
                             for present in self.bit_array()]
            else:
                items = [(v.signature if v is not None else b"")
                         for v in self._votes]
            self._hash = merkle.root(items)
        return self._hash

    def validate_basic(self) -> None:
        """Structural checks (reference `types/block.go:307-331`)."""
        if self.block_id.is_zero():
            raise ValueError("commit with zero block id")
        from tendermint_tpu.types.canonical import TYPE_PRECOMMIT
        if self._wire is not None:
            # one (height, round, type) for all by construction: the
            # first present vote's is every vote's
            if self._hrt[2] != TYPE_PRECOMMIT:
                raise ValueError(f"commit vote {_first_present(self._absent)}"
                                 " is not a precommit")
            return
        if not self._votes:
            raise ValueError("commit with no precommits")
        height, round_ = self.height(), self.round()
        for i, v in enumerate(self._votes):
            if v is None:
                continue
            if v.type != TYPE_PRECOMMIT:
                raise ValueError(f"commit vote {i} is not a precommit")
            if v.height != height or v.round != round_:
                raise ValueError(f"commit vote {i} has wrong height/round")

    def encode(self) -> bytes:
        if self._wire is not None:
            return self._wire
        out = self.block_id.encode() + u32(len(self._votes))
        for v in self._votes:
            if v is None:
                out += u8(0)
            else:
                out += u8(1) + v.encode()
        return out

    @classmethod
    def decode(cls, r: Reader) -> "Commit":
        start = r.pos
        block_id = BlockID.decode(r)
        bid_len = r.pos - start
        n = r.u32()
        if n == 0:
            return cls(block_id=block_id, precommits=[])
        end = r.pos + n * (_REC_FIXED + bid_len)
        wire = bytes(r.buf[start:end])
        reason = _irregular(wire, n, bid_len)
        nils = nil_at = ()
        if reason is not None:
            # not n full records: the same form with nil entries, where
            # the present records pass the same test
            kept = _wire_with_nil_entries(r.buf, start, n, bid_len)
            if kept is not None:
                wire, nils, nil_at = kept
                end, reason = start + len(wire), None
        if reason is None:
            r.pos = end
            commit = cls(block_id=block_id)
            commit._wire = wire
            commit._n = n
            commit._absent, commit._nil_at = nils, nil_at
            commit._cols = _columns(n - len(nils), bid_len)
            # of the first PRESENT record, which every other was held to
            at = bid_len + 4 + _first_present(nils) + _OFF_HRT
            hrt = wire[at:at + 13]
            commit._hrt = (int.from_bytes(hrt[:8], "big"),
                           int.from_bytes(hrt[8:12], "big"), hrt[12])
            REGISTRY.commits_decoded_wire.inc()
            if nils:
                # one bare instant a commit that kept its bytes through
                # nil entries; a full commit writes nothing
                REGISTRY.commits_decoded_wire_absent.inc()
                REGISTRY.commit_precommits_absent.inc(len(nils))
                tracing.instant("commit.wire_absent", height=commit._hrt[0],
                                absent=len(nils))
            return commit
        votes: list[Vote | None] = []
        t0 = time.perf_counter()
        for i in range(n):
            marker = r.u8()
            if marker > 1:
                # go-wire's pointer byte is 0 or 1: read as a truth
                # value, two byte strings would decode to one commit
                raise ValueError(f"commit entry {i}: marker byte {marker}")
            votes.append(Vote.decode(r) if marker else None)
        # one bare record a commit decoded vote by vote (as
        # `block.txs_hash` below: a `perf_counter` pair and a ring write)
        tracing.RECORDER.record("commit.decode.votes",
                                tracing.perf_to_epoch(t0),
                                time.perf_counter() - t0, None,
                                cat=tracing.CAT_NONE)
        commit = cls(block_id=block_id, precommits=votes)
        REGISTRY.commits_decoded_objects.inc()
        absent = n - commit.num_sigs()
        if absent:
            # upstream's nil entries beside something else that is
            # irregular (or none present): `_irregular` trips over the
            # body's length first, which says nothing of why it is short
            REGISTRY.commit_precommits_absent.inc(absent)
            reason = "absent"
        tracing.instant("commit.object_form", height=commit.height(),
                        reason=reason)
        return commit


EMPTY_COMMIT = Commit(block_id=ZERO_BLOCK_ID, precommits=[])


@dataclass
class Block:
    header: Header
    txs: list[bytes]
    last_commit: Commit

    _hash: bytes | None = field(default=None, repr=False, compare=False)
    # blocks are value objects: the serialization is cached (and seeded
    # with the wire bytes on decode) so fast-sync's part-set re-hash does
    # not re-encode a 100-vote commit per block
    _encoded: bytes | None = field(default=None, repr=False, compare=False)

    @classmethod
    def make(cls, chain_id: str, height: int, time_ns: int, txs: list[bytes],
             last_commit: Commit, last_block_id: BlockID,
             validators_hash: bytes, app_hash: bytes) -> "Block":
        """Assemble a block with derived hashes
        (reference `types/block.go:31-50` MakeBlock)."""
        header = Header(
            chain_id=chain_id, height=height, time_ns=time_ns,
            num_txs=len(txs), last_block_id=last_block_id,
            last_commit_hash=(last_commit.hash() if last_commit.is_commit()
                              else b""),
            data_hash=txs_hash(txs), validators_hash=validators_hash,
            app_hash=app_hash)
        return cls(header=header, txs=list(txs), last_commit=last_commit)

    def hash(self) -> bytes:
        if self._hash is None:
            self._hash = self.header.hash()
        return self._hash

    @property
    def height(self) -> int:
        return self.header.height

    def validate_basic(self) -> None:
        """Structural self-consistency (reference `types/block.go:53-90`)."""
        h = self.header
        if h.height < 1:
            raise ValueError("block height < 1")
        if h.num_txs != len(self.txs):
            raise ValueError("num_txs mismatch")
        # one bare record a block around the tx Merkle root (1,000 leaves
        # in a full block); bookkeeping (CAT_NONE) like the apply stages
        # it nests under, so the window histograms read as before
        t0 = time.perf_counter()
        data_hash = txs_hash(self.txs)
        tracing.RECORDER.record("block.txs_hash", tracing.perf_to_epoch(t0),
                                time.perf_counter() - t0, None,
                                cat=tracing.CAT_NONE)
        if h.data_hash != data_hash:
            raise ValueError("data hash mismatch")
        if h.height == 1:
            if self.last_commit.is_commit():
                raise ValueError("first block must have empty last commit")
            if h.last_commit_hash:
                raise ValueError("first block last_commit_hash must be empty")
        else:
            if h.last_commit_hash != self.last_commit.hash():
                raise ValueError("last_commit_hash mismatch")
            self.last_commit.validate_basic()

    def encode(self) -> bytes:
        if self._encoded is None:
            out = self.header.encode()
            out += u32(len(self.txs))
            for tx in self.txs:
                out += lp_bytes(tx)
            out += self.last_commit.encode()
            self._encoded = out
        return self._encoded

    @classmethod
    def decode_bytes(cls, data: bytes) -> "Block":
        r = Reader(data)
        header = Header.decode(r)
        txs = [r.lp_bytes() for _ in range(r.u32())]
        last_commit = Commit.decode(r)
        r.expect_done()
        blk = cls(header=header, txs=txs, last_commit=last_commit)
        blk._encoded = data   # deterministic codec: decode/encode roundtrip
        return blk

    def make_part_set(self, part_size: int | None = None) -> PartSet:
        """Serialize and chunk (reference `types/block.go:115-117`)."""
        from tendermint_tpu.types.part_set import PART_SIZE
        return PartSet.from_data(self.encode(), part_size or PART_SIZE)

    def block_id(self, part_set: PartSet | None = None) -> BlockID:
        ps = part_set or self.make_part_set()
        return BlockID(hash=self.hash(), parts=ps.header)

    def __str__(self):
        return (f"Block#{self.header.height}"
                f"[{len(self.txs)} txs, hash {self.hash().hex()[:12]}]")

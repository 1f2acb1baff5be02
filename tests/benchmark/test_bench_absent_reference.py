"""The program against the plain reference of upstream's `VerifyCommit`
(`refcommit.py`) on chains the benchmark's builder makes FROM THE TRAFFIC
FILE'S OWN PLAN (`absent-precommits.json`: two members down, 20 precommits
in 1,000 late) at 10 and 16 validators over 130 commits, two 64-block
windows and a cut one: `verify_commits_batched` window by window and
`ValidatorSet.verify_commit` commit by commit accept what the reference
accepts and refuse what it refuses, at the same height, by the same class
of error and, for a signature, at the same member.  Both sides read a
served commit FROM THE SAME BYTES, the program through `Block.decode_bytes`
and the reference through a decoder of its own; a table of tampers edits
votes and encodes them again, a second one edits the served block's bytes
(a record one slot off its index beside a nil entry, a marker byte that is
neither 0 nor 1, a count that disagrees with the body), which no
re-encoding can express.  No test here says which decoder or lane builder
the program took: only what it read and what it answered.  And the commits
a node stores and serves after a fast-sync of such a chain hold the nil
entries where the chain does."""

import json
import os
import random
import struct
import sys
from types import SimpleNamespace

import pytest
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PrivateKey

import benchutil
from benchutil import REPO
from benchmark.lib import chain
from refcommit import (RefDecodeError, RefVote, key_of, members_of, ref_commit,
                       ref_commit_alone, ref_decode_block, ref_verify_commit,
                       ref_verify_window)
from tendermint_tpu.types import Block, Commit, Vote, canonical
from tendermint_tpu.types.codec import Reader
from tendermint_tpu.types.validator import (CommitPowerError,
                                            CommitSignatureError,
                                            verify_commits_batched)
from tendermint_tpu.utils.metrics import REGISTRY

with open(os.path.join(REPO, "benchmark", "traffic",
                       "absent-precommits.json")) as _f:
    MIX = json.load(_f)
PLAN, BLOCK = MIX["absent"], MIX["block"]
CHAIN_ID, SEED = "bench-absent-ref", 2**31 + 44
N_COMMITS = 130                    # 64 + 64 + 2: the last window is cut
WINDOW = 64
SIZES = (10, 16)


def _build(n_vals: int, n_blocks: int = N_COMMITS + 1):
    seeds, vs = chain.valset_at(SEED, n_vals, None, 1)
    with chain.Signers(seeds, 0) as sg:
        built = chain.build_chain(CHAIN_ID, seeds, vs, n_blocks, BLOCK, SEED,
                                  sg, keep_objects=True, absent=PLAN)
    return SimpleNamespace(built=built, vs=vs, seeds=seeds, n=n_vals,
                           members=members_of(vs))


@pytest.fixture(scope="module")
def chains():
    return {n: _build(n) for n in SIZES}


@pytest.fixture(autouse=True)
def native_backend():
    from tendermint_tpu.crypto import backend as cb
    old = cb._current
    cb.set_backend("native")
    yield
    cb._current = old


def served_items(c) -> list[tuple]:
    """[(block id, height, the BYTES of the successor block, which carries
    the commit of the height)] for heights 1 .. N_COMMITS: what a peer
    serves, and what both sides read."""
    built = c.built
    items = []
    for h in range(1, N_COMMITS + 1):
        block, ps, _seen = built["objects"][h - 1]
        items.append((block.block_id(ps), h, built["encoded"][h]))
    return items


def windows(items: list[tuple]) -> list[list[tuple]]:
    return [items[i:i + WINDOW] for i in range(0, len(items), WINDOW)]


def program_item(item) -> tuple:
    """The item as the program has it in a window: a served block's bytes
    through `Block.decode_bytes` (which raises a `ValueError` on a bad
    block), a `Commit` a test built as it stands."""
    bid, h, served = item
    if isinstance(served, Commit):
        return item
    return (bid, h, Block.decode_bytes(served).last_commit)


def reference_item(item) -> tuple:
    """The same item as the reference has it: the records its own
    decoder reads in the same bytes (or the decode error), and of a
    `Commit` a test built, in its `encode()`."""
    bid, h, served = item
    if isinstance(served, Commit):
        return (bid, h, ref_commit_alone(served.encode()))
    return (bid, h, ref_commit(served))


def served_commit(item) -> Commit:
    return program_item(item)[2]


def _present(commit) -> list[int]:
    """The positions that hold a precommit, of a `Commit` or of the
    reference's records."""
    entries = (commit.precommits if isinstance(commit, Commit)
               else commit.entries)
    return [i for i, v in enumerate(entries) if v is not None]


def _member(refs: list[tuple], height: int, lane: int) -> int:
    """A signature error's lane (a position among the precommits that are
    there) turned into the member it belongs to, by the REFERENCE's
    reading of which entries are there."""
    commit = next(c for _b, h, c in refs if h == height)
    return _present(commit)[lane]


def in_words(call, refs: list[tuple], height: int):
    """What the program's call says, in the reference's words.  The
    structural refusals of `ValidatorSet.verify_commit`, and the block
    decoder's, are bare ValueErrors that name no height: `height` then."""
    try:
        call()
    except CommitSignatureError as e:
        return ("signature", e.height, _member(refs, e.height, e.lane))
    except CommitPowerError as e:
        return ("power", e.height)
    except (ValueError, IndexError) as e:
        return ("format", getattr(e, "height", height))
    return None


def window_verdict(c, items: list[tuple], refs: list[tuple]):
    """`verify_commits_batched` on the window as a node has it.  A block
    is decoded where it is received, and one that does not decode is a
    bad block from its peer (`blockchain/reactor.py` catches `ValueError`
    and `IndexError`) that never reaches a window: the verdict is the
    one on the commits before it, then `format` at its height."""
    window, bad = [], None
    for item in items:
        bad = in_words(lambda: window.append(program_item(item)), refs,
                       item[1])
        if bad is not None:
            break
    verdict = in_words(
        lambda: verify_commits_batched(c.vs, CHAIN_ID, window), refs, None)
    return bad if verdict is None else verdict


def single_verdict(c, item, ref):
    """`ValidatorSet.verify_commit` on one commit as it was served."""
    def call():
        bid, h, commit = program_item(item)
        c.vs.verify_commit(CHAIN_ID, bid, h, commit)
    return in_words(call, [ref], item[1])


def verdicts(c, items: list[tuple], at: int) -> tuple:
    """(the reference's, the program's) verdicts on the window `items`,
    then on the one commit at index `at` of it."""
    refs = [reference_item(item) for item in items]
    return (ref_verify_window(CHAIN_ID, c.members, refs),
            window_verdict(c, items, refs),
            ref_verify_commit(CHAIN_ID, c.members, *refs[at]),
            single_verdict(c, items[at], refs[at]))


def both(c, items: list[tuple], at: int):
    """The one verdict of `verdicts`: each pair has to agree."""
    ref_w, sys_w, ref_1, sys_1 = got = verdicts(c, items, at)
    assert ref_w == sys_w and ref_1 == sys_1, got
    assert ref_w == ref_1
    return ref_w


def same_records(ref, commit: Commit) -> bool:
    """Whether the program's decoder read what the reference's read:
    entry by entry (index, address, height, round, type, block id,
    signature), nil entries at the same positions, and the commit's own
    block id."""
    if (key_of(commit.block_id) != ref.block_id
            or commit.size() != len(ref.entries)):
        return False
    return all(
        (e is None) == (v is None) and (e is None or e == RefVote(
            v.validator_address, v.validator_index, v.height, v.round,
            v.type, key_of(v.block_id), v.signature))
        for e, v in zip(ref.entries, commit.precommits))


def edited(commit, edit) -> Commit:
    """The commit with `edit(votes)` applied to a copy of its votes, as a
    peer would send it: encoded and decoded again."""
    votes = list(commit.precommits)
    edit(votes)
    out = Commit.decode(Reader(Commit(block_id=commit.block_id,
                                      precommits=votes).encode()))
    return out


def with_commit(items, at: int, commit: Commit) -> list[tuple]:
    """The window with the served block at `at` carrying `commit`: its
    `encode()` where the reference's walk finds the block's commit (the
    last thing in a block), so both sides read the tampered commit from
    the same bytes."""
    served = items[at][2]
    return with_bytes(items, at, served[:ref_decode_block(
        served).commit_at] + commit.encode())


def with_bytes(items, at: int, served) -> list[tuple]:
    bid, h, _served = items[at]
    return items[:at] + [(bid, h, served)] + items[at + 1:]


def signed_by(c, position: int, vote: Vote) -> Vote:
    """`vote` as the member at `position` of the set would have signed
    it (OpenSSL, the builder's key for that member)."""
    val = c.vs.validators[position]
    out = Vote(**{**vote.__dict__, "validator_index": position,
                  "validator_address": val.address, "signature": b""})
    key = Ed25519PrivateKey.from_private_bytes(c.seeds[position])
    return Vote(**{**out.__dict__,
                   "signature": key.sign(out.sign_bytes(CHAIN_ID))})


# -- sound commits -------------------------------------------------------------

@pytest.mark.parametrize("n_vals", SIZES)
def test_the_chain_is_the_plans_and_every_commit_holds_nil_entries(chains,
                                                                   n_vals):
    """What the benchmark cares about in a served commit, whatever form
    the program keeps it in: its block id, an entry a member, the nil
    entries where the plan puts them, and its `encode()` the bytes it
    was served in."""
    c = chains[n_vals]
    most = -(-n_vals // 3) - 1
    for item in served_items(c):
        bid, h, served = item
        commit = served_commit(item)
        silent = chain.absent_at(SEED, n_vals, None, PLAN, h)
        assert 2 <= len(silent) <= most        # the two that are down
        assert commit.num_sigs() == c.built["signed"][h - 1] == \
            n_vals - len(silent)
        assert commit.size() == n_vals and commit.block_id == bid
        assert commit.encode() == served[ref_decode_block(served).commit_at:]
        keys = [chain.pub_of(chain.val_seed(SEED, i)) for i in silent]
        assert {i for i, v in enumerate(commit.precommits) if v is None} == \
            {i for i, m in enumerate(c.members) if m[1] in keys}


@pytest.mark.parametrize("n_vals", SIZES)
def test_the_references_decoder_reads_what_the_programs_reads(chains,
                                                              n_vals):
    """All 130 served commits, through `refcommit.py`'s own walk over the
    block's bytes and through `Block.decode_bytes`: the same (index,
    address, height, round, type, block id, signature) entry by entry,
    nil entries at the same positions; the block's height and where its
    commit starts are read right (the commit re-encodes to that slice);
    and every entry's marker byte is where the walk says it is."""
    c = chains[n_vals]
    nil_entries = 0
    for bid, h, served in served_items(c):
        blk = ref_decode_block(served)
        block = Block.decode_bytes(served)
        assert blk.height == block.height == h + 1
        assert blk.commit.block_id == key_of(bid)
        assert same_records(blk.commit, block.last_commit)
        assert len(blk.entry_at) == n_vals + 1
        assert blk.entry_at[-1] == len(served)
        assert blk.count_at + 4 == blk.entry_at[0]
        assert [served[at] for at in blk.entry_at[:-1]] == [
            int(e is not None) for e in blk.commit.entries]
        nil_entries += blk.commit.entries.count(None)
    assert nil_entries == N_COMMITS * n_vals - sum(
        c.built["signed"][:N_COMMITS]) >= 2 * N_COMMITS


def test_both_decoders_refuse_a_served_block_cut_short_anywhere(chains):
    """Every proper prefix of a served block, and the block with a byte
    more: no block to either decoder."""
    _bid, _h, served = served_items(chains[10])[17]
    for cut in list(range(len(served))) + [len(served) + 1]:
        short = (served + b"\0")[:cut]
        assert isinstance(ref_commit(short), RefDecodeError), cut
        with pytest.raises((ValueError, IndexError)):
            Block.decode_bytes(short)


@pytest.mark.parametrize("n_vals", SIZES)
def test_sound_commits_are_accepted_by_both_by_window_and_by_commit(chains,
                                                                    n_vals):
    c = chains[n_vals]
    items = served_items(c)
    wins = windows(items)
    assert [len(w) for w in wins] == [64, 64, 2]
    sigs0 = REGISTRY.sigs_verified.value
    for w in wins:
        refs = [reference_item(item) for item in w]
        assert ref_verify_window(CHAIN_ID, c.members, refs) is None
        assert window_verdict(c, w, refs) is None
    held = sum(c.built["signed"][:N_COMMITS])
    # a nil entry is not verified: the lanes are the precommits held
    assert REGISTRY.sigs_verified.value - sigs0 == held < N_COMMITS * n_vals
    for item in items:
        ref = reference_item(item)
        assert ref_verify_commit(CHAIN_ID, c.members, *ref) is None
        assert single_verdict(c, item, ref) is None
    assert REGISTRY.sigs_verified.value - sigs0 == 2 * held


# -- tampered commits: votes edited and encoded again ------------------------------

def _forge(c, commit):
    k = _present(commit)[1]

    def edit(votes):
        sig = bytearray(votes[k].signature)
        sig[5] ^= 0x40
        votes[k] = Vote(**{**votes[k].__dict__, "signature": bytes(sig)})
    return edited(commit, edit), ("signature", k)


def _drop_to_two_thirds(c, commit):
    """Signed entries turned nil until no more than 2/3 of the power is
    left: the most that +2/3 refuses."""
    keep = (2 * c.n) // 3                    # 3 x keep <= 2 x n

    def edit(votes):
        for i in _present(commit)[keep:]:
            votes[i] = None
    return edited(commit, edit), ("power", None)


def _fill_verbatim(c, commit):
    """A nil entry filled with another member's vote as it stands."""
    hole = next(i for i, v in enumerate(commit.precommits) if v is None)
    donor = _present(commit)[0]

    def edit(votes):
        votes[hole] = votes[donor]
    return edited(commit, edit), ("format", None)


def _fill_readdressed(c, commit):
    """A nil entry filled with another member's signature under the
    silent member's own index and address: it verifies under no key of
    that position."""
    hole = next(i for i, v in enumerate(commit.precommits) if v is None)
    donor = _present(commit)[0]

    def edit(votes):
        votes[hole] = Vote(**{**votes[donor].__dict__,
                              "validator_index": hole,
                              "validator_address":
                                  c.vs.validators[hole].address})
    return edited(commit, edit), ("signature", hole)


def _another_round(c, commit):
    k = _present(commit)[2]

    def edit(votes):
        votes[k] = Vote(**{**votes[k].__dict__, "round": 3})
    return edited(commit, edit), ("format", None)


TAMPERS = {"forged-signature": _forge,
           "two-thirds-and-no-more": _drop_to_two_thirds,
           "nil-filled-verbatim": _fill_verbatim,
           "nil-filled-readdressed": _fill_readdressed,
           "another-round": _another_round}
# (window, index in it) of the commit a tamper is made in: each the typical
# commit of these chains (the two that are down, nobody late)
AT = [(0, 17), (1, 63), (2, 1)]
AT_IDS = ["first-window", "second-window", "cut-window"]


@pytest.mark.parametrize("at", AT, ids=AT_IDS)
@pytest.mark.parametrize("tamper", sorted(TAMPERS))
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_tampered_commit_is_refused_by_both_alike(chains, n_vals, tamper,
                                                    at):
    c = chains[n_vals]
    win, k = at
    items = windows(served_items(c))[win]
    h, commit = items[k][1], served_commit(items[k])
    assert None in commit.precommits           # it has nil entries
    bad, (kind, member) = TAMPERS[tamper](c, commit)
    verdict = both(c, with_commit(items, k, bad), k)
    assert verdict == ((kind, h, member) if kind == "signature"
                       else (kind, h))
    if tamper == "two-thirds-and-no-more":
        assert 3 * bad.num_sigs() <= 2 * n_vals < 3 * (bad.num_sigs() + 1)


def test_exactly_two_thirds_of_the_power_is_not_more_than_two_thirds():
    """12 members of equal power: 8 signed is exactly 2/3, refused by
    both; 9 is accepted by both."""
    c = _build(12, 4)
    block, ps, _seen = c.built["objects"][1]
    item = [(block.block_id(ps), 2, c.built["encoded"][2])]
    commit = served_commit(item[0])
    assert commit.num_sigs() >= 9
    for keep, want in ((9, None), (8, ("power", 2))):
        def edit(votes):
            for i in _present(commit)[keep:]:
                votes[i] = None
        bad = edited(commit, edit)
        assert bad.num_sigs() == keep
        assert both(c, with_commit(item, 0, bad), 0) == want


# -- tampered commits: the served block's bytes edited -----------------------------
#
# A tamper takes the chain, the served block's bytes and the reference's
# walk over them (which says where each entry's marker byte is) and gives
# the bytes a peer sends instead, what both sides have to say to them
# (kind, member; kind None = accepted) and whether the reference's decoder
# itself refuses them.  Both sides are handed the same bytes.

def _entry(served: bytes, blk, k: int) -> bytes:
    return served[blk.entry_at[k]:blk.entry_at[k + 1]]


def _replaced(served: bytes, blk, k: int, new: bytes) -> bytes:
    """The block with its entry k replaced by the bytes `new`."""
    return served[:blk.entry_at[k]] + new + served[blk.entry_at[k + 1]:]


def _swapped(served: bytes, blk, j: int, k: int) -> bytes:
    """The block with its entries j < k each where the other was."""
    # the later one first: the offsets before it stand
    return _replaced(_replaced(served, blk, k, _entry(served, blk, j)),
                     blk, j, _entry(served, blk, k))


def _with_byte(served: bytes, at: int, value: int) -> bytes:
    return served[:at] + bytes([value]) + served[at + 1:]


def _nil_before_a_record(blk) -> int:
    """The first nil entry that a precommit follows."""
    e = blk.commit.entries
    return next(k for k in range(len(e) - 1)
                if e[k] is None and e[k + 1] is not None)


def _after_a_nil(blk) -> int:
    """The first signed member AFTER a nil entry: the lane a presence
    map is most likely to misplace."""
    return _nil_before_a_record(blk) + 1


def _tallied(c, signed: int):
    """Accepted while the precommits that count hold more than 2/3 of the
    set's whole power (equal powers), else `power`."""
    return (None if 3 * signed > 2 * c.n else "power", None)


def _lp(b: bytes) -> bytes:
    return struct.pack(">I", len(b)) + b


def _record(vote: RefVote) -> bytes:
    """An entry that holds `vote`, byte for byte as a peer sends it."""
    block_hash, parts_total, parts_hash = vote.block_id
    return (b"\1" + _lp(vote.address) + struct.pack(
        ">IQIB", vote.index, vote.height, vote.round, vote.type)
        + _lp(block_hash) + struct.pack(">I", parts_total) + _lp(parts_hash)
        + _lp(vote.signature))


def _signed_for(c, vote: RefVote, block_id: tuple) -> bytes:
    """The member's entry with its precommit FOR `block_id`, validly
    signed (OpenSSL, the builder's key for that member)."""
    block_hash, parts_total, parts_hash = block_id
    key = Ed25519PrivateKey.from_private_bytes(c.seeds[vote.index])
    return _record(vote._replace(block_id=block_id, signature=key.sign(
        canonical.sign_bytes(CHAIN_ID, vote.type, vote.height, vote.round,
                             block_hash=block_hash, parts_hash=parts_hash,
                             parts_total=parts_total))))


def _other_block(blk) -> tuple:
    block_hash, parts_total, parts_hash = blk.commit.block_id
    return (bytes(b ^ 0x5a for b in block_hash), parts_total, parts_hash)


NIL_BLOCK = (b"", 0, b"")


def _nil_marker_swapped_with_its_successor(c, served, blk):
    """(a) [nil][record k + 1] sent as [record k + 1][nil]: a sound
    record one slot EARLY, under its own index."""
    k = _nil_before_a_record(blk)
    return _swapped(served, blk, k, k + 1), ("format", None), False


def _record_moved_behind_its_nil_successor(c, served, blk):
    """(b) the mirror: [record k][nil] sent as [nil][record k], a sound
    record one slot LATE."""
    e = blk.commit.entries
    k = next(k for k in range(len(e) - 1)
             if e[k] is not None and e[k + 1] is None)
    return _swapped(served, blk, k, k + 1), ("format", None), False


def _marker_2_on_a_nil_entry(c, served, blk):
    """(c) a marker byte that is neither 0 nor 1, where no vote follows."""
    k = _nil_before_a_record(blk)
    return _with_byte(served, blk.entry_at[k], 2), ("format", None), True


def _marker_2_before_a_sound_record(c, served, blk):
    """(c) and where one does: a decoder that reads the byte as a truth
    value takes the commit whole."""
    k = _after_a_nil(blk)
    return _with_byte(served, blk.entry_at[k], 2), ("format", None), True


def _count(c, served, blk, by: int):
    n = len(blk.commit.entries) + by
    return (served[:blk.count_at] + struct.pack(">I", n)
            + served[blk.count_at + 4:]), ("format", None), True


def _count_one_more(c, served, blk):
    """(d) the count one more than the entries the body holds."""
    return _count(c, served, blk, +1)


def _count_one_less(c, served, blk):
    """(d) and one less: the last entry is left over."""
    return _count(c, served, blk, -1)


def _signature_of_63_bytes(c, served, blk):
    """(e) a signature's length prefix 63, its last byte dropped: the
    records after it sit where the prefix says."""
    k = _after_a_nil(blk)
    rec = _entry(served, blk, k)
    assert rec[-68:-64] == struct.pack(">I", 64)
    return _replaced(served, blk, k, rec[:-68] + struct.pack(">I", 63)
                   + rec[-64:-1]), ("format", None), False


def _field(c, served, blk, field: str, value: int):
    """(f) one record's height, round or type another than the first's,
    in the bytes (not the first record's: the commit's are the first's)."""
    k = _after_a_nil(blk)
    vote = blk.commit.entries[k]
    assert k > _present(blk.commit)[0] and getattr(vote, field) != value
    return _replaced(served, blk, k, _record(
        vote._replace(**{field: value}))), ("format", None), False


def _another_height_in_the_bytes(c, served, blk):
    return _field(c, served, blk, "height", blk.height)


def _another_round_in_the_bytes(c, served, blk):
    return _field(c, served, blk, "round", 1)


def _a_prevote_in_the_bytes(c, served, blk):
    return _field(c, served, blk, "type", 1)


def _a_block_hash_of_31_bytes(c, served, blk):
    """A precommit whose block hash is neither empty nor 32 bytes has no
    sign bytes in the fixed layout: malformed."""
    k = _after_a_nil(blk)
    vote = blk.commit.entries[k]
    block_hash, parts_total, parts_hash = vote.block_id
    return _replaced(served, blk, k, _record(vote._replace(
        block_id=(block_hash[:31], parts_total, parts_hash)))), \
        ("format", None), False


def _one_signed_for(c, served, blk, block_id: tuple):
    """(g) one precommit validly signed for another block id: verified
    and not tallied."""
    k = _after_a_nil(blk)
    return _replaced(served, blk, k, _signed_for(
        c, blk.commit.entries[k], block_id)), _tallied(
            c, len(_present(blk.commit)) - 1), False


def _one_signed_for_another_block(c, served, blk):
    return _one_signed_for(c, served, blk, _other_block(blk))


def _one_signed_for_the_nil_block(c, served, blk):
    return _one_signed_for(c, served, blk, NIL_BLOCK)


def _too_many_signed_for_another_block(c, served, blk):
    """(g) every signature verifies, and no more than 2/3 of the power
    is for the block: every other precommit is for another block, or for
    the nil block, by turns."""
    keep = (2 * c.n) // 3                    # 3 x keep <= 2 x n
    others = list(enumerate(_present(blk.commit)[keep:]))
    for i, k in reversed(others):            # the offsets before k stand
        served = _replaced(served, blk, k, _signed_for(
            c, blk.commit.entries[k],
            NIL_BLOCK if i % 2 else _other_block(blk)))
    return served, ("power", None), False


def _nil_at(c, served, blk, ks):
    signed = len(_present(blk.commit)) - sum(
        blk.commit.entries[k] is not None for k in ks)
    for k in sorted(ks, reverse=True):
        served = _replaced(served, blk, k, b"\0")
    return served, (_tallied(c, signed) if signed else ("format", None)), \
        False


def _entry_0_nil(c, served, blk):
    """(h) a commit whose FIRST entry is nil."""
    assert blk.commit.entries[0] is not None
    return _nil_at(c, served, blk, [0])


def _last_entry_nil(c, served, blk):
    """(h) whose LAST entry is nil: the body ends in a marker byte."""
    assert blk.commit.entries[-1] is not None
    return _nil_at(c, served, blk, [c.n - 1])


def _every_entry_nil(c, served, blk):
    """(h) whose entries are ALL nil: no height to hold it to."""
    return _nil_at(c, served, blk, range(c.n))


def _two_records_swapped_whole(c, served, blk):
    """(i) two sound records, each at the other's position."""
    j, k = _present(blk.commit)[0], _after_a_nil(blk)
    assert j < k
    return _swapped(served, blk, j, k), ("format", None), False


def _forged_after_a_nil_entry(c, served, blk):
    """(j) a forged signature at the first signed member after a nil
    entry: refused AT THAT MEMBER."""
    k = _after_a_nil(blk)
    at = blk.entry_at[k + 1] - 64 + 5
    return _with_byte(served, at, served[at] ^ 0x40), ("signature", k), False


def _the_commits_own_block_id_altered(c, served, blk):
    """The commit's own block id another than its precommits': upstream's
    loop reads each precommit's and never the commit's, so every vote
    still counts.  (A form that kept one block id a commit and not one a
    record would sign the wrong bytes here.)"""
    at = blk.commit_at + 4
    return _with_byte(served, at, served[at] ^ 1), (None, None), False


BYTE_TAMPERS = {
    "nil-marker-swapped-with-its-successor":
        _nil_marker_swapped_with_its_successor,
    "record-moved-behind-its-nil-successor":
        _record_moved_behind_its_nil_successor,
    "marker-2-on-a-nil-entry": _marker_2_on_a_nil_entry,
    "marker-2-before-a-sound-record": _marker_2_before_a_sound_record,
    "count-one-more": _count_one_more,
    "count-one-less": _count_one_less,
    "signature-of-63-bytes": _signature_of_63_bytes,
    "another-height-in-the-bytes": _another_height_in_the_bytes,
    "another-round-in-the-bytes": _another_round_in_the_bytes,
    "a-prevote-in-the-bytes": _a_prevote_in_the_bytes,
    "a-block-hash-of-31-bytes": _a_block_hash_of_31_bytes,
    "one-signed-for-another-block": _one_signed_for_another_block,
    "one-signed-for-the-nil-block": _one_signed_for_the_nil_block,
    "too-many-signed-for-another-block": _too_many_signed_for_another_block,
    "entry-0-nil": _entry_0_nil,
    "last-entry-nil": _last_entry_nil,
    "every-entry-nil": _every_entry_nil,
    "two-records-swapped-whole": _two_records_swapped_whole,
    "forged-after-a-nil-entry": _forged_after_a_nil_entry,
    "the-commits-own-block-id-altered": _the_commits_own_block_id_altered,
}
# (window, index in it) of the commit a byte tamper is made in: as `AT`, but
# in the second window a commit with a late precommit more (7 of 10 and 13
# of 16 signed), so that a vote that counts not is met on both sides of 2/3
BYTE_AT = [(0, 17), (1, 3), (2, 1)]
# Where the PARENT's program (6df9d5b) departs from the reference, found
# by this table: the case, and what the program says there.  The repair
# is the program's and is owed by a later PR (PERF.md, section 7); a
# program that is repaired passes the case, one that departs in any other
# way fails it.
OWED = {
    "marker-2-before-a-sound-record":
        "`Commit.decode` reads an entry's marker byte as a truth value "
        "(`if r.u8()`, types/block.py:339): a marker of 2 before a sound "
        "record decodes as a vote that is there, and the commit is "
        "accepted; go-wire and the reference refuse the block",
}


@pytest.mark.parametrize("at", BYTE_AT, ids=AT_IDS)
@pytest.mark.parametrize("tamper", sorted(BYTE_TAMPERS))
@pytest.mark.parametrize("n_vals", SIZES)
def test_tampered_bytes_get_one_verdict_from_both(chains, n_vals, tamper, at):
    """Every case can be expressed in each of the three windows, the cut
    one of two commits too: a tamper is made inside ONE commit."""
    c = chains[n_vals]
    win, k = at
    items = windows(served_items(c))[win]
    _bid, h, served = items[k]
    blk = ref_decode_block(served)
    assert None in blk.commit.entries          # it has nil entries
    bad, (kind, member), no_block = BYTE_TAMPERS[tamper](c, served, blk)
    assert bad != served
    assert isinstance(ref_commit(bad), RefDecodeError) == no_block
    want = (None if kind is None else
            (kind, h, member) if kind == "signature" else (kind, h))
    got = verdicts(c, with_bytes(items, k, bad), k)
    if tamper in OWED and got == (want, None, want, None):
        pytest.xfail(OWED[tamper])
    ref_w, sys_w, ref_1, sys_1 = got
    assert ref_w == sys_w and ref_1 == sys_1, got
    assert ref_w == ref_1 == want
    if not no_block:
        # what decodes, decodes to the same records on both sides
        try:
            commit = Block.decode_bytes(bad).last_commit
        except (ValueError, IndexError):
            assert want == ("format", h)
        else:
            assert same_records(ref_commit(bad), commit)


@pytest.mark.parametrize("at", BYTE_AT, ids=AT_IDS)
@pytest.mark.parametrize("n_vals", SIZES)
def test_the_first_records_round_altered_is_refused_by_both_at_its_height(
        chains, n_vals, at):
    """The one place where the ORDER of the checks shows (found by
    flipping single bytes of served commits at random, 6,000 of them;
    every other flip got one verdict from both, but for a marker byte):
    the commit's round is its first record's, so with that record's
    round altered in the bytes upstream's loop, entry by entry, meets
    the first record's signature first (it no longer verifies), while a
    program that checks every entry's form before it verifies any
    signature meets the second record's round first.  Both refuse the
    commit at its height and blame the same peer; either class is right
    of the program, the reference's is upstream's."""
    c = chains[n_vals]
    win, k = at
    items = windows(served_items(c))[win]
    _bid, h, served = items[k]
    blk = ref_decode_block(served)
    first = _present(blk.commit)[0]
    vote = blk.commit.entries[first]
    bad = _replaced(served, blk, first, _record(vote._replace(round=1)))
    ref_w, sys_w, ref_1, sys_1 = verdicts(c, with_bytes(items, k, bad), k)
    assert ref_w == ref_1 == ("signature", h, first)
    assert sys_w == sys_1 and sys_w in (ref_w, ("format", h))


@pytest.mark.parametrize("n_vals", SIZES)
def test_random_edits_of_a_served_commit_get_one_verdict_from_both(chains,
                                                                   n_vals):
    """600 seeded edits of the commits of the first 12 heights, one a
    time: a byte set, a bit flipped, a byte dropped or put in, anywhere
    in the commit; an entry swapped with another, written over another,
    dropped, doubled, or taken from the commit of another height.  Both
    sides say the same of each, commit-wise, but for the two readings the
    cases above name: a marker byte that is neither 0 nor 1 before a
    sound record (`OWED`), and the first record's round, where the order
    of the checks shows."""
    c = chains[n_vals]
    items = served_items(c)[:12]
    rng = random.Random(SEED + n_vals)
    refused = owed = 0
    for _trial in range(600):
        k = rng.randrange(len(items))
        bid, h, served = items[k]
        blk = ref_decode_block(served)
        at = rng.randrange(blk.commit_at, len(served))
        i, j = rng.sample(range(n_vals), 2)
        entries = [_entry(served, blk, e) for e in range(n_vals)]
        edit = rng.choice(["set", "flip", "drop", "put", "swap", "over",
                           "less", "more", "other"])
        if edit == "set":
            bad = _with_byte(served, at, rng.choice([0, 1, 2, 255]))
        elif edit == "flip":
            bad = _with_byte(served, at, served[at] ^ (1 << rng.randrange(8)))
        elif edit == "drop":
            bad = served[:at] + served[at + 1:]
        elif edit == "put":
            bad = served[:at] + bytes([rng.choice([0, 1, 2])]) + served[at:]
        elif edit == "swap":
            bad = _swapped(served, blk, min(i, j), max(i, j))
        elif edit == "over":
            bad = _replaced(served, blk, i, entries[j])
        elif edit == "less":
            bad = _replaced(served, blk, i, b"")
        elif edit == "more":
            bad = _replaced(served, blk, i, entries[i] + entries[j])
        else:
            other = items[(k + 1) % len(items)][2]
            bad = _replaced(served, blk, i, _entry(
                other, ref_decode_block(other), i))
        if bad == served:
            continue
        item = (bid, h, bad)
        ref = reference_item(item)
        said = ref_verify_commit(CHAIN_ID, c.members, *ref)
        got = single_verdict(c, item, ref)
        refused += said is not None
        if (isinstance(ref[2], RefDecodeError) and got is None
                and bad[at] > 1 and at in blk.entry_at):
            owed += 1                          # `OWED`: the marker byte
            continue
        first = _present(blk.commit)[0]
        assert got == said or (got, said) == (
            ("format", h), ("signature", h, first)), (edit, at, i, j, h)
    assert refused > 400 and owed < 20


@pytest.mark.parametrize("tamper", [
    "nil-marker-swapped-with-its-successor",
    "record-moved-behind-its-nil-successor", "two-records-swapped-whole"])
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_decoder_that_places_a_record_by_its_index_field_is_caught(
        chains, monkeypatch, n_vals, tamper):
    """The comparison shown to fail: in the program's place a decoder
    that puts each record where its `validator_index` says (what a
    presence map filled from the records' own fields would do) and not
    where it sits in the bytes.  It mends a record that is one slot off
    and accepts the commit, window-wise and commit-wise, where the
    reference refuses it: `both` says so."""
    def placed_by_index(item):
        bid, h, served = item
        commit = Block.decode_bytes(served).last_commit
        votes = [None] * commit.size()
        for vote in commit.precommits:
            if vote is not None:
                votes[vote.validator_index] = vote
        return (bid, h, Commit(block_id=commit.block_id, precommits=votes))

    c = chains[n_vals]
    win, k = BYTE_AT[0]
    items = windows(served_items(c))[win]
    _bid, h, served = items[k]
    bad, _want, _no_block = BYTE_TAMPERS[tamper](
        c, served, ref_decode_block(served))
    changed = with_bytes(items, k, bad)
    assert both(c, changed, k) == ("format", h)
    monkeypatch.setattr(sys.modules[__name__], "program_item",
                        placed_by_index)
    refused, accepted = ("format", h), None
    assert verdicts(c, changed, k) == (refused, accepted, refused, accepted)
    with pytest.raises(AssertionError):
        both(c, changed, k)


@pytest.mark.parametrize("tamper", [
    "one-signed-for-another-block", "one-signed-for-the-nil-block",
    "entry-0-nil", "last-entry-nil"])
def test_a_vote_that_counts_not_is_met_on_both_sides_of_two_thirds(chains,
                                                                   tamper):
    """(g), (h): a precommit that is verified and not tallied, or one
    entry more nil, leaves the commit accepted in some of the cases above
    and short of +2/3 in others (10 validators, one late: 6 count)."""
    kinds = set()
    for n_vals in SIZES:
        for win, k in BYTE_AT:
            served = windows(served_items(chains[n_vals]))[win][k][2]
            kinds.add(BYTE_TAMPERS[tamper](
                chains[n_vals], served, ref_decode_block(served))[1][0])
    assert kinds == {None, "power"}


@pytest.mark.parametrize("forged", [False, True], ids=["sound", "forged"])
@pytest.mark.parametrize("n_vals", SIZES)
def test_a_wire_commit_and_its_object_form_side_by_side_in_one_window(
        chains, n_vals, forged):
    """The commit of one height with its nil entries signed after all (a
    commit every member reached in time), once as `Commit.decode` leaves
    it, in its wire bytes, and once holding votes, between the chain's
    own commits: both agree with the reference; with one signature of
    the WIRE commit forged, both refuse it at that member."""
    c = chains[n_vals]
    items = windows(served_items(c))[0]
    k = 30
    bid, h, _served = items[k]
    commit = served_commit(items[k])
    some = commit.precommits[_present(commit)[0]]

    def fill(votes):
        for i, v in enumerate(votes):
            if v is None:
                votes[i] = signed_by(c, i, some)
        if forged:
            sig = bytearray(votes[4].signature)
            sig[0] ^= 1
            votes[4] = Vote(**{**votes[4].__dict__, "signature": bytes(sig)})
    wire = edited(commit, fill)
    assert wire.wire_backed() and wire.num_sigs() == n_vals
    objects = Commit(block_id=wire.block_id,
                     precommits=list(wire.precommits))
    assert not objects.wire_backed() and objects == wire
    window = items[:k] + [(bid, h, wire), (bid, h, objects)] + items[k + 1:]
    refs = [reference_item(item) for item in window]
    want = ("signature", h, 4) if forged else None
    assert ref_verify_window(CHAIN_ID, c.members, refs) == want
    assert window_verdict(c, window, refs) == want
    for item, ref in zip(window[k:k + 2], refs[k:k + 2]):
        assert ref_verify_commit(CHAIN_ID, c.members, *ref) == want
        assert single_verdict(c, item, ref) == want


# -- what the node stores and serves -----------------------------------------------

def test_the_stored_and_served_commits_keep_their_nil_entries(chains):
    """A CPU fast-sync of the 16-validator chain through the real pool,
    reactor and `apply_window`: at every height the seen commit and the
    successor's LastCommit the store loads back hold a nil entry exactly
    where the chain does, and `/block` and `/commit` answer the count."""
    from tendermint_tpu.rpc.routes import Routes
    c = chains[16]
    built, tip = c.built, N_COMMITS
    bc = benchutil.fast_sync(built, CHAIN_ID, "kvstore", tip)
    routes = Routes(SimpleNamespace(
        block_store=bc.store,
        config=SimpleNamespace(rpc=SimpleNamespace(unsafe=False)))).table
    for h in range(1, tip + 1):
        seen = built["objects"][h - 1][2]
        holds = [v is not None for v in seen.precommits]
        assert holds.count(False) >= 2
        stored = [bc.store.load_seen_commit(h)]
        if h < tip:
            stored.append(bc.store.load_block_commit(h))
            assert bc.store.load_block(h + 1).last_commit.bit_array() == holds
        for got in stored:
            assert got.bit_array() == holds and got == seen
            assert got.encode() == seen.encode()
        assert routes["commit"]({"height": h})["precommits"] == \
            holds.count(True) == built["signed"][h - 1]
        if h > 1:
            assert routes["block"]({"height": h})["block"]["last_commit"][
                "precommits"] == built["signed"][h - 2]

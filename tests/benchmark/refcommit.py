"""The plain reference of commit verification for a chain whose commits
miss precommits: upstream tendermint v0.10.3's `ValidatorSet.VerifyCommit`
(`types/validator_set.go:220-264`) written as the loop it is.

    the set's size against the commit's, the height against the commit's;
    per entry: a nil precommit is SKIPPED, neither verified nor tallied;
    height, round, type, index and address of a precommit that is there;
    its signature over its sign bytes under the member's key (OpenSSL);
    the member's power tallied when the vote is for the block id;
    accepted only if the tally is MORE than 2/3 of the set's WHOLE power.

It reads a commit's `precommits` and a vote's fields and `sign_bytes`,
and nothing of what the program verifies with: no lane builder of
`types/validator.py`, no `Commit.wire_columns`, no batch plane, no crypto
backend.  Departure from upstream, noted: upstream's loop does not
compare a precommit's `validator_index` and address with its position;
the program refuses both as malformed, and so does this.

A verdict is a tuple the tests compare with the program's exceptions:
`None` (accepted), `("format", height)`, `("signature", height,
validator index)` or `("power", height)`.
"""

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import \
    Ed25519PublicKey

from tendermint_tpu.types import TYPE_PRECOMMIT


def ref_verify_commit(chain_id: str, members: list[tuple], block_id,
                      height: int, commit):
    """`members`: (address, 32-byte public key, power) in set order."""
    votes = commit.precommits
    if len(members) != len(votes):
        return ("format", height)
    present = [v for v in votes if v is not None]
    if not present or present[0].height != height:
        return ("format", height)
    round_ = present[0].round
    tallied = 0
    for idx, vote in enumerate(votes):
        if vote is None:
            continue                  # may be nil if the validator skipped
        address, pub, power = members[idx]
        if (vote.height != height or vote.round != round_
                or vote.type != TYPE_PRECOMMIT
                or vote.validator_index != idx
                or vote.validator_address != address
                or len(vote.signature) != 64):
            return ("format", height)
        try:
            Ed25519PublicKey.from_public_bytes(pub).verify(
                vote.signature, vote.sign_bytes(chain_id))
        except InvalidSignature:
            return ("signature", height, idx)
        if vote.block_id == block_id:
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

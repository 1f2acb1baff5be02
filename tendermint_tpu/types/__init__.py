"""Domain types: blocks, votes, validators, and the crypto-plane contracts.

The layer every other layer compiles against (reference `types/`,
SURVEY.md §2.2).
"""

from tendermint_tpu.types.block import (Block, BlockID, Commit, EMPTY_COMMIT,
                                        Header, ZERO_BLOCK_ID)
from tendermint_tpu.types.canonical import (SIGN_BYTES_LEN, TYPE_HEARTBEAT,
                                            TYPE_PRECOMMIT, TYPE_PREVOTE,
                                            TYPE_PROPOSAL)
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
from tendermint_tpu.types.keys import PrivKey, PubKey, address_from_pubkey
from tendermint_tpu.types.part_set import (PART_SIZE, Part, PartSet,
                                           PartSetHeader, ZERO_PSH)
from tendermint_tpu.types.priv_validator import DoubleSignError, PrivValidator
from tendermint_tpu.types.proposal import Heartbeat, Proposal
from tendermint_tpu.types.tx import Tx, TxProof, txs_hash, txs_proof
from tendermint_tpu.types.validator import Validator, ValidatorSet
from tendermint_tpu.types.vote import (DuplicateVoteEvidence, ErrVoteConflict,
                                       Vote, VoteSet)

__all__ = [
    "Block", "BlockID", "Commit", "EMPTY_COMMIT", "Header",
    "ZERO_BLOCK_ID",
    "SIGN_BYTES_LEN", "TYPE_HEARTBEAT", "TYPE_PRECOMMIT", "TYPE_PREVOTE",
    "TYPE_PROPOSAL", "GenesisDoc", "GenesisValidator", "PrivKey", "PubKey",
    "address_from_pubkey", "PART_SIZE", "Part", "PartSet", "PartSetHeader",
    "ZERO_PSH", "DoubleSignError", "PrivValidator", "Heartbeat", "Proposal",
    "Tx", "TxProof", "txs_hash", "txs_proof", "Validator", "ValidatorSet",
    "DuplicateVoteEvidence", "ErrVoteConflict", "Vote", "VoteSet",
]

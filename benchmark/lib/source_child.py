"""The source child: builds the chain, then serves it from N peers.

One process, started by the harness, holds everything that feeds the
node: the chain builder (`chain.py`, OpenSSL and hashlib) and the source
peers, which share ONE store of encoded blocks instead of saving and
applying the chain once each.  It never imports jax (asserted before it
reports ready), so it cannot touch the chip or the node's GIL.

    python -m benchmark.lib.source_child <spec.json>

spec: seed, chain_id, n_vals, n_blocks, n_sources, traffic, index_path,
`valset` where the traffic mix states a validator-set plan, `absent`
where it states a plan of absent precommits and `powers` where it states
a plan of voting powers.
When every peer listens it writes the per-height index (block hashes,
app hashes, encoded sizes, and `signed`: the precommits the commit of
the height holds, index h - 1; and `valsets`: for each set of members
that signs, its first height, its hash at that height and its public
keys in set order; a `powers` plan adds no entry and no key: the check
asks `chain.valset_at` for the powers of a height) to
`index_path`, prints one JSON line
{"ready": ..., "genesis": ..., "addrs": [...]} and serves until its
stdin closes.
"""

from __future__ import annotations

import json
import sys
import time


class _Encoded:
    """What a source's reactor needs of a loaded block: its wire bytes."""
    __slots__ = ("_data",)

    def __init__(self, data: bytes):
        self._data = data

    def encode(self) -> bytes:
        return self._data


class ServedStore:
    """The one store all source peers read: height -> encoded block."""

    def __init__(self, encoded: list[bytes]):
        self._encoded = encoded
        self.height = len(encoded)
        self.base = 1

    def load_block(self, height: int):
        if 1 <= height <= self.height:
            return _Encoded(self._encoded[height - 1])
        return None


def start_sources(chain_id: str, gen, store, n: int):
    """n dialable source peers over one store, each behind the
    reference's per-peer rate limit as configured (P2PConfig defaults:
    512 KB/s).  They verify nothing and apply nothing."""
    from tendermint_tpu.blockchain.reactor import BlockchainReactor
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.p2p.switch import make_switch
    from tendermint_tpu.state.state import get_state
    from tendermint_tpu.utils.db import MemDB
    switches = []
    for i in range(n):
        reactor = BlockchainReactor(get_state(MemDB(), gen), None, store,
                                    fast_sync=False)
        sw = make_switch(chain_id, {"blockchain": reactor},
                         config=P2PConfig(laddr="tcp://127.0.0.1:0",
                                          pex=False),
                         moniker=f"src-{i}")
        sw.start()
        switches.append(sw)
    return switches


def main(argv) -> int:
    t0 = time.monotonic()
    with open(argv[1]) as f:
        spec = json.load(f)
    from benchmark.lib import chain
    plan, powers = spec.get("valset"), spec.get("powers")
    seeds, vs = chain.valset_at(spec["seed"], spec["n_vals"], plan, 1,
                                powers)
    built = chain.build_chain(spec["chain_id"], seeds, vs, spec["n_blocks"],
                              spec["traffic"], spec["seed"], valset=plan,
                              absent=spec.get("absent"), powers=powers)
    t_built = time.monotonic()
    gen = chain.genesis_dict(spec["chain_id"], vs)
    with open(spec["index_path"], "w") as f:
        json.dump({"block_hash": [b.hex() for b in built["block_hash"]],
                   "app_hash": [b.hex() for b in built["app_hash"]],
                   "size": [len(e) for e in built["encoded"]],
                   "signed": built["signed"],
                   "valsets": [
                       {"from_height": h, "hash": s.hash().hex(),
                        "validators": [v.pub_key.bytes_.hex()
                                       for v in s.validators]}
                       for h, s in built["valsets"]]}, f)
    store = ServedStore(built["encoded"])
    switches = start_sources(spec["chain_id"], chain.genesis_doc(gen), store,
                             spec["n_sources"])
    try:
        if "jax" in sys.modules:
            print("source child imported jax", file=sys.stderr, flush=True)
            return 3
        print(json.dumps({
            "ready": True, "genesis": gen,
            "addrs": [str(sw._listener.addr) for sw in switches],
            "build_s": t_built - t0, "n_blocks": store.height,
            "bytes": sum(len(e) for e in built["encoded"])}), flush=True)
        sys.stdin.read()              # serve until the harness lets go
    finally:
        for sw in switches:
            sw.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

"""Node: the composition root wiring every subsystem together.

Reference: `node/node.go` — `NewNode` (`:68-236`) builds DBs, state,
handshake, proxy app conns, mempool, consensus, reactors, switch, and RPC;
`OnStart` (`:238-271`) brings up the listener, reactors, and RPC servers;
`RunForever` (`:288`).
"""

from __future__ import annotations

import os
import threading
import time

from tendermint_tpu.blockchain.store import BlockStore
from tendermint_tpu.config import Config
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.consensus.state import ConsensusState
from tendermint_tpu.crypto import backend as crypto_backend
from tendermint_tpu.mempool.mempool import Mempool
from tendermint_tpu.proxy import ClientCreator
from tendermint_tpu.state.state import get_state
from tendermint_tpu.state.txindex import KVTxIndexer
from tendermint_tpu.types import GenesisDoc, PrivValidator
from tendermint_tpu.types.events import EventSwitch
from tendermint_tpu.utils.db import new_db
from tendermint_tpu.utils import log as log_mod
from tendermint_tpu.utils import metrics

log = log_mod.get_logger("node")


class Node:
    def __init__(self, config: Config,
                 priv_validator: PrivValidator | None = None,
                 genesis_doc: GenesisDoc | None = None,
                 app=None):
        """Build everything (reference `NewNode` node/node.go:68-236).

        `app` overrides config.base.proxy_app with an Application instance
        (in-process custom apps, tests).
        """
        self.config = config
        base = config.base
        log_mod.set_level_spec(base.log_level)
        cr = config.crypto
        if cr.supervised:
            crypto_backend.set_backend_supervised(
                base.crypto_backend,
                breaker_threshold=cr.breaker_threshold,
                breaker_cooldown_s=cr.breaker_cooldown_s,
                call_timeout_s=cr.call_timeout_s,
                retries=cr.retries,
                spot_check_every=cr.spot_check_every)
        else:
            crypto_backend.set_backend(base.crypto_backend)

        # --- storage (reference :70-77) ---
        if base.db_backend == "memdb":
            mk = lambda name: new_db("memdb")
        else:
            os.makedirs(base.db_dir(), exist_ok=True)
            mk = lambda name: new_db("sqlite",
                                     os.path.join(base.db_dir(),
                                                  name + ".db"))
        self.block_store_db = mk("blockstore")
        self.state_db = mk("state")

        # --- genesis + state (reference :78) ---
        self.genesis_doc = genesis_doc or GenesisDoc.load(base.genesis_file())
        initial_state = get_state(self.state_db, self.genesis_doc)
        self.block_store = BlockStore(self.block_store_db)

        # --- priv validator ---
        self.priv_validator = priv_validator
        if self.priv_validator is None and base.db_backend != "memdb":
            self.priv_validator = PrivValidator.load_or_generate(
                base.priv_validator_file())

        # --- app conns + handshake (reference :83-89) ---
        self.proxy_app = ClientCreator(
            app if app is not None else base.proxy_app).new_app_conns()
        self.handshaker = Handshaker(initial_state, self.block_store)
        self.handshaker.handshake(self.proxy_app)

        # --- events, mempool, tx index, consensus (reference :96-158) ---
        self.evsw = EventSwitch()
        mempool_wal = (os.path.join(base.db_dir(), "mempool.wal")
                       if base.db_backend != "memdb" else "")
        self.mempool = Mempool(self.proxy_app.mempool, config.mempool,
                               wal_path=mempool_wal)
        self.tx_indexer = (KVTxIndexer(mk("tx_index"))
                           if base.db_backend != "memdb"
                           else KVTxIndexer(new_db("memdb")))
        if mempool_wal:
            # the tx index says which journalled txs already committed —
            # don't re-admit those (kvstore-style apps accept replays)
            from tendermint_tpu.types.tx import Tx
            n = self.mempool.recover_wal(
                committed=lambda tx: self.tx_indexer.get(Tx(tx).hash)
                is not None)
            if n:
                log.info("mempool wal recovered", txs=n)
        wal_path = (os.path.join(base.db_dir(), "cs.wal")
                    if base.db_backend != "memdb" else "")
        self.consensus = ConsensusState(
            config.consensus, initial_state, self.proxy_app.consensus,
            self.block_store, self.mempool,
            priv_validator=self.priv_validator, evsw=self.evsw,
            wal_path=wal_path, tx_indexer=self.tx_indexer,
            node_id=config.base.moniker)

        # --- evidence pool (equivocation proofs, SURVEY §2.2) ---
        from tendermint_tpu.state.evidence import EvidencePool
        self.evidence_pool = EvidencePool(mk("evidence"),
                                          self.genesis_doc.chain_id)
        self.evsw.subscribe(
            "node-evidence", "EvidenceDoubleSign",
            lambda ev: self.evidence_pool.add(
                ev, self._valset_at(ev.vote_a.height)))

        # --- p2p switch (built when a listen addr is configured) ---
        self.switch = None
        self._maybe_build_p2p()

        # --- background precompile of the crypto hot paths ---
        # A cold validator joining mid-height must not stall for the
        # first-verify XLA compile (SURVEY §5: measured ~1-2 min cold);
        # warm the current valset's tables + standard lane buckets while
        # the node boots.  Daemon threads: never block startup; `stop()`
        # waits for the program one is warming.
        self._stopped = threading.Event()
        self._warm_ups: list[threading.Thread] = []
        self._maybe_precompile()

        # --- RPC ---
        self.rpc_server = None
        self.grpc_server = None

    def _valset_at(self, height: int):
        """The validator set that signed votes at `height`: from saved
        history when available (evidence can arrive after an EndBlock
        membership change), else the live set."""
        st = self.consensus.state
        vs = st.load_validators(height)
        return vs if vs is not None else st.validators

    @property
    def state(self):
        """The state the node has APPLIED, for the RPC and `status()`.
        While the node fast-syncs that is the blockchain reactor's: it
        advances a copy of its own (`node/p2p_setup.py`), which consensus
        gets only at the hand-over, so `consensus.state` stands at the
        boot height (and the boot validator set) all through a catch-up.
        After the hand-over consensus swaps in a fresh State copy on
        every commit, so readers go through here each time and hold no
        State object.  The reactor's state is advanced IN PLACE by its
        apply thread: a reader takes one reference of a field (`vs =
        state.validators` is one whole set, never mutated once it is
        installed) and does not read the field twice for one answer."""
        bc = (self.switch.reactor("blockchain")
              if self.switch is not None else None)
        if bc is not None and bc.fast_sync and not bc.handed_over:
            return bc.state
        return self.consensus.state

    def _maybe_precompile(self) -> None:
        """Warm the crypto plane on daemon threads named
        `crypto-precompile`.  A node that fast-syncs warms only the
        window's program at boot, asks for blocks once that is done, and
        warms the rest when it has caught up (`precompile_for_validators`'
        stages): each program is seconds of Python tracing under the
        GIL, which the block download needs, and a sync that starts
        beside the warm-up races it for the window's program."""
        from tendermint_tpu.crypto import backend as cb
        be = cb.get_backend()
        if not hasattr(be, "precompile_for_validators"):
            return

        def warm(vals, stage: str, done=None) -> None:
            def run():
                try:
                    t0 = time.time()
                    be.precompile_for_validators(vals, stage, self._stopped)
                    log.info("crypto precompile done", stage=stage,
                             validators=vals.size(),
                             seconds=round(time.time() - t0, 1))
                except Exception:
                    log.exception("crypto precompile failed", stage=stage)
                finally:
                    if done is not None:
                        done.set()

            t = threading.Thread(target=run, daemon=True,
                                 name="crypto-precompile")
            self._warm_ups.append(t)
            t.start()

        vals = self.consensus.state.validators
        bc = (self.switch.reactor("blockchain")
              if self.switch is not None else None)
        if bc is None or not bc.fast_sync:
            warm(vals, "all")
            return
        bc.request_when = threading.Event()
        warm(vals, "catchup", done=bc.request_when)
        hand_over = bc.on_caught_up

        def caught_up(state) -> None:
            warm(state.validators, "live")
            hand_over(state)

        bc.on_caught_up = caught_up

    def _maybe_build_p2p(self) -> None:
        """Wire the p2p stack when a listen address is configured; a
        node configured solo (empty p2p.laddr) skips it (reference runs
        alone with fast_sync off, node/node.go:117-125)."""
        if not self.config.p2p.laddr:
            return
        from tendermint_tpu.node.p2p_setup import build_p2p
        self.switch = build_p2p(self)

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        """Reference `OnStart` node/node.go:238-271."""
        if self.switch is not None:
            self.switch.start()   # reactors own consensus startup
        else:
            self.consensus.start()
        if self.config.rpc.laddr:
            from tendermint_tpu.rpc.server import RPCServer
            self.rpc_server = RPCServer(self, self.config.rpc)
            self.rpc_server.start()
        if self.config.rpc.grpc_laddr:
            try:
                from tendermint_tpu.rpc.grpc_server import GRPCServer
                from tendermint_tpu.rpc.routes import Routes
                self.grpc_server = GRPCServer(
                    Routes(self), self.config.rpc.grpc_laddr)
                self.grpc_server.start()
            except ImportError:
                log.warn("rpc.grpc_laddr set but grpcio unavailable")

    def stop(self) -> None:
        self._stopped.set()
        if self.rpc_server is not None:
            self.rpc_server.stop()
        if self.grpc_server is not None:
            self.grpc_server.stop()
        if self.switch is not None:
            self.switch.stop()
        self.consensus.stop()
        self.mempool.close()
        # a warm-up ends before its next program: a process that exits
        # with a thread inside an XLA compile aborts instead
        for t in self._warm_ups:
            t.join(timeout=120)

    def run_forever(self) -> None:
        """Reference `RunForever` node/node.go:288."""
        try:
            while not self._stopped.wait(0.5):
                pass
        except KeyboardInterrupt:
            self.stop()

    # -- introspection for RPC ------------------------------------------
    def status(self) -> dict:
        latest_height = self.block_store.height
        meta = self.block_store.load_block_meta(latest_height) \
            if latest_height else None
        state = self.state
        return {
            "node_info": {
                "moniker": self.config.base.moniker,
                "network": state.chain_id,
                "version": "0.1.0",
            },
            "pub_key": (self.priv_validator.pub_key.hex()
                        if self.priv_validator else None),
            "latest_block_height": latest_height,
            "latest_block_hash": (meta.block_id.hash.hex() if meta else ""),
            "latest_app_hash": state.app_hash.hex(),
            "validator_count": state.validators.size(),
            "consensus": self.consensus.get_round_state_summary(),
            "metrics": metrics.snapshot(),
        } | self._crypto_status()

    def _crypto_status(self) -> dict:
        be = crypto_backend.get_backend()
        fn = getattr(be, "supervisor_status", None)
        return {"crypto_supervisor": fn()} if fn is not None else {}

"""A kvstore that answers `EndBlock` with the validator diffs of its
block's `val:` txs: the app a churn chain of the benchmark needs, which
the program does not have yet.  It stores a `val:` tx as the kvstore
stores any tx (so the app hash is `RefKVStore`'s) and registers itself
under `APP_NAME`, the name a rehearsal's configuration states."""

from tendermint_tpu.abci.app import register_app
from tendermint_tpu.abci.apps.kvstore import KVStoreApp
from tendermint_tpu.abci.types import ResponseEndBlock, Validator

APP_NAME = "kvstore-valset-test"


class ValsetKVStoreApp(KVStoreApp):
    def __init__(self):
        super().__init__()
        self._diffs: list[Validator] = []

    def deliver_tx(self, tx: bytes):
        if tx.startswith(b"val:"):
            pub, _, power = tx[4:].partition(b"/")
            self._diffs.append(Validator(bytes.fromhex(pub.decode()),
                                         int(power)))
        return super().deliver_tx(tx)

    def end_block(self, height: int) -> ResponseEndBlock:
        diffs, self._diffs = self._diffs, []
        return ResponseEndBlock(diffs=diffs)


register_app(APP_NAME, ValsetKVStoreApp)

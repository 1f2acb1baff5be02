"""Node + RPC end-to-end: the minimum slice (SURVEY.md §7 phase 4).

Reference: `rpc/rpc_test.go` + `test/app/` — a live node serving
JSON-RPC/URI/WebSocket with broadcast_tx_commit landing txs in blocks.
"""

import threading
import time

import pytest

from tendermint_tpu.config import test_config as fast_config
from tendermint_tpu.node.node import Node
from tendermint_tpu.rpc.client import HTTPClient, LocalClient, RPCError, WSClient
from tendermint_tpu.types import GenesisDoc, GenesisValidator, PrivValidator, PrivKey

CHAIN = "rpc-chain"


@pytest.fixture(scope="module")
def node():
    cfg = fast_config()
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = ""
    pv = PrivValidator(PrivKey(b"\x11" * 32))
    gen = GenesisDoc(chain_id=CHAIN,
                     validators=[GenesisValidator(pv.pub_key.bytes_, 10)],
                     genesis_time_ns=1)
    n = Node(cfg, priv_validator=pv, genesis_doc=gen)
    n.start()
    # wait for first blocks
    deadline = time.time() + 20
    while time.time() < deadline and n.block_store.height < 1:
        time.sleep(0.01)
    assert n.block_store.height >= 1
    yield n
    n.stop()


@pytest.fixture
def client(node):
    return HTTPClient(node.rpc_server.addr)


def test_status(node, client):
    st = client.status()
    assert st["node_info"]["network"] == CHAIN
    assert st["latest_block_height"] >= 1
    assert st["validator_count"] == 1


def test_broadcast_tx_commit_lands(node, client):
    res = client.broadcast_tx_commit(tx="0x" + b"rpc=42".hex())
    assert res["check_tx"]["code"] == 0
    assert res["deliver_tx"]["code"] == 0
    assert res["height"] >= 1
    # query the app for the value
    q = client.abci_query(data=b"rpc".hex())
    assert bytes.fromhex(q["value"]) == b"42"
    # tx index lookup
    tx_res = client.tx(hash=res["hash"])
    assert tx_res["height"] == res["height"]
    assert bytes.fromhex(tx_res["tx"]) == b"rpc=42"


def test_block_and_blockchain_routes(node, client):
    client.broadcast_tx_commit(tx="0x" + b"r2=1".hex())
    h = node.block_store.height
    blk = client.block(height=h)
    assert blk["block"]["header"]["height"] == h
    bc = client.blockchain()
    assert bc["last_height"] >= h
    assert bc["block_metas"][0]["height"] == bc["last_height"]
    cm = client.commit(height=h)
    assert cm["precommits"] == 1
    vals = client.validators()
    assert len(vals["validators"]) == 1
    gen = client.genesis()
    assert gen["genesis"]["chain_id"] == CHAIN
    dump = client.dump_consensus_state()
    assert dump["round_state"]["height"] >= h


def test_uri_get_endpoints(node):
    import json
    import urllib.request
    addr = node.rpc_server.addr
    with urllib.request.urlopen(f"{addr}/status") as r:
        out = json.loads(r.read())
    assert out["result"]["latest_block_height"] >= 1
    with urllib.request.urlopen(f"{addr}/num_unconfirmed_txs") as r:
        out = json.loads(r.read())
    assert "n_txs" in out["result"]
    # root lists routes
    with urllib.request.urlopen(addr) as r:
        out = json.loads(r.read())
    assert "status" in out["routes"]


def test_unknown_method_and_errors(node, client):
    with pytest.raises(RPCError, match="unknown method"):
        client.call("not_a_method")
    with pytest.raises(RPCError, match="no block"):
        client.block(height=10_000_000)


def test_websocket_new_block_subscription(node):
    from tendermint_tpu.types import events as ev
    ws = WSClient(node.rpc_server.addr)
    try:
        ws.subscribe(ev.NEW_BLOCK)
        msg = ws.recv()
        assert msg["method"] == "event"
        assert msg["params"]["event"] == ev.NEW_BLOCK
        assert msg["params"]["data"]["height"] >= 1
        # status over the same ws connection
    finally:
        ws.close()


def test_local_client(node):
    lc = LocalClient(node)
    st = lc.status()
    assert st["latest_block_height"] >= 1


def test_status_reports_live_state(node, client):
    """Regression: Node.state must track consensus's per-commit State
    swap; the boot-time snapshot would report a stale app hash forever."""
    before = client.status()
    client.broadcast_tx_commit(tx="0x" + b"live=state".hex())
    after = client.status()
    assert after["latest_block_height"] > before["latest_block_height"]
    assert after["latest_app_hash"] != before["latest_app_hash"]
    # blocks commit every ~85 ms here: one may land between the answer
    # and the read of the node's own state, so read the pair again
    for _ in range(10):
        if after["latest_app_hash"] == node.consensus.state.app_hash.hex():
            break
        after = client.status()
    assert after["latest_app_hash"] == node.consensus.state.app_hash.hex()


def test_unsafe_routes_gated(node, client):
    """unsafe_* routes exist only when rpc.unsafe is set (reference
    AddUnsafeRoutes, rpc/core/routes.go:30-36)."""
    from tendermint_tpu.rpc.routes import Routes
    with pytest.raises(RPCError):
        client.call("unsafe_flush_mempool")
    node.config.rpc.unsafe = True
    try:
        r = Routes(node)
        assert "unsafe_flush_mempool" in r.table
        node.mempool.check_tx(b"zz=1")
        assert r.unsafe_flush_mempool({})["flushed"]
        assert node.mempool.size() == 0
    finally:
        node.config.rpc.unsafe = False


def test_metrics_endpoint(node):
    """GET /metrics serves the Prometheus text exposition with live
    instrument values — a committed block must show in the counter and
    the histogram triple must be present."""
    import urllib.request
    addr = node.rpc_server.addr
    with urllib.request.urlopen(f"{addr}/metrics") as r:
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        text = r.read().decode()
    lines = text.splitlines()
    committed = [ln for ln in lines
                 if ln.startswith("tendermint_blocks_committed ")]
    assert committed and int(committed[0].split()[1]) >= 1
    assert "# TYPE tendermint_round_seconds_hist histogram" in lines
    assert any('_bucket{le="+Inf"}' in ln for ln in lines)
    assert any(ln.startswith("tendermint_uptime_seconds") for ln in lines)


def test_debug_flight_recorder_route(node, client):
    """The flight recorder is an unsafe-gated route: absent by default,
    and when enabled it round-trips both the raw span list and the
    Chrome trace form of the same recorder."""
    from tendermint_tpu.rpc.routes import Routes
    from tendermint_tpu.utils import tracing
    with pytest.raises(RPCError, match="unknown method"):
        client.call("debug_flight_recorder")
    node.config.rpc.unsafe = True
    try:
        r = Routes(node)
        assert "debug_flight_recorder" in r.table
        tracing.RECORDER.instant("test.marker", k=1)
        out = r.debug_flight_recorder({})
        assert out["total"] >= 1
        assert out["capacity"] == tracing.RECORDER.capacity
        names = [s["name"] for s in out["spans"]]
        assert "test.marker" in names
        # a live node records consensus activity through the recorder
        assert any(n.startswith(("consensus.", "wal.")) for n in names)
        chrome = r.debug_flight_recorder({"format": "chrome"})
        evs = chrome["trace"]["traceEvents"]
        assert any(e["name"] == "test.marker" for e in evs)
        assert any(e["ph"] == "M" for e in evs)
        with pytest.raises(ValueError, match="format"):
            r.debug_flight_recorder({"format": "xml"})
    finally:
        node.config.rpc.unsafe = False


def test_validators_route_accum_snapshot(node, client):
    """/validators reports a consistent accum snapshot taken under the
    consensus lock; with one validator the priority must always be the
    post-rotation value 0 no matter when the scrape lands."""
    for _ in range(3):
        vals = client.validators()
        (v,) = vals["validators"]
        assert v["accum"] == 0
        assert v["voting_power"] == 10


def test_debug_flight_recorder_filters(node, client):
    """Server-side name/last filters: a 16k-span ring answers questions
    about its tail without shipping the whole ring over the wire."""
    from tendermint_tpu.rpc.routes import Routes
    from tendermint_tpu.utils import tracing
    node.config.rpc.unsafe = True
    try:
        r = Routes(node)
        for i in range(5):
            tracing.RECORDER.record(f"filt.me{i}", ts_s=1000.0 + i,
                                    dur_s=0.1)
        out = r.debug_flight_recorder({"name": "filt.me"})
        assert [s["name"] for s in out["spans"]] == \
            [f"filt.me{i}" for i in range(5)]
        out = r.debug_flight_recorder({"name": "filt.me", "last": 2})
        assert [s["name"] for s in out["spans"]] == \
            ["filt.me3", "filt.me4"]
        chrome = r.debug_flight_recorder(
            {"format": "chrome", "name": "filt.me", "last": 1})
        evs = chrome["trace"]["traceEvents"]
        assert [e["name"] for e in evs if e["ph"] != "M"] == ["filt.me4"]
        assert any(e["ph"] == "M" for e in evs)     # metadata survives
    finally:
        node.config.rpc.unsafe = False


def test_debug_doctor_and_bench_history_routes(node, client, tmp_path,
                                               monkeypatch):
    """debug_doctor reports attribution over the live recorder;
    debug_bench_history serves the ledger with path containment (a
    ledger param may not escape the node's working directory)."""
    from tendermint_tpu.rpc.routes import Routes
    from tendermint_tpu.utils import ledger, tracing
    node.config.rpc.unsafe = True
    try:
        r = Routes(node)
        assert "debug_doctor" in r.table
        assert "debug_bench_history" in r.table
        # the recorder ring is process-global: window-keyed spans left
        # by earlier fast-sync tests would flip the doctor into
        # window attribution and hide the span injected below
        tracing.RECORDER.clear()
        tracing.RECORDER.record("scalar.verify", ts_s=2000.0, dur_s=1.0)
        rep = r.debug_doctor({})["report"]
        assert rep["schema"] == "tpu-bft-doctor/1"
        assert rep["headline_gap"]["scalar_tail"] >= 1.0
        monkeypatch.chdir(tmp_path)
        ledger.append_entry("led.jsonl",
                            {"configs": {"config0":
                                         {"blocks_per_sec": 5.0}}})
        out = r.debug_bench_history({"ledger": "led.jsonl"})
        assert out["count"] == 1
        assert out["latest_deltas"]["config0"]["rate"] == 5.0
        with pytest.raises(ValueError):
            r.debug_bench_history({"ledger": "../etc/passwd"})
        with pytest.raises(ValueError):
            r.debug_bench_history({"ledger": "a/b.jsonl"})
    finally:
        node.config.rpc.unsafe = False

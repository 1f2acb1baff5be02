"""One run of one cell: build, boot, warm, measure, check, report.

`run_cell` is what `run.py` calls after it has found the cell's files.
The process it runs in holds the chip, the node and the clock; the
source peers and the `/status` prober are children (`children.py`).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

from benchmark.lib import accounting, chain, children as children_mod

WINDOW_BLOCKS = 64               # the reactor's DEFAULT_BATCH, asserted below
WARM_WINDOWS = 3                 # the first verified window and two more
TRACE_MAX_S = 20.0               # a trace covers at most this much
PROBE_INTERVAL_S = 0.1           # /status, open loop, 10 a second
DEADLINE_S = 1150.0              # the driver allows a compiling run 1200 s

# exit codes besides 0 (a result line was printed) and 1 (it broke)
EXIT_NO_DEVICE = 2
EXIT_MEASURED_NOTHING = 4
EXIT_DEADLINE = 5

# jitted entry points of ops/: what must not compile or load once warm
KERNELS = frozenset(f"jit({n})" for n in (
    "verify", "verify_grouped", "verify_grouped_templated",
    "sign_grouped_templated", "build_neg_comb", "leaf_hashes", "roots",
    "root_from_leaf_hashes"))


class MeasuredNothing(Exception):
    """The run cannot give a reading (chain too short, window too
    short): exit non-zero and print no result."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_cell(root: str, workload: str) -> dict:
    """The cell's files, found by the names in BENCHMARK.json."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    from benchmark.lib import reducers

    def here(m):
        return "workloads" not in m or workload in m["workloads"]
    return {
        "name": workload, "chips": cell["chips"], "config": config,
        "config_name": cell["config"], "traffic": traffic,
        "traffic_name": cell["traffic"],
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [dict(m, spec=reducers.load_layer(root, m["name"]))
                      for m in bench["per_layer"] if here(m)],
    }


DEFAULT_HEADROOM = 2.0           # a chain plan that names none


def chain_plan(cell: dict) -> dict:
    """The cell's chain plan (`chain` of the traffic file, by
    configuration, else its default; a configuration file may override
    by traffic name), with its `headroom`."""
    plan = cell["config"].get("chain", {}).get(cell["traffic_name"])
    if plan is None:
        by_cfg = cell["traffic"]["chain"]
        plan = by_cfg.get(cell["config_name"], by_cfg["default"])
    return dict(plan, headroom=plan.get("headroom", DEFAULT_HEADROOM))


def chain_blocks(cell: dict, seconds: float) -> int:
    """How long a chain to serve: `headroom` times what the parent syncs
    in warm-up plus the window, in whole reactor windows, plus the block
    that carries the last commit."""
    plan = chain_plan(cell)
    blocks = plan["headroom"] * plan["parent_blocks_per_s"] * \
        (plan["warmup_s"] + seconds)
    windows = max(WARM_WINDOWS + 4, -(-int(blocks) // WINDOW_BLOCKS))
    return windows * WINDOW_BLOCKS + 1


def boot_node(home: str, gen, addrs: list[str], app: str | None = None):
    """What `cli node --home <home> --crypto-backend tpu --fast-sync`
    constructs (copied from `chip_smoke.boot_node`): genesis and
    priv-validator on disk, sqlite stores, RPC and p2p on loopback, the
    supervised ladder off; with `--proxy-app <app>` where the
    configuration's file names its app."""
    from tendermint_tpu.config import Config
    from tendermint_tpu.node.node import Node
    os.makedirs(home, exist_ok=True)
    cfg = Config()
    cfg.base.home = home
    cfg.base.chain_id = gen.chain_id
    cfg.base.moniker = os.path.basename(home)
    cfg.base.crypto_backend = "tpu"
    cfg.base.fast_sync = True
    if app is not None:
        cfg.base.proxy_app = app
    cfg.crypto.supervised = False
    cfg.rpc.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.laddr = "tcp://127.0.0.1:0"
    cfg.p2p.pex = False
    cfg.p2p.persistent_peers = list(addrs)
    gen.save(cfg.base.genesis_file())
    return Node(cfg), cfg


def stated_as_run(cfg: dict, node_cfg=None) -> None:
    """The deployment's shapes and limits as its file states them are
    the program's own defaults: the harness sets none of them, so a
    default that moves is a different deployment, and an error here.
    So is a node (`node_cfg`, the booted node's Config) whose app is not
    the one the file names."""
    from tendermint_tpu.blockchain import pool, reactor
    from tendermint_tpu.config import P2PConfig
    from tendermint_tpu.types.part_set import PART_SIZE
    p2p = P2PConfig()
    run = {"part_bytes": PART_SIZE, "window_blocks": reactor.DEFAULT_BATCH,
           "max_pending_requests": pool.MAX_PENDING,
           "max_pending_per_peer": pool.MAX_PENDING_PER_PEER,
           "peer_rate_bytes_per_s": min(p2p.send_rate, p2p.recv_rate)}
    if node_cfg is not None and "app" in cfg:
        run["app"] = node_cfg.base.proxy_app
    differ = {k: (cfg[k], v) for k, v in run.items() if cfg[k] != v}
    if differ or reactor.DEFAULT_BATCH != WINDOW_BLOCKS:
        raise RuntimeError("the configuration states (file, program): "
                           f"{differ}; window {reactor.DEFAULT_BATCH}")


def returns_val_diffs(app) -> bool:
    """Whether `app`, a fresh in-process app, returns a well-formed
    `val:<pubkey>/<power>` tx (the builder's own, `chain._val_tx`) as the
    `EndBlock` diff of the block that carries it: asked of the app, never
    looked up by its name."""
    tx = chain._val_tx(0, 0, chain.POWER + 1)
    app.deliver_tx(tx)
    return [(d.pub_key, d.power) for d in app.end_block(1).diffs] == [
        chain.parse_val_tx(tx)]


def app_fits_plans(cfg: dict, traffic: dict) -> None:
    """A ValueError that names the configuration, the mix, the plan and
    the app unless the app the configuration states (the program's
    default where its file names none) is in the program's registry of
    in-process apps and returns `val:` txs as `EndBlock` diffs IF AND
    ONLY IF the mix states a `valset` or a `powers` plan.  Under such a
    plan an app that returns none stores the txs, no set moves and the
    second header names a set the node does not hold; without one, an app
    that does is another deployment than the one the file describes."""
    from tendermint_tpu.abci.app import create_app
    from tendermint_tpu.config import Config
    app = cfg.get("app", Config().base.proxy_app)
    plans = [p for p in ("valset", "powers") if traffic.get(p)]
    stated = "a " + " and a ".join(plans) if plans else "no valset or powers"
    what = (f"configuration {cfg.get('name')!r} states the app {app!r} "
            f"under the mix {traffic.get('name')!r}, which states {stated} "
            "plan")
    try:
        made = create_app(app)
    except ValueError as e:
        raise ValueError(f"{what}: {e}") from e
    diffs = returns_val_diffs(made)
    if plans and not diffs:
        raise ValueError(
            f"{what}: the plan needs an app that returns its "
            f"`val:<pubkey>/<power>` txs as EndBlock diffs, and {app!r} "
            "returns none")
    if diffs and not plans:
        raise ValueError(
            f"{what}: {app!r} returns `val:` txs as EndBlock diffs, which "
            "no block of this mix carries; another deployment than the "
            "file's")


def precommit_limits(index: dict, synced: int, tip: int) -> tuple[int, int]:
    """What the served chain holds, by the index's `signed` (`validators`
    at every height where the mix states no `absent` plan): (the
    precommits of the commits of heights 1 .. `synced`, the precommits
    of the commit the block at `tip` carries).  A node that has synced K
    heights from genesis has verified K commits: height h is applied on
    the commit OF h, which block h + 1 carries as its LastCommit; and
    the block at `tip` carries the commit of `tip - 1`, none at 1."""
    signed = index["signed"]
    return sum(signed[:synced]), (signed[tip - 2] if tip > 1 else 0)


def absent_report(index: dict, heights: list[int], n_vals: int) -> str:
    """What traffic the interval was, for the run's log: the share of
    its commit lanes that were signed, and how many of its commits and
    of its 64-block windows (`heights`, in order, 64 at a time: the
    reactor's windows but where a `valset` plan cuts them) hold an
    absent precommit."""
    signed = [index["signed"][h - 1] for h in heights]
    lanes = len(heights) * n_vals
    short = [s < n_vals for s in signed]
    windows = [short[i:i + WINDOW_BLOCKS]
               for i in range(0, len(short), WINDOW_BLOCKS)]
    return (f"precommits: {sum(signed)} of {lanes} commit lanes of the "
            f"interval signed ({100.0 * sum(signed) / lanes:.3f} %); "
            f"{sum(short)} of {len(heights)} commits and "
            f"{sum(map(any, windows))} of {len(windows)} 64-block windows "
            "hold an absent precommit")


def set_answers_differ(answered: list[dict], state_validators, vs) -> list:
    """The two terms of `rpc_answers_differ` that hold a node to the
    builder's set `vs` of the height after its tip, by name where they
    differ: `/validators` answers that set's public keys each with its
    voting power, in set order, and the state's set hashes to its hash
    (which covers the powers too)."""
    return [name for name, differs in (
        ("/validators",
         [(v["pub_key"], v["voting_power"]) for v in answered] !=
         [(v.pub_key.bytes_.hex(), v.voting_power) for v in vs.validators]),
        ("state validators hash", state_validators.hash() != vs.hash()))
        if differs]


def powers_report(seed: int, n_vals: int, valset: dict | None,
                  powers: dict | None, heights: list[int]) -> str:
    """What traffic the interval was, for the run's log: how many of
    its heights carry a diff that moves a voting power, so that the
    header of the height after holds another `validators_hash`; by
    `chain.power_txs`, which the builder made the blocks from."""
    moved = sum(bool(chain.power_txs(seed, n_vals, valset, powers, h))
                for h in heights)
    return (f"powers: {moved} of {len(heights)} heights of the interval "
            f"change a voting power (plan {powers})")


def precompile_running() -> bool:
    return any(t.name == "crypto-precompile" and t.is_alive()
               for t in threading.enumerate())


def wait_for(pred, timeout: float, what: str, poll: float = 0.05) -> None:
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timeout after {timeout:.0f}s waiting for "
                               f"{what}")
        time.sleep(poll)


def start_watchdog(kids: children_mod.Children) -> threading.Timer:
    def fire():
        print(f"benchmark: still running after {DEADLINE_S:.0f}s; giving up",
              file=sys.stderr, flush=True)
        kids.stop_all()
        os._exit(EXIT_DEADLINE)
    t = threading.Timer(DEADLINE_S, fire)
    t.daemon = True
    t.start()
    return t


def hist_state(registry) -> dict:
    return {"batchplane_wait_seconds": {
        k: (h.count, h._sum)
        for k, h in registry.batchplane_wait_seconds.items()}}


def hist_delta(before: dict, after: dict) -> dict:
    out = {}
    for name, cells in after.items():
        was = before.get(name, {})
        out[name] = {k: (c - was.get(k, (0, 0.0))[0],
                         t - was.get(k, (0, 0.0))[1])
                     for k, (c, t) in cells.items()}
    return out


class Closer(threading.Thread):
    """Takes the run's close when it is due, on a thread of its own.

    In a traced run the thread of the harness's clock is inside
    `jax.profiler.stop_trace()` when the window closes: it collects and
    writes the trace before it returns, a minute and more for 20 s of a
    cell that runs 250 windows.  So the close (`take()`: read the node,
    then stop the sync) is taken here at `at_mono`, and the harness
    collects it with `taken()` when it gets there."""

    def __init__(self, at_mono: float, take):
        super().__init__(name="bench-close", daemon=True)
        self.at_mono, self._take = at_mono, take
        self._off = threading.Event()
        self.close = None
        self.error = None

    def run(self) -> None:
        if self._off.wait(max(0.0, self.at_mono - time.monotonic())):
            return
        try:
            self.close = self._take()
        except Exception as e:        # raised again by taken()
            self.error = e

    def call_off(self) -> None:
        self._off.set()

    def taken(self, timeout: float) -> dict:
        """What `take()` returned, once the close is due and taken."""
        self.join(max(0.0, self.at_mono - time.monotonic()) + timeout)
        if self.error is not None:
            raise self.error
        if self.close is None:
            raise TimeoutError(f"the close was not taken {timeout:.0f}s "
                               "after it was due")
        return self.close


class Checks:
    """Every number compared, printed beside its limit as it is taken,
    and kept under a short name for the result's line and the last
    lines of standard error (`report_compared`)."""

    def __init__(self):
        self.ok = True
        self.compared: dict[str, dict] = {}

    def _note(self, key: str, name: str, value, word: str, limit,
              good) -> None:
        self.ok &= bool(good)
        self.compared[key] = {"value": value, f"at_{word}": limit,
                              "ok": bool(good)}
        say(f"check {name}: {value} (limit: at {word} {limit}) "
            f"{'ok' if good else 'NOT OK'}")

    def at_most(self, key: str, name: str, value, limit) -> None:
        self._note(key, name, value, "most", limit, value <= limit)

    def at_least(self, key: str, name: str, value, limit) -> None:
        self._note(key, name, value, "least", limit, value >= limit)


def report_compared(compared: dict) -> None:
    """Each number compared beside its limit, as the last lines of
    standard error (the result's line carries the same under `checks`)."""
    for key, c in compared.items():
        word = "at_most" if "at_most" in c else "at_least"
        print(f"benchmark: compared {key} = {c['value']} (limit "
              f"{word.replace('_', ' ')} {c[word]})"
              f"{'' if c['ok'] else ' NOT OK'}", file=sys.stderr)
    sys.stderr.flush()


def kernel_programs(spans) -> int:
    """Compiles or cache loads of a kernel (or of anything that took as
    long as one) among `spans`; one-op helpers do not count."""
    return sum(s["name"] == "xla.compile" and
               (s["args"]["fn"] in KERNELS or s["dur"] >= 1.0) for s in spans)


def reduce_device_trace(devtrace, trace_dir: str, spans: list[dict],
                        anchor_epoch: float, end_epoch: float) -> dict:
    """The profiler's trace of [anchor, end] (recorder's clock) reduced,
    with the reactor windows that completed in it."""
    events = devtrace.read_events(devtrace.find_xplane(trace_dir))
    offset = devtrace.clock_offset(events, anchor_epoch)
    if offset is None:
        raise RuntimeError("the trace has no anchor annotation")
    t0 = anchor_epoch - offset
    reduced = devtrace.reduce(events, t0, t0 + (end_epoch - anchor_epoch),
                              spans=spans, offset=offset)
    reduced["reactor_windows"] = sum(
        s["name"] == accounting.WINDOW_SPAN and
        anchor_epoch <= accounting.span_end(s) <= end_epoch for s in spans)
    say(f"trace: {len(events)} events, planes {reduced['planes']}, lines of "
        f"the first {reduced['lines']}, programs {reduced['kernels']}")
    return reduced


def run_cell(root: str, cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, expect_platform: str = "tpu",
             known_kinds=None, fault: str | None = None):
    """Run the cell once.  Returns the result object of the last line.
    Raises SystemExit(EXIT_NO_DEVICE) on a wrong device and
    MeasuredNothing when the run cannot give a reading."""
    cfg, traffic = cell["config"], cell["traffic"]
    n_vals, n_sources = cfg["validators"], cfg["source_peers"]
    valset = traffic.get("valset")    # the mix's validator-set plan
    absent = traffic.get("absent")    # its plan of absent precommits
    powers = traffic.get("powers")    # and its plan of voting powers
    # a malformed plan, or two that do not go together: here
    chain.check_plans(seed, n_vals, valset, absent, powers)
    # an app that does not go with them: here too, not at height 2
    app_fits_plans(cfg, traffic)
    n_blocks = chain_blocks(cell, seconds)
    workdir = tempfile.mkdtemp(prefix="tmbench_")
    kids = children_mod.Children(root)
    dog = start_watchdog(kids)
    node = None
    closer = None
    tracing_on = False
    try:
        # -- the children first: the chain builds while jax imports -----
        index_path = os.path.join(workdir, "index.json")
        spec_path = os.path.join(workdir, "source_spec.json")
        chain_id = f"tm-bench-{seed}"
        with open(spec_path, "w") as f:
            json.dump({"seed": seed, "chain_id": chain_id, "n_vals": n_vals,
                       "n_blocks": n_blocks, "n_sources": n_sources,
                       "traffic": traffic["block"],
                       "index_path": index_path,
                       **({"valset": valset} if valset else {}),
                       **({"absent": absent} if absent else {}),
                       **({"powers": powers} if powers else {})}, f)
        source = kids.start("benchmark.lib.source_child", spec_path)
        prober = kids.start("benchmark.lib.prober_child")
        say(f"children: source {source.pid}, prober {prober.pid}")

        # -- the device, or nothing ------------------------------------
        import jax
        devs = jax.devices()
        platform, kind = devs[0].platform, devs[0].device_kind
        say(f"device: platform {platform}, kind {kind}, count {len(devs)}")
        from benchmark.lib import roofline
        known = (known_kinds if known_kinds is not None
                 else tuple(roofline.load_peaks()))
        if (platform != expect_platform or kind not in known
                or len(devs) < cell["chips"]):
            print(f"benchmark: this cell needs {cell['chips']} device(s) of "
                  f"platform {expect_platform!r}, kind in {known}; jax found "
                  f"{len(devs)} of platform {platform!r}, kind {kind!r}. "
                  "Nothing was run.", file=sys.stderr, flush=True)
            raise SystemExit(EXIT_NO_DEVICE)

        # no comb-table disk cache: every seed is a new validator set, so
        # the 312 MiB file a fresh node writes is never read, and writing
        # it costs the window that follows ~4 % (my chip runs, PR 23)
        os.environ["TM_TABLE_CACHE_DIR"] = ""
        from tendermint_tpu.crypto import backend as cb
        from tendermint_tpu.utils import tracing
        from tendermint_tpu.utils.metrics import REGISTRY
        from benchmark.lib import control, devtrace, reducers
        if fault:
            from benchmark.lib import faults
            faults.install(fault)
            say(f"FAULT INSTALLED under the timed path: {fault}")

        ready = kids.read_json_line(source, DEADLINE_S / 2,
                                    "the source child")
        say(f"chain: {ready['n_blocks']} blocks x {n_vals} validators, "
            f"{ready['bytes'] / 1e6:.1f} MB, built in the source child in "
            f"{ready['build_s']:.1f}s; {n_sources} peers listen")
        with open(index_path) as f:
            index = json.load(f)
        gen = chain.genesis_doc(ready["genesis"])

        # -- boot: only now, so chain building never leaks into it ------
        counters_boot = REGISTRY.snapshot()
        spans_before = tracing.RECORDER.total
        t_boot = tracing.now_epoch()
        node, node_cfg = boot_node(os.path.join(workdir, "node"), gen,
                                   ready["addrs"], cfg.get("app"))
        stated_as_run(cfg, node_cfg)
        be = cb.get_backend()
        if type(be).__name__ != "TpuBackend" or be.platform != platform:
            raise RuntimeError(f"node installed backend {be!r} on "
                               f"{getattr(be, 'platform', None)}")
        node.start()
        bc = node.switch.reactor("blockchain")
        wait_for(lambda: REGISTRY.blocks_synced.value -
                 counters_boot["blocks_synced"] >= WARM_WINDOWS *
                 WINDOW_BLOCKS and not precompile_running(),
                 DEADLINE_S * 0.8, "the warm-up windows and the boot "
                 f"precompile thread (node at {node.block_store.height})")

        # -- the window --------------------------------------------------
        trace_dir = os.path.join(workdir, "trace")
        trace_s = min(seconds, TRACE_MAX_S)
        anchor_epoch = None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing_on = True
            with jax.profiler.TraceAnnotation(devtrace.ANCHOR):
                anchor_epoch = tracing.now_epoch()
        hist_open = hist_state(REGISTRY)
        t_open_mono = time.monotonic() + 0.05
        t_open = tracing.now_epoch() + 0.05
        t_close_mono = t_open_mono + seconds
        kids.send_json_line(prober, {
            "url": node.rpc_server.addr.rstrip("/") + "/status",
            "t_open": t_open_mono, "t_close": t_close_mono,
            "interval_s": PROBE_INTERVAL_S})
        setup_s = t_open_mono - t_start
        height_open = node.block_store.height
        say(f"window open after {setup_s:.1f}s of set-up, node at "
            f"{height_open}")

        def take_close() -> dict:
            """Read the close, then stop the sync where it is, before
            anything that can take long: nothing the node does after the
            close reaches a check, a counter or the ring."""
            late_s = time.monotonic() - t_close_mono
            close = {"height": node.block_store.height,
                     "switched": bc._switched,
                     "counters": REGISTRY.snapshot(),
                     "hists": hist_delta(hist_open, hist_state(REGISTRY)),
                     "read_late_s": late_s}
            bc.stop()
            bc._thread.join(timeout=120)
            if bc._thread.is_alive():
                raise RuntimeError("the fast-sync thread did not stop")
            close["stopped_late_s"] = time.monotonic() - t_close_mono
            close["height_stopped"] = node.block_store.height
            return close

        closer = Closer(t_close_mono, take_close)
        closer.start()
        trace_end_epoch = None
        if trace:
            time.sleep(max(0.0, t_open_mono + trace_s - time.monotonic()))
            trace_end_epoch = tracing.now_epoch()
            jax.profiler.stop_trace()     # may return long after the close
            tracing_on = False
            say(f"trace: stop asked {trace_end_epoch - t_open:.3f}s after "
                f"the open, written {time.monotonic() - t_close_mono:.1f}s "
                "after the close")
        close = closer.taken(150)
        t_close = t_open + seconds
        height_close, switched = close["height"], close["switched"]
        counters_close, hists = close["counters"], close["hists"]
        say(f"close: read {1e3 * close['read_late_s']:.1f} ms after it was "
            f"due, node at {height_close}; the sync stopped "
            f"{close['stopped_late_s']:.3f}s after the close, node at "
            f"{close['height_stopped']}")
        spans = [s for s in tracing.RECORDER.snapshot() if s["ts"] >= t_boot]
        ring_records = tracing.RECORDER.total - spans_before
        overflow = ring_records > tracing.RECORDER.capacity
        if switched or height_close >= n_blocks - 2 * WINDOW_BLOCKS:
            raise MeasuredNothing(
                f"the node reached height {height_close} of {n_blocks - 1} "
                "served before the window closed (it opened at height "
                f"{height_open}; {'a traced' if trace else 'an untraced'} "
                "run): the chain is too short for this rate, the run has "
                "measured nothing")

        probe = kids.read_json_line(prober, 60, "the prober child")

        # -- check ----------------------------------------------------------
        from tendermint_tpu.rpc.client import HTTPClient

        def stored_hash(h):
            meta = node.block_store.load_block_meta(h)
            return meta.block_id.hash.hex() if meta else None

        try:
            acct = accounting.account(
                spans, t_open, t_close, stored_hash,
                lambda h: index["block_hash"][h - 1])
        except accounting.WindowTooShort as e:
            raise MeasuredNothing(str(e)) from e
        checks = Checks()
        tip = bc.state.last_block_height
        checks.at_most("refused", "refused heights in the interval",
                       len(acct["refused"]), 0)
        checks.at_most("wrong_hash", "applied heights whose stored hash "
                       "differs from the builder's",
                       len(acct["wrong_hash"]), 0)
        checks.at_most("tip_hash_differs", "tip block hash differs from the "
                       f"builder's (height {tip})",
                       int(stored_hash(tip) != index["block_hash"][tip - 1]),
                       0)
        checks.at_most("app_hash_differs", "app hash at the tip differs "
                       "from the builder's",
                       int(bc.state.app_hash.hex() !=
                           index["app_hash"][tip - 1]), 0)
        moved = {k: counters_close[k] - counters_boot[k] for k in
                 ("sigs_verified", "blocks_synced", "crypto_fallback_calls")}
        held_synced, held_at_tip = precommit_limits(
            index, moved["blocks_synced"], tip)
        rpc = HTTPClient(node.rpc_server.addr)
        st = rpc.status()
        blk = rpc.block(height=tip)["block"]
        vals = rpc.validators()["validators"]
        # the set of the height after the tip, members and powers, as
        # the builder has it (the genesis set where the mix states no
        # plan): what the node's state and its RPC have to hold, and what
        # the control signs with
        val_seeds, vs = chain.valset_at(seed, n_vals, valset, tip + 1,
                                        powers)
        sets = [s["from_height"] for s in index["valsets"]]
        say(f"validators: the builder's set {sum(h <= tip + 1 for h in sets)}"
            f" of {len(sets)} holds at height {tip + 1}")
        rpc_wrong = [name for name, differs in (
            ("/status height", st["latest_block_height"] != tip),
            ("/status hash",
             st["latest_block_hash"] != index["block_hash"][tip - 1]),
            ("/status validator_count", st["validator_count"] != n_vals),
            ("/block hash", blk["block_hash"] != index["block_hash"][tip - 1]),
            ("/block height", blk["header"]["height"] != tip),
            ("/block precommits",
             blk["last_commit"]["precommits"] != held_at_tip))
            if differs] + set_answers_differ(vals, bc.state.validators, vs)
        checks.at_most("rpc_answers_differ", "/status, /block, /validators "
                       "answers and the state's validator set that differ "
                       f"from the builder's {rpc_wrong}", len(rpc_wrong), 0)
        checks.at_most("fallback_calls", "crypto_fallback_calls moved by",
                       moved["crypto_fallback_calls"], 0)
        checks.at_most("scalar_verify_spans", "scalar.verify spans", sum(
            s["name"] == "scalar.verify" for s in spans), 0)
        checks.at_least("sigs_verified", "sigs_verified moved by "
                        "(precommits the served chain holds for the heights "
                        "synced since boot)",
                        moved["sigs_verified"], held_synced)
        in_window = [s for s in spans if s["name"] == "xla.compile" and
                     t_open <= accounting.span_end(s) <= t_close]
        checks.at_most("kernel_programs_in_window", "kernel compiles or "
                       "loads inside the window",
                       kernel_programs(in_window), 0)
        say(f"one-op helper compiles inside the window: {len(in_window)}")
        checks.at_most("ring_overflowed", "flight recorder overflowed "
                       f"({ring_records} records of "
                       f"{tracing.RECORDER.capacity})", int(overflow), 0)
        checks.at_most("probe_errors", "probes answered with an error",
                       probe["errors"], 0)

        # the verdict control: the window's own bucket, after the window,
        # signed by the builder's set and run against the node's, whose
        # table is resident
        t_control = tracing.now_epoch()
        batch = control.build(seed, val_seeds, WINDOW_BLOCKS)
        got = control.device_verdicts(bc.state.validators, batch)
        checks.at_most("control_lanes_differ", "verdict control: lanes of "
                       f"{got.size} ({batch['forged']} forged) where the "
                       "device is not OpenSSL",
                       control.mismatches(got, batch), 0)
        checks.at_most("control_programs", "programs compiled or loaded for "
                       "the verdict control",
                       kernel_programs(s for s in tracing.RECORDER.snapshot()
                                       if s["ts"] >= t_control), 0)

        # -- metrics ------------------------------------------------------
        first = min((s for s in spans if s["name"] in
                     ("fastsync.verify", "fastsync.lookahead")),
                    key=accounting.span_end)
        lat = probe["latency_s"]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[:cell["chips"]])
        size = index["size"]
        harness = {
            "sync_blocks_per_s": acct["blocks_per_s"],
            "rpc_status_p95_ms": 1e3 * accounting.percentile(lat, 95),
            "boot_to_first_window_s": accounting.span_end(first) - t_boot,
            "setup_s": setup_s,
            "link_util_pct": 100.0 * sum(size[h - 1] for h in
                                         acct["heights"]) /
            (n_sources * node_cfg.p2p.recv_rate * acct["elapsed_s"]),
            "hbm_peak_MiB": peak / 2**20 if peak else None,
            "device_kind": kind,
            "bucket_lanes": cb._bucket(WINDOW_BLOCKS * n_vals),
            "bucket_templates": cb._bucket(WINDOW_BLOCKS),
        }
        say(f"prober: {len(lat)} probes, p50 "
            f"{1e3 * accounting.percentile(lat, 50):.2f} ms, p95 "
            f"{harness['rpc_status_p95_ms']:.2f} ms, sent late by at most "
            f"{1e3 * max(probe['late_s'], default=0.0):.2f} ms (mean "
            f"{1e3 * sum(probe['late_s']) / max(1, len(probe['late_s'])):.2f}"
            f" ms), unanswered at the close {probe['unanswered']}")
        headroom = chain_plan(cell)["headroom"]
        say(f"interval: {acct['windows']} whole reactor windows, "
            f"{acct['applied']} heights in {acct['elapsed_s']:.3f}s; node at "
            f"{height_open} at the open and {height_close} of "
            f"{n_blocks - 1} served at the close "
            f"({height_close - height_open - acct['applied']} heights "
            "outside the interval); level "
            f"{headroom * height_close / (n_blocks - 1):.2f} of the "
            f"parent's expected, the tip at {headroom:.2f}")
        say(absent_report(index, acct["heights"], n_vals))
        say(powers_report(seed, n_vals, valset, powers, acct["heights"]))
        reduced = None
        if trace and platform == "tpu":
            reduced = reduce_device_trace(devtrace, trace_dir, spans,
                                          anchor_epoch, trace_end_epoch)
        ctx = {
            "spans": accounting.in_interval(spans, acct["t_first"],
                                            acct["t_last"]),
            "boot_spans": [s for s in spans
                           if accounting.span_end(s) <= t_open],
            "hists": hists, "harness": harness, "trace": reduced,
            "notes": [],
        }
        metrics, not_measured = {}, []
        if trace:
            for m in cell["per_layer"]:
                v = reducers.read_metric(m["spec"], ctx)
                if v is None:
                    not_measured.append(m["name"])
                else:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        else:
            for m in cell["end_to_end"]:
                metrics[m["name"]] = {"value": harness[m["name"]],
                                      "unit": m["unit"]}
        for note in ctx["notes"]:
            say(note)
        result = {"correct": checks.ok, "attempted": acct["attempted"],
                  "failed": acct["failed"], "metrics": metrics,
                  "device": {"platform": platform, "kind": kind,
                             "count": len(devs),
                             "memory_peak_bytes": peak or None}}
        if not_measured:
            result["not_measured"] = not_measured
        if reduced is not None:
            result["device"].update(busy_s=reduced["busy_s"],
                                    window_s=reduced["window_s"])
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
        result["checks"] = checks.compared    # last in the line
        return result
    finally:
        dog.cancel()
        if closer is not None:
            closer.call_off()
        if tracing_on:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        try:
            if node is not None:
                node.stop()
        finally:
            kids.stop_all()
            shutil.rmtree(workdir, ignore_errors=True)

"""SupervisedBackend: runtime fault tolerance for the crypto ladder.

`crypto/backend.py` picks ONE implementation at construction and never
swaps it — a mid-flight device failure (XLA error, OOM, runtime hang)
surfaces there as an exception in consensus or fast-sync, or worse,
could be mistaken for a bad signature.  Hardware verification pipelines treat accelerator
failure as a first-class recoverable event with a slower verified path
behind it (cf. arXiv:2104.06968, arXiv:2112.02229); this wrapper gives
the framework that property:

  * a fallback LADDER (tpu -> native -> python) where every rung answers
    the same Backend protocol; the python bigint floor cannot fail,
  * per-call TIMEOUTS on device rungs (a hung XLA call must not wedge
    the consensus thread forever),
  * bounded RETRY on the device rung before a call falls down the ladder,
  * a CIRCUIT BREAKER per rung: K consecutive faults trip it OPEN (calls
    skip the rung), a cooldown later it goes HALF-OPEN and admits one
    probe; a successful probe restores the rung (CLOSED),
  * optional SPOT CHECKS: every Nth device verify re-checks one sampled
    lane on the golden reference — a silently corrupting device is
    demoted to a fault instead of poisoning consensus,
  * deterministic fault injection via TM_CHAOS_CRYPTO (utils/chaos.py)
    so all of the above is testable on healthy hardware.

THE INVARIANT: an infrastructure error is never reported as "bad
signature".  Every verify returns the reference answer (computed on a
lower rung if need be); `DeviceFault` escapes only when every rung is
unavailable, and callers (fast-sync, vote tally, light client) treat it
as retryable — never as peer misbehavior.
"""

from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout

import numpy as np

from tendermint_tpu.utils import tracing
from tendermint_tpu.utils.chaos import CryptoChaos, DeviceFault
from tendermint_tpu.utils.log import get_logger
from tendermint_tpu.utils.metrics import REGISTRY, Summary

log = get_logger("crypto")

# breaker states
CLOSED, HALF_OPEN, OPEN = "closed", "half-open", "open"

# ladder order: fastest rung first, golden reference floor last
LADDER_ORDER = ("tpu", "native", "python")


class _Rung:
    """One ladder rung plus its breaker state (guarded by the
    supervisor's lock)."""

    def __init__(self, name: str, backend, is_device: bool):
        self.name = name
        self.backend = backend
        self.is_device = is_device
        self.state = CLOSED
        self.consecutive_faults = 0
        self.opened_at = 0.0
        self.trips = 0
        self.recoveries = 0
        self.faults = 0
        self.calls = 0
        self.latency = Summary()

    def snapshot(self) -> dict:
        return {"name": self.name, "state": self.state,
                "calls": self.calls, "faults": self.faults,
                "consecutive_faults": self.consecutive_faults,
                "trips": self.trips, "recoveries": self.recoveries,
                "latency_mean_s": round(self.latency.mean, 6)}


def _env_num(name: str, cast, default):
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return cast(v)
    except ValueError:
        raise ValueError(f"{name}={v!r} is not a valid {cast.__name__}")


class SupervisedBackend:
    """Fronts a ladder of Backend rungs with retry, timeout, breaker, and
    spot-check supervision.  Same Backend protocol as the rungs, so
    consensus/fast-sync/light cannot tell it apart from a bare backend."""

    name = "supervised"

    def __init__(self, rungs: list[tuple[str, object]],
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 call_timeout_s: float = 60.0,
                 retries: int = 1,
                 spot_check_every: int = 0,
                 chaos: CryptoChaos | None = None):
        if not rungs:
            raise ValueError("supervised backend needs at least one rung")
        # only non-floor rungs are supervised as "devices": the last rung
        # is the trusted floor — no timeout thread, no chaos, and its
        # exceptions (structural errors like set_key misuse) propagate
        self._rungs = [_Rung(n, b, i < len(rungs) - 1)
                       for i, (n, b) in enumerate(rungs)]
        self.breaker_threshold = max(1, breaker_threshold)
        self.breaker_cooldown_s = breaker_cooldown_s
        self.call_timeout_s = call_timeout_s
        self.retries = max(0, retries)
        self.spot_check_every = max(0, spot_check_every)
        # explicit kwarg > installed ChaosConfig (scenario engine) >
        # TM_CHAOS_CRYPTO env (standalone node); see utils/chaos.py
        self.chaos = chaos if chaos is not None else CryptoChaos.current()
        self._lock = threading.Lock()
        self._spot_count = 0
        # timeout enforcement: the rung call runs on a worker and we wait
        # with a deadline; a truly hung device call leaks its worker (it
        # cannot be cancelled) so the pool must tolerate a few zombies
        self._pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="crypto-supervisor")

    # -- ladder construction -------------------------------------------
    @classmethod
    def build(cls, primary: str = "tpu", **knobs) -> "SupervisedBackend":
        """Construct the standard ladder starting at `primary`, skipping
        rungs whose deps are missing, always ending on the python floor.
        Knob defaults come from TM_CRYPTO_* env vars so the supervised
        backend is fully configurable without a config file."""
        from tendermint_tpu.crypto import backend as cb
        knobs.setdefault("breaker_threshold",
                         _env_num("TM_CRYPTO_BREAKER_THRESHOLD", int, 3))
        knobs.setdefault("breaker_cooldown_s",
                         _env_num("TM_CRYPTO_BREAKER_COOLDOWN", float, 30.0))
        knobs.setdefault("call_timeout_s",
                         _env_num("TM_CRYPTO_TIMEOUT", float, 60.0))
        knobs.setdefault("retries", _env_num("TM_CRYPTO_RETRIES", int, 1))
        knobs.setdefault("spot_check_every",
                         _env_num("TM_CRYPTO_SPOT_CHECK", int, 0))
        names = (LADDER_ORDER[LADDER_ORDER.index(primary):]
                 if primary in LADDER_ORDER else (primary, "python"))
        rungs: list[tuple[str, object]] = []
        for n in names:
            try:
                rungs.append((n, cb._BACKENDS[n]()))
            except Exception as e:
                log.warn("crypto ladder rung unavailable; skipping",
                         rung=n, error=str(e))
        if not rungs or rungs[-1][0] != "python":
            rungs.append(("python", cb.PythonBackend()))
        return cls(rungs, **knobs)

    # -- breaker mechanics ---------------------------------------------
    def _admit(self, rung: _Rung) -> bool:
        """May a call use this rung right now?  OPEN rungs past their
        cooldown transition to HALF_OPEN and admit the caller as the
        probe."""
        if not rung.is_device:
            return True                      # the floor is always admitted
        with self._lock:
            if rung.state == CLOSED or rung.state == HALF_OPEN:
                return True
            if time.monotonic() - rung.opened_at >= self.breaker_cooldown_s:
                rung.state = HALF_OPEN
                log.info("crypto breaker half-open; probing rung",
                         rung=rung.name)
                return True
            return False

    def _on_fault(self, rung: _Rung, err: BaseException) -> None:
        with self._lock:
            rung.faults += 1
            rung.consecutive_faults += 1
            REGISTRY.crypto_device_faults.inc()
            REGISTRY.crypto_rung_faults.labels(rung.name).inc()
            tripped = False
            if rung.state == HALF_OPEN:
                # failed probe: straight back to OPEN, fresh cooldown
                rung.state = OPEN
                rung.opened_at = time.monotonic()
                rung.trips += 1
                tripped = True
            elif (rung.state == CLOSED and
                    rung.consecutive_faults >= self.breaker_threshold):
                rung.state = OPEN
                rung.opened_at = time.monotonic()
                rung.trips += 1
                tripped = True
            if tripped:
                REGISTRY.crypto_breaker_trips.inc()
        if tripped:
            log.warn("crypto breaker tripped", rung=rung.name,
                     fault=str(err)[:200],
                     consecutive=rung.consecutive_faults)
        else:
            log.warn("crypto device fault", rung=rung.name,
                     fault=str(err)[:200])

    def _on_success(self, rung: _Rung) -> None:
        with self._lock:
            if rung.state == HALF_OPEN:
                rung.state = CLOSED
                rung.recoveries += 1
                REGISTRY.crypto_breaker_recoveries.inc()
                log.info("crypto breaker recovered; rung restored",
                         rung=rung.name)
            rung.consecutive_faults = 0

    # -- invocation -----------------------------------------------------
    def _await(self, fut, what: str):
        """Wait for a device call under `call_timeout_s`.  The first call
        of a shape compiles (and a cold set builds its tables) for
        minutes on an empty cache; that is not a hung device, so the
        deadline is not enforced while the backend reports a cold
        dispatch in flight — the breaker must not demote the device
        during boot."""
        from tendermint_tpu.crypto import backend as cb
        while True:
            try:
                return fut.result(timeout=self.call_timeout_s)
            except FutureTimeout:
                if cb.cold_dispatch_in_flight():
                    continue
                fut.cancel()
                raise DeviceFault(f"{what} exceeded the "
                                  f"{self.call_timeout_s}s call timeout")

    def _invoke(self, rung: _Rung, method: str, args: tuple):
        """One attempt on one rung: chaos injection, timeout enforcement,
        latency accounting.  Any exception or timeout from a device rung
        is normalized to DeviceFault; floor-rung exceptions propagate
        (they are caller bugs, not infrastructure)."""
        fn = _rung_fn(rung.backend, method)
        chaos = self.chaos if rung.is_device else None

        def run():
            if chaos is not None:
                chaos.before_call()
            out = fn(*args)
            if chaos is not None:
                out = chaos.corrupt(out)
            return out

        t0 = time.perf_counter()
        rung.calls += 1
        REGISTRY.crypto_rung_calls.labels(rung.name).inc()
        # CAT_NONE: the supervised wrapper's wall clock double-counts the
        # categorized spans the backend emits inside it
        with tracing.span("crypto.call", cat=tracing.CAT_NONE,
                          rung=rung.name, method=method):
            if not rung.is_device:
                out = run()
            else:
                try:
                    if self.call_timeout_s > 0:
                        out = self._await(self._pool.submit(run),
                                          f"{rung.name}.{method}")
                    else:
                        out = run()
                except DeviceFault:
                    raise
                except Exception as e:
                    raise DeviceFault(
                        f"{rung.name}.{method} failed: {e!r}") from e
        rung.latency.observe(time.perf_counter() - t0)
        return out

    def _supervised(self, method: str, args: tuple, spot=None):
        """Run `method` down the ladder.  `spot` maps (out, lane) ->
        (pub, msg, sig) bytes for spot-check re-verification of one
        sampled lane on the golden reference."""
        last_fault: BaseException | None = None
        for ri, rung in enumerate(self._rungs):
            if not self._admit(rung):
                continue
            if ri > 0:
                REGISTRY.crypto_fallback_calls.inc()
            attempts = 1 + (self.retries if rung.is_device else 0)
            for _ in range(attempts):
                try:
                    out = self._invoke(rung, method, args)
                    if (spot is not None and rung.is_device and
                            not self._spot_ok(out, spot)):
                        raise DeviceFault(
                            f"{rung.name}.{method} spot check mismatch: "
                            "device answer contradicts the reference")
                    self._on_success(rung)
                    return out
                except DeviceFault as e:
                    last_fault = e
                    self._on_fault(rung, e)
                    with self._lock:
                        open_now = rung.state == OPEN
                    if open_now:
                        break                # tripped: stop retrying here
        raise DeviceFault(
            f"all crypto rungs failed for {method}: {last_fault}")

    def _spot_ok(self, out, spot) -> bool:
        """Every Nth device verify re-checks one deterministic lane on
        the bigint reference.  True = consistent (or checking disabled)."""
        if self.spot_check_every <= 0:
            return True
        n = len(out)
        if n == 0:
            return True
        with self._lock:
            self._spot_count += 1
            if self._spot_count % self.spot_check_every != 0:
                return True
            lane = self._spot_count % n
        REGISTRY.crypto_spot_checks.inc()
        from tendermint_tpu.crypto import pure_ed25519 as _ref
        pub, msg, sig = spot(lane)
        want = _ref.verify(bytes(pub), bytes(msg), bytes(sig))
        if bool(out[lane]) == want:
            return True
        REGISTRY.crypto_spot_check_mismatches.inc()
        return False

    # -- Backend protocol ----------------------------------------------
    def verify_batch(self, pubkeys, msgs, sigs) -> np.ndarray:
        return self._supervised(
            "verify_batch", (pubkeys, msgs, sigs),
            spot=lambda i: (np.asarray(pubkeys)[i].tobytes(),
                            np.asarray(msgs)[i].tobytes(),
                            np.asarray(sigs)[i].tobytes()))

    def verify_grouped(self, set_key, val_pubs, val_idx, msgs,
                       sigs) -> np.ndarray:
        return self._supervised(
            "verify_grouped", (set_key, val_pubs, val_idx, msgs, sigs),
            spot=lambda i: (
                np.asarray(val_pubs)[int(np.asarray(val_idx)[i])].tobytes(),
                np.asarray(msgs)[i].tobytes(),
                np.asarray(sigs)[i].tobytes()))

    def verify_grouped_templated(self, set_key, val_pubs, val_idx,
                                 tmpl_idx, templates, sigs) -> np.ndarray:
        return self._supervised(
            "verify_grouped_templated",
            (set_key, val_pubs, val_idx, tmpl_idx, templates, sigs),
            spot=lambda i: (
                np.asarray(val_pubs)[int(np.asarray(val_idx)[i])].tobytes(),
                np.asarray(templates)[
                    int(np.asarray(tmpl_idx)[i])].tobytes(),
                np.asarray(sigs)[i].tobytes()))

    def _active_rung(self) -> _Rung | None:
        """First rung the breaker currently admits."""
        for rung in self._rungs:
            if self._admit(rung):
                return rung
        return None

    def active_rung_name(self) -> str | None:
        """Name of the rung the ladder would serve from right now — the
        hook consumers (vote-ingest micro-batching, scenario manifests)
        use to make device-vs-scalar decisions through the supervisor
        without reaching into breaker internals."""
        rung = self._active_rung()
        return rung.name if rung is not None else None

    # -- passthroughs ---------------------------------------------------
    def tables_cached(self, set_key: bytes) -> bool:
        """True when the ACTIVE rung would serve this set without a
        multi-second build: device rungs delegate; CPU rungs need no
        tables, so a tripped-to-CPU ladder reports warm."""
        rung = self._active_rung()
        if rung is None:
            return True
        fn = getattr(rung.backend, "tables_cached", None)
        return True if fn is None else fn(set_key)

    def precompile_for_validators(self, vals, stage: str = "all",
                                  stop=None) -> None:
        """Warm-up is best-effort: a fault during precompile must not
        trip the breaker (nothing was being verified) or crash boot."""
        for rung in self._rungs:
            fn = getattr(rung.backend, "precompile_for_validators", None)
            if fn is None:
                continue
            try:
                fn(vals, stage, stop)
            except Exception:
                log.exception("crypto precompile failed on rung",
                              rung=rung.name)
            return

    def warm_derive(self, n_vals: int) -> None:
        """A sighted set change (`backend.valset_change_ahead`), handed
        to the first rung that derives tables."""
        for rung in self._rungs:
            fn = getattr(rung.backend, "warm_derive", None)
            if fn is not None:
                fn(n_vals)
                return

    # -- introspection --------------------------------------------------
    def supervisor_status(self) -> dict:
        """Breaker/ladder state for the RPC status endpoint and tests."""
        with self._lock:
            rungs = [r.snapshot() for r in self._rungs]
        active = self._active_rung()
        return {
            "active_rung": active.name if active is not None else None,
            "breaker_threshold": self.breaker_threshold,
            "breaker_cooldown_s": self.breaker_cooldown_s,
            "call_timeout_s": self.call_timeout_s,
            "retries": self.retries,
            "spot_check_every": self.spot_check_every,
            "chaos": (f"{self.chaos.mode}:every={self.chaos.every}"
                      if self.chaos is not None and self.chaos.active
                      else None),
            "rungs": rungs,
        }


def _rung_fn(backend, method: str):
    """Resolve `method` on a rung, adapting down the protocol the same
    way the module-level helpers in crypto/backend.py do (a rung without
    the templated form gathers host-side and batches plainly)."""
    fn = getattr(backend, method, None)
    if fn is not None:
        return fn
    if method == "verify_grouped":
        return lambda set_key, val_pubs, val_idx, msgs, sigs: \
            backend.verify_batch(np.asarray(val_pubs)[np.asarray(val_idx)],
                                 msgs, sigs)
    if method == "verify_grouped_templated":
        inner = _rung_fn(backend, "verify_grouped")
        return lambda set_key, val_pubs, val_idx, tmpl_idx, templates, \
            sigs: inner(set_key, val_pubs, val_idx,
                        np.asarray(templates)[np.asarray(tmpl_idx)], sigs)
    raise AttributeError(f"rung backend {backend!r} lacks {method}")

"""Golden fixture tests for each tmlint rule family: every rule must
catch a seeded violation and stay quiet on the compliant twin.  These
are the proof that a zero-finding run over the real package means
"checked and clean", not "checker inert"."""

import textwrap

import pytest

from tendermint_tpu.analysis import lint_paths


def lint_src(tmp_path, src, relpath="mod.py"):
    """Lint one fixture source; returns the findings list."""
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    res = lint_paths([str(tmp_path)], root=str(tmp_path))
    assert not res.errors, res.errors
    return res.findings


def rules_of(findings):
    return {f.rule for f in findings}


# -- lock discipline --------------------------------------------------------


def test_lock_order_cycle_across_classes(tmp_path):
    findings = lint_src(tmp_path, """
        import threading

        class A:
            def __init__(self, b):
                self._lock = threading.Lock()
                self.b = b

            def step(self):
                with self._lock:
                    self.b.poke()

        class B:
            def __init__(self, a):
                self._lock = threading.Lock()
                self.a = a

            def poke(self):
                with self._lock:
                    pass

            def reverse(self):
                with self._lock:
                    self.a.step()
        """)
    cycles = [f for f in findings if f.rule == "lock-order"]
    assert cycles, findings
    assert "A._lock" in cycles[0].message and "B._lock" in cycles[0].message


def test_lock_order_quiet_on_consistent_order(tmp_path):
    findings = lint_src(tmp_path, """
        import threading

        class A:
            def __init__(self, b):
                self._lock = threading.Lock()
                self.b = b

            def step(self):
                with self._lock:
                    self.b.poke()

        class B:
            def __init__(self):
                self._lock = threading.Lock()

            def poke(self):
                with self._lock:
                    pass
        """)
    assert "lock-order" not in rules_of(findings)


def test_unlocked_write_flagged_and_locked_twin_quiet(tmp_path):
    findings = lint_src(tmp_path, """
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def add(self, x):
                with self._lock:
                    self._items.append(x)

            def clear(self):
                self._items = []     # seeded violation

        class CleanPool:
            def __init__(self):
                self._lock = threading.Lock()
                self._items = []

            def add(self, x):
                with self._lock:
                    self._items.append(x)

            def clear(self):
                with self._lock:
                    self._items = []
        """)
    bad = [f for f in findings if f.rule == "unlocked-write"]
    assert len(bad) == 1
    assert bad[0].symbol == "Pool.clear"


def test_unlocked_write_allows_init_and_private_helper(tmp_path):
    # construction is single-threaded; a private helper whose every
    # caller holds the lock inherits the caller's lock
    findings = lint_src(tmp_path, """
        import threading

        class Meter:
            def __init__(self):
                self._lock = threading.Lock()
                self._total = 0
                self._load()

            def update(self, n):
                with self._lock:
                    self._total += n
                    self._roll()

            def _roll(self):
                self._total = min(self._total, 100)

            def _load(self):
                self._total = 0
        """)
    assert "unlocked-write" not in rules_of(findings)


# -- JAX hot-path hygiene ---------------------------------------------------


def test_host_sync_item_flagged_on_hot_path(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp

        def count(xs):
            s = jnp.sum(xs)
            return s.item()     # seeded violation
        """, relpath="ops/agg.py")
    syncs = [f for f in findings if f.rule == "jax-host-sync"]
    assert syncs and syncs[0].symbol == "count"


def test_host_sync_quiet_off_hot_path(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp

        def count(xs):
            return jnp.sum(xs).item()
        """, relpath="rpc/agg.py")
    assert "jax-host-sync" not in rules_of(findings)


def test_host_sync_int_of_tainted_value(tmp_path):
    findings = lint_src(tmp_path, """
        import jax.numpy as jnp

        def total(xs):
            s = jnp.sum(xs)
            return int(s)       # seeded violation
        """, relpath="crypto/agg.py")
    assert "jax-host-sync" in rules_of(findings)


def test_retrace_mutable_global_closure(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        _CACHE = {}

        @jax.jit
        def f(x):
            return x + len(_CACHE)   # retrace hazard
        """, relpath="ops/f.py")
    assert "jax-retrace" in rules_of(findings)


def test_retrace_python_if_on_traced_arg(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x > 0:                # trace-time branch on traced value
                return x
            return -x
        """, relpath="ops/g.py")
    assert "jax-retrace" in rules_of(findings)


def test_retrace_quiet_on_shape_branch(tmp_path):
    findings = lint_src(tmp_path, """
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 4:       # static at trace time
                return x
            return -x
        """, relpath="ops/h.py")
    assert "jax-retrace" not in rules_of(findings)


def test_static_argnums_list_flagged(tmp_path):
    findings = lint_src(tmp_path, """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=[0])
        def f(n, x):
            return x * n
        """, relpath="ops/s.py")
    assert "jax-static-argnums" in rules_of(findings)


# -- route gating / write containment ----------------------------------------


def test_route_gating_flags_ungated_debug_route(tmp_path):
    findings = lint_src(tmp_path, """
        class Routes:
            def __init__(self, node, config):
                self.table = {
                    "status": self.status,
                    "debug_stacks": self.debug_stacks,   # outside gate
                }
                if getattr(config.rpc, "unsafe", False):
                    self.table.update({
                        "unsafe_flush": self.unsafe_flush,
                    })

            def status(self):
                return {}

            def debug_stacks(self):
                return {}

            def unsafe_flush(self):
                return {}
        """)
    gated = [f for f in findings if f.rule == "route-gating"]
    assert len(gated) == 1
    assert "debug_stacks" in gated[0].message


def test_route_write_containment(tmp_path):
    findings = lint_src(tmp_path, """
        import os

        class Routes:
            def __init__(self, config):
                self.table = {}
                if getattr(config.rpc, "unsafe", False):
                    self.table.update({
                        "debug_dump": self.debug_dump,
                        "debug_dump_safe": self.debug_dump_safe,
                    })

            def debug_dump(self, path):
                with open(path, "w") as f:    # uncontained write
                    f.write("x")

            def debug_dump_safe(self, path):
                real = os.path.realpath(path)
                with open(real, "w") as f:
                    f.write("x")
        """)
    writes = [f for f in findings if f.rule == "route-write-containment"]
    assert len(writes) == 1
    assert "debug_dump" in writes[0].message


# -- span / metric conventions -----------------------------------------------


def test_span_category_unknown_prefix_flagged(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu.utils import tracing

        def work():
            with tracing.span("mystery.phase"):
                pass

        def fine():
            with tracing.span("verify.dispatch", lanes=8):
                pass

        def also_fine():
            with tracing.span("mystery.other", cat=tracing.CAT_NONE):
                pass
        """)
    spans = [f for f in findings if f.rule == "span-category"]
    assert len(spans) == 1
    assert "mystery.phase" in spans[0].message


def test_span_category_covers_timeline_prefixes(tmp_path):
    """Golden fixtures for the consensus timeline plane: consensus.*
    and telemetry.* names resolve through the prefix table, so the
    lifecycle / collector spans need no cat= keyword — while a typo'd
    prefix right next to them is still flagged."""
    findings = lint_src(tmp_path, """
        from tendermint_tpu.utils import tracing

        def lifecycle():
            with tracing.span("consensus.stage.propose"):
                pass
            with tracing.span("consensus.height"):
                pass

        def collector():
            with tracing.span("telemetry.merge"):
                pass

        def typo():
            with tracing.span("consenus.stage.propose"):
                pass
        """)
    spans = [f for f in findings if f.rule == "span-category"]
    assert len(spans) == 1
    assert "consenus.stage.propose" in spans[0].message


def test_metric_name_series_collision_and_bad_label(tmp_path):
    findings = lint_src(tmp_path, """
        class Registry:
            def __init__(self):
                self.rpc_s = Histogram()        # generates rpc_s_count
                self.rpc_s_count = Counter()    # collides
                self.peers = GaugeVec("le")     # reserved label
        """)
    msgs = [f.message for f in findings if f.rule == "metric-name"]
    assert any("collides" in m for m in msgs), findings
    assert any("reserved" in m for m in msgs), findings


def test_scenario_budget_flags_stress_without_budgets(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu.scenarios.engine import register

        def _safety(ctx, obs):
            pass

        # stress tier (smoke absent) with no budgets kwarg at all
        @register("storm-a", "a storm", safety=[("s", _safety)],
                  liveness=[("l", _safety)], budget_s=60.0)
        def storm_a(ctx):
            return {}

        # explicit smoke=False with an EMPTY budgets dict
        @register("storm-b", "b storm", safety=[("s", _safety)],
                  liveness=[("l", _safety)], smoke=False, budgets={})
        def storm_b(ctx):
            return {}
        """)
    hits = [f for f in findings if f.rule == "scenario-budget"]
    assert len(hits) == 2, findings
    assert "storm-a" in hits[0].message
    assert "storm-b" in hits[1].message


def test_scenario_budget_quiet_on_smoke_and_budgeted(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu.scenarios.engine import register

        def _safety(ctx, obs):
            pass

        # smoke tier: budgets optional
        @register("quick", "a smoke", safety=[("s", _safety)],
                  liveness=[("l", _safety)], smoke=True)
        def quick(ctx):
            return {}

        # stress tier WITH a declared budget: compliant
        @register("storm", "a storm", safety=[("s", _safety)],
                  liveness=[("l", _safety)], smoke=False,
                  budgets={"commit_latency_p99": {"max": 30.0}})
        def storm(ctx):
            return {}

        # an unrelated register() (e.g. the rule registry) is ignored
        def register_other(cls):
            return cls

        table = register_other(dict)
        """)
    assert [f for f in findings if f.rule == "scenario-budget"] == []


def test_scenario_budget_statesync_registration_shapes(tmp_path):
    # Golden twin of the statesync scenario registrations: a stress rig
    # whose budgets carry only "min" bounds (a speedup floor is still a
    # budget), a stress rig mixing min and max bounds, and a smoke-tier
    # torn-tail probe with no budgets at all.  All three are compliant;
    # the variant that drops the budgets kwarg is not.
    findings = lint_src(tmp_path, """
        from tendermint_tpu.scenarios.engine import register

        def _safety(ctx, obs):
            pass

        @register("snapshot-join-twin", "rejoin from snapshot",
                  safety=[("restore-parity", _safety)],
                  liveness=[("victim-synced", _safety)],
                  smoke=False, budget_s=420.0,
                  budgets={"catchup_speedup_x": {"min": 10.0}})
        def join_twin(ctx):
            return {}

        @register("snapshot-tamper-twin", "reject corrupted chunks",
                  safety=[("no-silent-acceptance", _safety)],
                  liveness=[("restored", _safety)],
                  smoke=False, budget_s=120.0,
                  budgets={"tamper_restore_s": {"max": 30.0},
                           "tamper_chunks_rejected": {"min": 1.0}})
        def tamper_twin(ctx):
            return {}

        @register("snapshot-torn-tail-twin", "recover past torn tail",
                  safety=[("torn-discarded", _safety)],
                  liveness=[("replayed", _safety)], smoke=True)
        def torn_twin(ctx):
            return {}

        @register("snapshot-join-naked", "stress rig, no budgets",
                  safety=[("s", _safety)], liveness=[("l", _safety)],
                  smoke=False, budget_s=420.0)
        def join_naked(ctx):
            return {}
        """)
    hits = [f for f in findings if f.rule == "scenario-budget"]
    assert len(hits) == 1, findings
    assert "snapshot-join-naked" in hits[0].message


def test_scenario_budget_mempool_registration_shapes(tmp_path):
    # Golden twin of the mempool ingress registrations: the stress-tier
    # flood gate declares min AND max bounds (an offered-load floor
    # plus admission-latency ceilings), the smoke-tier eviction storm
    # carries budgets it is not obliged to, and the variant that drops
    # the flood's budgets kwarg is the seeded violation.
    findings = lint_src(tmp_path, """
        from tendermint_tpu.scenarios.engine import register

        def _safety(ctx, obs):
            pass

        @register("mempool-flood-twin", "100k tx/s ingress flood",
                  safety=[("zero-silent-drops", _safety)],
                  liveness=[("rig-commits-through-flood", _safety)],
                  smoke=False, budget_s=420.0, backend="rig",
                  budgets={"offered_per_sec": {"min": 100000.0},
                           "admit_p50_s": {"max": 0.001},
                           "admit_p99_s": {"max": 0.25},
                           "commit_latency_p99": {"max": 30.0}})
        def flood_twin(ctx):
            return {}

        @register("eviction-storm-twin", "priority eviction audit",
                  safety=[("no-priority-inversion", _safety)],
                  liveness=[("storm-reached-overload", _safety)],
                  smoke=True, budget_s=180.0,
                  budgets={"priority_inversions": {"max": 0.0},
                           "unaccounted_rejections": {"max": 0.0}})
        def storm_twin(ctx):
            return {}

        @register("mempool-flood-naked", "flood without budgets",
                  safety=[("s", _safety)], liveness=[("l", _safety)],
                  smoke=False, budget_s=420.0, backend="rig")
        def flood_naked(ctx):
            return {}
        """)
    hits = [f for f in findings if f.rule == "scenario-budget"]
    assert len(hits) == 1, findings
    assert "mempool-flood-naked" in hits[0].message


# -- batch-plane producer discipline ---------------------------------------


def test_batchplane_flags_direct_backend_call_in_producer(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu.crypto import backend as cb

        def verify_commit_any(new_set, idxs, msgs, sigs):
            return cb.verify_grouped(new_set.set_key(),
                                     new_set.pubs_matrix(), idxs,
                                     msgs, sigs)
        """, relpath="light/client.py")
    hits = [f for f in findings if f.rule == "batchplane-producer"]
    assert len(hits) == 1, findings
    assert "cb.verify_grouped" in hits[0].message


def test_batchplane_flags_from_import_alias(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu.crypto.backend import verify_batch as vb

        def check_sigs(pubs, msgs, sigs):
            return vb(pubs, msgs, sigs)
        """, relpath="mempool/mempool.py")
    hits = [f for f in findings if f.rule == "batchplane-producer"]
    assert len(hits) == 1, findings


def test_batchplane_quiet_on_plane_submission_twin(tmp_path):
    findings = lint_src(tmp_path, """
        from tendermint_tpu import batchplane

        def verify_commit_any(new_set, idxs, msgs, sigs):
            return batchplane.verify_grouped(
                new_set.set_key(), new_set.pubs_matrix(), idxs, msgs,
                sigs, producer="light", klass=batchplane.CLASS_LIGHT)
        """, relpath="light/client.py")
    assert not [f for f in findings if f.rule == "batchplane-producer"]


def test_batchplane_allows_scheduler_and_ladder_direct_calls(tmp_path):
    # the scheduler itself and non-producer layers stay direct by design
    src = """
        from tendermint_tpu.crypto import backend as cb

        def _run_grouped(set_key, val_pubs, idx, msgs, sigs):
            return cb.verify_grouped(set_key, val_pubs, idx, msgs, sigs)
        """
    for rel in ("batchplane/scheduler.py", "crypto/supervised.py"):
        findings = lint_src(tmp_path, src, relpath=rel)
        assert not [f for f in findings
                    if f.rule == "batchplane-producer"], rel


def test_rule_catalog_covers_all_families():
    from tendermint_tpu.analysis import all_rules
    names = {n for n, _ in all_rules()}
    assert {"lock-order", "unlocked-write", "jax-host-sync",
            "jax-retrace", "jax-static-argnums", "route-gating",
            "route-write-containment", "span-category", "metric-name",
            "scenario-budget", "batchplane-producer"} <= names

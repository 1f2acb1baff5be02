"""One expected failure, by name, and nothing else.

`test_bench_valset.py::test_every_accepted_configuration_boots_the_
programs_default_app` (PR 35) runs over every configuration of
`BENCHMARK.json` and holds each to `app == "kvstore"`, the program's
default.  That was true of every configuration then.  `catchup-churn-100v`
(PR 36) is the first whose deployment needs another app: it states
`valset_kvstore`, the kvstore that returns the chain's `val:` txs as
`EndBlock` diffs, and a chain whose validator set changes cannot be synced
by the default app.  The test's file is the benchmark's and not a
`model_config` PR's to edit, so its case for the new configuration is
marked as expected to fail here, where it can be read; what it was there
to guard (a configuration's app is one the program has, and the node
booted for a cell runs the app its file states) is asserted for every
configuration in `test_bench_churn_cell.py`.  A `benchmark` issue rewords
the test and deletes this file (PERF.md, Open questions)."""

import pytest

STALE = ("test_every_accepted_configuration_boots_the_programs_default_app"
         "[catchup-churn-100v]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == STALE:
            item.add_marker(pytest.mark.xfail(
                reason="catchup-churn-100v states valset_kvstore, not the "
                       "default app: the test predates configurations "
                       "with an app of their own", strict=False))

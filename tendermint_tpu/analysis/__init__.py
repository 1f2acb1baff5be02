"""tmlint: AST-based invariant checker for this codebase.

The framework carries three hand-maintained invariant families that
nothing used to enforce: lock discipline across the threaded modules
(the reference implementation leans on Go's race detector, which the
Python port lost), JAX hot-path hygiene (the runtime doctor can only
observe a shape-drift recompile or an implicit host sync on paths a
run happens to exercise), and registration conventions (unsafe-gating
of `debug_*`/`unsafe_*` RPC routes, category-prefixed span names feeding
`utils/attribution.py`, Prometheus-valid metric names).  tmlint makes
violations fail tier-1 instead of surfacing as a 12x bench regression or
a deadlocked replay.

Run it as `python -m tendermint_tpu.cli lint` (add `--json` for machine
output); `tests/test_tmlint_repo.py` runs the same pass in tier-1.

Rule families (see each module's docstring for details):

- `locks.py`     lock-order / unlocked-write   (lock discipline)
- `hotpath.py`   jax-host-sync / jax-retrace / jax-static-argnums
- `conventions.py` route-gating / route-write-containment /
                 span-category / metric-name
- `batchplane_rule.py` batchplane-producer (verify work must ride the
                 shared device batch plane)

Suppression and grandfathering:

- inline: append ``# tmlint: disable=<rule>[,<rule>...]`` (or
  ``disable=all``) to the offending line;
- baseline: `analysis/baseline.json` holds fingerprints of grandfathered
  findings — `cli lint --update-baseline` regenerates it.  New hot-path
  modules must not be baselined (README "Static analysis").
"""

from tendermint_tpu.analysis.core import (Finding, LintResult, all_rules,
                                          baseline_path, lint_paths,
                                          load_baseline, save_baseline)

# importing the rule modules registers their rule classes
from tendermint_tpu.analysis import (batchplane_rule, conventions,  # noqa: E402,F401  (registration import)
                                     hotpath, locks)

__all__ = ["Finding", "LintResult", "all_rules", "baseline_path",
           "lint_paths", "load_baseline", "save_baseline"]

"""tmlint rule: hot-path verify producers must ride the batch plane.

- **batchplane-producer**: modules on the verify hot path (``consensus/``,
  ``light/``, ``mempool/``, ``blockchain/``, ``types/``) must submit
  signature-verify work through ``tendermint_tpu.batchplane`` — never
  call ``crypto.backend``'s ``verify_batch`` / ``verify_grouped`` /
  ``verify_grouped_templated`` directly.  A direct call bypasses
  the shared scheduler: its lanes cannot coalesce with concurrent
  producers, ignore priority classes (a light-client flood would no
  longer yield to consensus votes), and skip the plane's occupancy /
  wait-time accounting, so the doctor's half-full-batch attribution
  under-reports.  The scheduler itself (``batchplane/``), the backend
  ladder (``crypto/``) and the device layers (``ops/``, ``parallel/``)
  stay direct by design.
"""

from __future__ import annotations

import ast

from tendermint_tpu.analysis.core import (FileCtx, Rule, call_name,
                                          register)

# path prefixes (posix, package-relative) where the rule applies
_PRODUCER_PREFIXES = ("consensus/", "light/", "mempool/", "blockchain/",
                      "types/")

_VERIFY_METHODS = {"verify_batch", "verify_grouped",
                   "verify_grouped_templated"}

_BACKEND_MODULE = "tendermint_tpu.crypto.backend"


def _backend_aliases(tree: ast.AST) -> tuple[set, set]:
    """(module_aliases, function_names) bound to crypto.backend in this
    file: ``from tendermint_tpu.crypto import backend as cb`` binds the
    alias ``cb``; ``from tendermint_tpu.crypto.backend import
    verify_grouped`` binds the bare function name."""
    mods: set[str] = set()
    fns: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == _BACKEND_MODULE:
                    mods.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            if node.module == "tendermint_tpu.crypto":
                for a in node.names:
                    if a.name == "backend":
                        mods.add(a.asname or "backend")
            elif node.module == _BACKEND_MODULE:
                for a in node.names:
                    if a.name in _VERIFY_METHODS:
                        fns.add(a.asname or a.name)
    return mods, fns


@register
class BatchPlaneProducerRule(Rule):
    name = "batchplane-producer"
    description = ("hot-path producers (consensus/light/mempool/"
                   "blockchain/types) must submit verify work through "
                   "the batch plane, not crypto.backend directly")

    def visit_file(self, ctx: FileCtx):
        rel = ctx.path.replace("\\", "/")
        for pre in ("tendermint_tpu/", "./"):
            if rel.startswith(pre):
                rel = rel[len(pre):]
        if not rel.startswith(_PRODUCER_PREFIXES):
            return
        mods, fns = _backend_aliases(ctx.tree)
        if not mods and not fns:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            hit = None
            if "." in name:
                base, meth = name.rsplit(".", 1)
                if base in mods and meth in _VERIFY_METHODS:
                    hit = name
            elif name in fns:
                hit = name
            if hit:
                yield ctx.finding(
                    self.name, node,
                    f"direct backend call '{hit}' bypasses the batch "
                    f"plane: lanes cannot coalesce with other producers "
                    f"and skip priority/fairness scheduling — submit via "
                    f"tendermint_tpu.batchplane with an explicit "
                    f"producer= and klass=")

"""tmlint JAX hot-path hygiene rules.

The PR-3 doctor can *observe* a shape-drift recompile or an implicit
host sync at runtime, but only on paths a run happens to exercise.
These rules catch the same hazards statically, in the modules that are
on the device hot path (``ops/``, ``crypto/``, ``parallel/``):

- **jax-host-sync**: an implicit device->host synchronization —
  ``.item()``, ``float()/int()/bool()`` on a value produced by a
  ``jnp.``/``jax.`` call or a ``*_jit`` dispatch, ``np.asarray()`` of
  such a value, and explicit ``.block_until_ready()``.  Each one stalls
  the dispatch pipeline; a sync inside a per-batch loop is the
  "scalar_tail" thief the doctor reports.  Deliberate sync points live
  in ``ALLOWED_SYNC_FUNCS`` (function-scope allowlist, stable across
  line shifts) or carry an inline ``# tmlint: disable=jax-host-sync``.

- **jax-retrace**: retrace/stale-trace hazards — a jit-decorated
  function reading a *mutable* module-level global (dict/list/set
  literal: mutating it later silently does NOT retrigger tracing), and
  Python ``if``/``while`` branching on the *value* of a traced argument
  (a ConcretizationTypeError at best, a silent per-value retrace via
  implicit bool sync at worst).  Branching on ``.shape``/``.ndim``/
  ``.dtype``/``len()``/``isinstance``/``is None`` is static and fine.

- **jax-static-argnums**: ``static_argnums`` must be an int or a tuple
  of ints; a list is unhashable in older jax versions and a common typo
  (``static_argnums=[0]`` where ``(0,)`` was meant) — and a non-int
  entry means a *value* is being marked static, which recompiles per
  value.
"""

from __future__ import annotations

import ast

from tendermint_tpu.analysis.core import (FileCtx, Rule, call_name,
                                          dotted_name, register)

# path fragments (posix, relative) that put a file on the device hot path
HOT_PATH_DIRS = ("ops/", "crypto/", "parallel/")

# deliberate sync points: (path suffix, enclosing qualname).  These are
# documented synchronization barriers — e.g. the table-build
# block_until_ready in crypto/backend.py commits comb tables to device
# memory before the fsync'd cache write, and verify() must read the
# lane-mask back to return Python bools.  Function-scoped (not
# line-numbered) so edits inside the file don't rot the allowlist.
ALLOWED_SYNC_FUNCS = {
    # verify API boundary: device lane-masks become Python bools
    # for the consensus/fast-sync callers — the sync IS the contract
    ("crypto/backend.py", "TpuBackend.verify_batch"),
    ("crypto/backend.py", "TpuBackend.verify_grouped"),
    ("crypto/backend.py", "TpuBackend.verify_grouped_templated"),
    # comb-table build commits tables to device memory before the
    # fsync'd on-disk cache write (backend.py "tbl.block_until_ready()")
    ("crypto/backend.py", "TpuBackend._build_tables"),
    # the warm-up thread exists to absorb the compile+first-dispatch wait
    ("crypto/backend.py", "TpuBackend._warm_verify_if_cold.warm"),
}

_HOST_CASTS = {"float", "int", "bool", "complex"}

# attribute/call contexts on a traced arg that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

# jax.* calls that return host objects (device handles, ints), not
# arrays — np.array() over these is not a device->host sync
_NON_ARRAY_JAX_CALLS = {
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.process_index",
}


def on_hot_path(path: str) -> bool:
    return any(f"/{d}" in f"/{path}" for d in HOT_PATH_DIRS)


def _is_allowed_sync(ctx: FileCtx, node: ast.AST) -> bool:
    qn = ctx.qualname_at(node)
    for suffix, func in ALLOWED_SYNC_FUNCS:
        if ctx.path.endswith(suffix) and qn == func:
            return True
    return False


# ---------------------------------------------------------------------------
# taint: which local names hold jax values?
# ---------------------------------------------------------------------------


def _expr_is_jax(node: ast.AST, tainted: set) -> bool:
    """True when the expression plausibly produces a traced/device
    value: rooted at jnp./jax., a *_jit(...) dispatch, or built from a
    tainted local."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            name = call_name(n)
            root = name.split(".", 1)[0]
            leaf = name.rsplit(".", 1)[-1]
            if name in _NON_ARRAY_JAX_CALLS:
                continue
            if root in ("jnp", "jax") or leaf.endswith("_jit"):
                return True
        elif isinstance(n, ast.Name) and n.id in tainted:
            return True
    return False


def _function_taint(fn: ast.AST) -> set:
    """Fixpoint over simple assignments: locals assigned from jax-ish
    expressions.  Parameters are NOT tainted (a helper taking `limbs`
    may legitimately receive numpy) — only provenance visible inside
    the function counts."""
    tainted: set = set()
    for _ in range(4):                       # small fixpoint
        grew = False
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            else:
                continue
            value = getattr(node, "value", None)
            if value is None or not _expr_is_jax(value, tainted):
                continue
            for tgt in targets:
                els = tgt.elts if isinstance(tgt, (ast.Tuple,
                                                   ast.List)) else [tgt]
                for el in els:
                    if isinstance(el, ast.Name) and el.id not in tainted:
                        tainted.add(el.id)
                        grew = True
        if not grew:
            break
    return tainted


# ---------------------------------------------------------------------------
# jit application discovery
# ---------------------------------------------------------------------------


def _jit_applications(tree: ast.AST):
    """Yield (call_or_decorator_node, static_argnums_value_node_or_None,
    target_fn_def_or_None) for every jax.jit application in the module:
    decorators (`@jax.jit`, `@partial(jax.jit, ...)`) and direct calls
    (`f_jit = jax.jit(f, ...)`)."""
    fn_defs = {n.name: n for n in ast.walk(tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}

    def is_jit_name(name: str) -> bool:
        return name in ("jit", "jax.jit", "pjit", "jax.pjit")

    def static_kw(call: ast.Call):
        for kw in call.keywords:
            if kw.arg in ("static_argnums", "static_argnames"):
                return kw.value if kw.arg == "static_argnums" else None
        return None

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    name = call_name(dec)
                    if is_jit_name(name):
                        yield dec, static_kw(dec), node
                    elif name.rsplit(".", 1)[-1] == "partial" and \
                            dec.args and \
                            is_jit_name(dotted_name(dec.args[0])):
                        yield dec, static_kw(dec), node
                elif is_jit_name(dotted_name(dec)):
                    yield dec, None, node
        elif isinstance(node, ast.Call) and is_jit_name(call_name(node)):
            target = None
            if node.args and isinstance(node.args[0], ast.Name):
                target = fn_defs.get(node.args[0].id)
            yield node, static_kw(node), target


def _static_param_names(fn, static_node) -> set:
    """Parameter names marked static via static_argnums (constant ints
    only; anything else is handled by the static-argnums rule)."""
    idxs: set = set()
    if isinstance(static_node, ast.Constant) and \
            isinstance(static_node.value, int):
        idxs = {static_node.value}
    elif isinstance(static_node, (ast.Tuple, ast.List)):
        idxs = {el.value for el in static_node.elts
                if isinstance(el, ast.Constant)
                and isinstance(el.value, int)}
    args = fn.args.posonlyargs + fn.args.args
    return {a.arg for i, a in enumerate(args) if i in idxs}


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------


@register
class HostSyncRule(Rule):
    name = "jax-host-sync"
    description = ("implicit device->host sync on the hot path "
                   "(.item(), float()/int()/bool() or np.asarray() of a "
                   "jax value, block_until_ready) outside the allowlist "
                   "of deliberate sync points")

    def visit_file(self, ctx: FileCtx):
        if not on_hot_path(ctx.path):
            return
        fns = [n for n in ast.walk(ctx.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        taint_by_fn = {id(fn): _function_taint(fn) for fn in fns}
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # .item() / .block_until_ready() on anything
            if isinstance(node.func, ast.Attribute):
                meth = node.func.attr
                if meth == "item":
                    if not _is_allowed_sync(ctx, node):
                        yield ctx.finding(
                            self.name, node,
                            ".item() forces a device->host sync; keep "
                            "the value on device or move the read to a "
                            "deliberate sync point")
                    continue
                if meth == "block_until_ready":
                    if not _is_allowed_sync(ctx, node):
                        yield ctx.finding(
                            self.name, node,
                            "block_until_ready() outside the allowlist "
                            "of deliberate sync points (ALLOWED_SYNC_"
                            "FUNCS in analysis/hotpath.py)")
                    continue
            name = call_name(node)
            leaf = name.rsplit(".", 1)[-1]
            root = name.split(".", 1)[0]
            is_cast = name in _HOST_CASTS
            is_np_pull = (root in ("np", "numpy", "onp")
                          and leaf in ("asarray", "array"))
            if not (is_cast or is_np_pull) or not node.args:
                continue
            arg = node.args[0]
            tainted = self._taint_for(ctx, node, taint_by_fn)
            if _expr_is_jax(arg, tainted):
                if _is_allowed_sync(ctx, node):
                    continue
                what = (f"{name}() on a jax value" if is_cast
                        else f"{name}() of a jax value")
                yield ctx.finding(
                    self.name, node,
                    f"{what} forces a device->host sync on the hot path")

    @staticmethod
    def _taint_for(ctx, node, taint_by_fn) -> set:
        cur = getattr(node, "_tmlint_parent", None)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return taint_by_fn.get(id(cur), set())
            cur = getattr(cur, "_tmlint_parent", None)
        return set()


@register
class RetraceRule(Rule):
    name = "jax-retrace"
    description = ("retrace/stale-trace hazard: jit function closing "
                   "over a mutable module global, or Python if/while on "
                   "the value of a traced argument")

    def visit_file(self, ctx: FileCtx):
        if not on_hot_path(ctx.path):
            return
        mutable_globals = self._mutable_globals(ctx.tree)
        for _, static_node, fn in _jit_applications(ctx.tree):
            if fn is None:
                continue
            static = _static_param_names(fn, static_node)
            yield from self._check_globals(ctx, fn, mutable_globals)
            yield from self._check_branches(ctx, fn, static)

    @staticmethod
    def _mutable_globals(tree) -> set:
        """Module-level names bound to dict/list/set literals or
        comprehensions — the containers whose later mutation a traced
        closure will never see."""
        out = set()
        body = getattr(tree, "body", ())
        for st in body:
            if isinstance(st, ast.Assign) and isinstance(
                    st.value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                               ast.ListComp, ast.SetComp)):
                for tgt in st.targets:
                    if isinstance(tgt, ast.Name):
                        out.add(tgt.id)
        return out

    def _check_globals(self, ctx, fn, mutable_globals):
        local = {a.arg for a in fn.args.posonlyargs + fn.args.args
                 + fn.args.kwonlyargs}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        local.add(tgt.id)
        for node in ast.walk(fn):
            if (isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in mutable_globals
                    and node.id not in local):
                yield ctx.finding(
                    self.name, node,
                    f"jit-traced function reads mutable module global "
                    f"'{node.id}'; mutating it later will NOT retrace — "
                    f"pass it as an argument or make it immutable")

    def _check_branches(self, ctx, fn, static_params):
        args = fn.args.posonlyargs + fn.args.args
        traced = {a.arg for a in args} - static_params - {"self"}
        if not traced:
            return
        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            bad = self._value_uses(node.test, traced)
            for name_node in bad:
                yield ctx.finding(
                    self.name, node,
                    f"Python {type(node).__name__.lower()} on the value "
                    f"of traced argument '{name_node.id}' "
                    f"(ConcretizationTypeError / silent host sync); "
                    f"branch on shapes, mark it static, or use "
                    f"jnp.where/lax.cond")

    @staticmethod
    def _value_uses(test, traced):
        """Name nodes of traced params whose *value* the test reads —
        shape/ndim/dtype/len/isinstance/`is None` uses are static and
        excluded."""
        static_parents: set = set()
        for n in ast.walk(test):
            if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
                static_parents.update(id(x) for x in ast.walk(n))
            elif isinstance(n, ast.Call) and call_name(n) in (
                    "len", "isinstance", "getattr", "hasattr", "type"):
                static_parents.update(id(x) for x in ast.walk(n))
            elif isinstance(n, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops):
                static_parents.update(id(x) for x in ast.walk(n))
        return [n for n in ast.walk(test)
                if isinstance(n, ast.Name) and n.id in traced
                and id(n) not in static_parents]


@register
class StaticArgnumsRule(Rule):
    name = "jax-static-argnums"
    description = ("static_argnums must be an int or tuple of ints "
                   "(lists/odd shapes recompile per call or fail to "
                   "hash)")

    def visit_file(self, ctx: FileCtx):
        if not on_hot_path(ctx.path):
            return
        for app, static_node, _fn in _jit_applications(ctx.tree):
            if static_node is None:
                continue
            if isinstance(static_node, ast.Constant):
                if not isinstance(static_node.value, int):
                    yield ctx.finding(
                        self.name, static_node,
                        f"static_argnums={static_node.value!r} is not an "
                        f"int or tuple of ints")
                continue
            if isinstance(static_node, ast.Tuple):
                bad = [el for el in static_node.elts
                       if isinstance(el, ast.Constant)
                       and not isinstance(el.value, int)]
                for el in bad:
                    yield ctx.finding(
                        self.name, el,
                        f"static_argnums entry {el.value!r} is not an "
                        f"int")
                continue
            yield ctx.finding(
                self.name, static_node,
                "static_argnums should be an int or a TUPLE of ints, "
                f"not a {type(static_node).__name__.lower().replace('ast.', '')} "
                "expression")

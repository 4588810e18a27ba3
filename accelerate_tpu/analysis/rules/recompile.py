"""recompile-hazard: python-scalar control flow / shapes inside jit without
``static_argnums``, and unbucketed batches fed to a captured step.

A jit argument used in an ``if``/``while`` test, in ``range()``, or as a
shape raises ConcretizationTypeError at trace time — or, when the caller
papers over it by passing python ints, silently recompiles the whole program
for every distinct value (the multi-minute XLA compile, per step).  The fix
is ``static_argnums``/``static_argnames`` (hashable, cache-keyed) or
``lax.cond``/``jnp.where`` for genuinely dynamic branches.

The capture-cache variant: ``CapturedStep.__call__`` keys its program cache
on ``(treedef, shapes, dtypes, sync_gradients, training)`` — a loop that
feeds *unpadded, varying-length* batches from a data loader into a
``compile_step``-captured callable compiles one program per distinct
sequence length.  The rule flags ``for batch in loader: step(batch)`` when
the loader shows no ``PaddingCollate`` / ``TPU_PAD_MULTIPLE`` / bucketing
evidence (a ``collate_fn=`` or a pad/bucket-named helper counts).

The serving variant (docs/serving.md): the captured serving/decode entries
(``serving/engine.py``'s ``run_prefill``/``run_decode_n``) pin one program
per bucketed geometry — an argument built straight from ``len(prompt)`` /
``.shape`` with no bucket/pad evidence in the call compiles one program
per distinct request length, the per-request analog of the unbucketed
loader loop.
"""

from __future__ import annotations

import ast
import re

from ..engine import Finding, Rule

# module-level constructors: leaf -> positional index of the shape argument
_SHAPE_CREATORS = {
    "zeros": 0,
    "ones": 0,
    "empty": 0,
    "full": 0,
    "eye": 0,
    "arange": 0,
    "linspace": 2,
    "broadcast_to": 1,
    "reshape": 1,
    "tile": 1,
}
# array methods: every argument is part of the shape
_SHAPE_METHODS = {"reshape", "broadcast_to", "tile"}
_JIT_LEAVES = {"jit", "pjit"}


def _jit_statics(call: ast.Call, module):
    """(static_argnums, static_argnames) literals from a jit(...) call."""
    nums: list[int] = []
    names: list[str] = []
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            nums.extend(
                e.value for e in elts if isinstance(e, ast.Constant) and isinstance(e.value, int)
            )
        elif kw.arg == "static_argnames":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            names.extend(
                e.value for e in elts if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return nums, names


def _jit_sites(module):
    """qualname -> (static_argnums, static_argnames) for every locally
    defined function wrapped by jit (decorator or call form)."""
    sites: dict[str, tuple[list[int], list[str]]] = {}
    cg = module.callgraph
    for info in cg.functions.values():
        for dec in getattr(info.node, "decorator_list", []):
            target = dec.func if isinstance(dec, ast.Call) else dec
            resolved = module.resolve(target) or ""
            leaf = resolved.rsplit(".", 1)[-1]
            if leaf in _JIT_LEAVES:
                statics = _jit_statics(dec, module) if isinstance(dec, ast.Call) else ([], [])
                sites[info.qualname] = statics
            elif leaf == "partial" and isinstance(dec, ast.Call):
                if any(
                    (module.resolve(a) or "").rsplit(".", 1)[-1] in _JIT_LEAVES
                    for a in dec.args
                ):
                    sites[info.qualname] = _jit_statics(dec, module)
    calls = [
        node
        for node in module.index.of_type(ast.Call)
        if (module.resolve(node.func) or "").rsplit(".", 1)[-1] in _JIT_LEAVES
        and node.args
        and isinstance(node.args[0], ast.Name)
    ]
    for node in module.index.as_walked(calls):
        for info in cg.by_leaf.get(node.args[0].id, []):
            sites.setdefault(info.qualname, _jit_statics(node, module))
    return sites


def _free_names(index, expr: ast.AST, hides) -> set[str]:
    """Names in ``expr`` outside every subtree whose root ``hides``."""
    hidden = [(index.pos[n], index.end[index.pos[n]]) for n in index.walk(expr) if hides(n)]
    return {
        n.id
        for n in index.walk(expr, ast.Name)
        if not any(a <= index.pos[n] < b for a, b in hidden)
    }


def _static_read(node: ast.AST) -> bool:
    """``x.shape`` / ``x.ndim`` / ``x.size`` and ``len(x)``: static at trace time."""
    return (isinstance(node, ast.Attribute) and node.attr in ("shape", "ndim", "size")) or (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Name)
        and node.func.id == "len"
    )


def _dynamic_shape_names(index, expr: ast.AST) -> set[str]:
    """Names a shape expression *dynamically* depends on: names that only
    appear under a static read don't make the shape dynamic."""
    return _free_names(index, expr, _static_read)


def _trace_safe_test(node: ast.AST) -> bool:
    """`x is None`, isinstance/hasattr/callable/getattr, len(), and
    `.shape`/`.ndim`/`.size` reads: static at trace time."""
    if isinstance(node, ast.Compare):
        return all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops)
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id in ("isinstance", "hasattr", "callable", "getattr", "len")
    return _static_read(node)


# names whose assignment marks a captured-step callable
_CAPTURE_LEAVES = {"compile_step", "CapturedStep"}
# AOT executable deserialization entry points (docs/aot_cache.md): loading a
# serialized executable bypasses trace+compile, so NOTHING re-validates that
# the program matches this process — the caller must check the entry's
# fingerprint/cache key (jax+jaxlib version, platform, device kind+count,
# mesh) or a stale entry from another topology dispatches a wrong program
_DESERIALIZE_LEAVES = {"deserialize_and_load"}
# evidence the caller checks the cache-key contract before loading: a
# fingerprint/cache-key/topology-named variable, attribute, or dict key
# anywhere in the enclosing scope (the aot_cache layer's own loaders name
# their guards exactly this way)
_FINGERPRINT_EVIDENCE_RE = re.compile(
    r"fingerprint|cache_key|cachekey|topolog|fp_digest", re.IGNORECASE
)
# captured serving/decode entry points (serving/engine.py): their ids/table
# arguments become program SHAPES, so request-derived lengths must pass
# through the bucketing helper (kv_blocks.bucket_length / generation.bucket_up)
_SERVING_ENTRY_LEAVES = {
    "run_prefill", "run_decode", "run_decode_n",
    "_prefill_jit", "_decode_jit", "_decode_n_jit",
}
# evidence the author already buckets shapes (PaddingCollate pads to
# TPU_PAD_MULTIPLE; any custom collate_fn is assumed to know its shapes)
_PAD_EVIDENCE_RE = re.compile(r"pad|bucket|PaddingCollate|TPU_PAD_MULTIPLE", re.IGNORECASE)
_LOADER_NAME_RE = re.compile(r"loader|batches", re.IGNORECASE)
# iteration adapters that pass their iterable's items through unchanged —
# `for i, batch in enumerate(loader)` is the same loader underneath
_ITER_WRAPPERS = {"enumerate", "zip", "tqdm", "islice", "cycle", "reversed"}


def _captured_names(module) -> set[str]:
    out = set()
    for node in module.index.of_type(ast.Assign):
        if isinstance(node.value, ast.Call):
            resolved = module.resolve(node.value.func) or ""
            if resolved.rsplit(".", 1)[-1] in _CAPTURE_LEAVES:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _has_raw_length_source(index, expr: ast.AST) -> bool:
    """Does the expression derive from a per-request length — ``len(...)``
    or a ``.shape``/``.size`` read?  Those are exactly the values that must
    go through the bucketing helper before becoming a serving-program shape."""
    for sub in index.walk(expr, ast.Call, ast.Attribute):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Name)
            and sub.func.id == "len"
        ):
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in ("shape", "size"):
            return True
    return False


def _subtree_has_pad_evidence(index, node: ast.AST) -> bool:
    for sub in index.walk(node, ast.Name, ast.Attribute, ast.keyword):
        if isinstance(sub, ast.Name) and _PAD_EVIDENCE_RE.search(sub.id):
            return True
        if isinstance(sub, ast.Attribute) and _PAD_EVIDENCE_RE.search(sub.attr):
            return True
        if isinstance(sub, ast.keyword) and sub.arg and (
            sub.arg == "collate_fn" or _PAD_EVIDENCE_RE.search(sub.arg)
        ):
            return True
    return False


def _scope_params(scope) -> set[str]:
    a = getattr(scope, "args", None)
    if a is None:
        return set()
    return {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}


def _assignment_in(index, scope, name: str):
    """First assignment to ``name`` in source order among the scope's own
    statements — own nodes stop at nested def/class bodies at any depth, so
    a function under a module-level ``if`` is never scanned as module code."""
    for node in index.own(scope, ast.Assign):
        if any(isinstance(t, ast.Name) and t.id == name for t in node.targets):
            return node.value
    return None


def _loader_expr(module, expr: ast.AST, scope, _depth: int = 0):
    """The loader-construction Call a loop iterates over, chasing assignments
    in the loop's own scope (a parameter or local binding never resolves to
    another function's same-named local; unbound names fall back to module
    level).  Depth-capped: `loader = loader`-style cycles terminate.  None
    when the iterable is not loader-shaped (ranges, fixed arrays, zips —
    those can't vary shapes per step)."""
    if _depth > 8:
        return None
    if isinstance(expr, ast.Name):
        if _PAD_EVIDENCE_RE.search(expr.id):
            return None  # `padded_loader` names its own mitigation
        assigned = _assignment_in(module.index, scope, expr.id)
        if (
            assigned is None
            and scope is not module.tree
            and expr.id not in _scope_params(scope)
        ):
            assigned = _assignment_in(module.index, module.tree, expr.id)
        if assigned is not None and not (
            isinstance(assigned, ast.Name) and assigned.id == expr.id
        ):
            return _loader_expr(module, assigned, scope, _depth + 1)
        return expr if _LOADER_NAME_RE.search(expr.id) else None
    if isinstance(expr, ast.Call):
        resolved = module.resolve(expr.func) or ""
        leaf = resolved.rsplit(".", 1)[-1]
        if _LOADER_NAME_RE.search(leaf) or leaf in ("prepare", "prepare_data_loader"):
            return expr
        if leaf in _ITER_WRAPPERS:
            for a in expr.args:
                found = _loader_expr(module, a, scope, _depth + 1)
                if found is not None:
                    return found
            return None
    if isinstance(expr, ast.Attribute) and _LOADER_NAME_RE.search(expr.attr):
        return expr
    return None


class RecompileHazard(Rule):
    id = "recompile-hazard"
    kind = "syntactic"
    description = (
        "jit argument used in python control flow / range() / shapes without "
        "static_argnums, an unhashable static default, or a captured step fed "
        "unbucketed loader batches"
    )
    fix_hint = (
        "mark the argument static (static_argnums/static_argnames) or "
        "bucket/pad the dynamic shape (TPU_PAD_MULTIPLE) so traces are reused"
    )

    def check(self, module, ctx):
        findings = []
        cg = module.callgraph
        for qual, (argnums, argnames) in _jit_sites(module).items():
            info = cg.functions[qual]
            node = info.node
            a = node.args
            params = [p.arg for p in a.posonlyargs + a.args]
            static = set(argnames)
            static.update(params[i] for i in argnums if 0 <= i < len(params))
            dynamic = {
                p
                for p in params + [p.arg for p in a.kwonlyargs]
                if p not in static and p not in ("self", "cls")
            }
            # unhashable default on a *static* param breaks the jit cache key
            defaults = dict(zip(params[len(params) - len(a.defaults):], a.defaults))
            for p in sorted(static):
                d = defaults.get(p)
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            d.lineno,
                            d.col_offset,
                            f"static argument '{p}' of jitted '{qual}' has an "
                            "unhashable default (list/dict/set) — jit's cache "
                            "key requires hashable statics",
                            symbol=qual,
                        )
                    )
            findings.extend(self._scan_body(module, info, dynamic))
        findings.extend(self._scan_capture_loops(module))
        findings.extend(self._scan_serving_calls(module))
        findings.extend(self._scan_aot_deserialize(module))
        return findings

    # -- AOT cache-key contract ------------------------------------------------
    def _scan_aot_deserialize(self, module):
        """A serialized executable deserialized without any fingerprint/
        cache-key check in scope: deserialize_and_load skips trace AND
        compile, so no layer below the caller re-validates that the stored
        program matches this process's topology/compiler — a stale entry
        (different device count, jax version, compression policy) would
        dispatch a wrong program instead of recompiling."""
        findings = []
        index = module.index

        def deserializes(node):
            return (module.resolve(node.func) or "").rsplit(".", 1)[-1] in _DESERIALIZE_LEAVES

        if not any(map(deserializes, index.of_type(ast.Call))):
            return findings
        scopes = [module.tree] + [info.node for info in module.callgraph.functions.values()]
        for scope in scopes:
            # own statements only: a nested function's deserialize call (and
            # its fingerprint guard) is judged in the nested scope's own row
            calls = [c for c in index.own(scope, ast.Call) if deserializes(c)]
            evidence = any(
                # meta["fingerprint"]-style dict keys count as Constants
                _FINGERPRINT_EVIDENCE_RE.search(
                    node.id if isinstance(node, ast.Name)
                    else node.attr if isinstance(node, ast.Attribute)
                    else node.value if isinstance(node.value, str)
                    else ""
                )
                for node in index.own(scope, ast.Name, ast.Attribute, ast.Constant)
            )
            if not calls or evidence:
                continue
            qual = getattr(scope, "name", "")
            for call in calls:
                findings.append(
                    Finding(
                        self.id,
                        module.rel_path,
                        call.lineno,
                        call.col_offset,
                        "serialized executable deserialized without a "
                        "fingerprint/cache-key check in scope — "
                        "deserialize_and_load skips trace AND compile, so a "
                        "stale entry (different device count/kind, jax or "
                        "jaxlib version, mesh, compression policy) dispatches "
                        "a wrong program; compare the entry's stored "
                        "fingerprint against the live topology first "
                        "(docs/aot_cache.md §invalidation)",
                        symbol=qual,
                    )
                )
        return findings

    # -- serving bucketing contract -------------------------------------------
    def _scan_serving_calls(self, module):
        """Raw request-length shapes flowing into a captured serving/decode
        entry: the serving programs pin ONE variant per bucketed geometry,
        so an argument built straight from ``len(prompt)`` / ``x.shape``
        without bucket/pad evidence compiles a fresh program per distinct
        request length — exactly the explosion the service exists to avoid."""
        findings = []
        index = module.index
        for node in index.of_type(ast.Call):
            resolved = module.resolve(node.func) or ""
            if resolved.rsplit(".", 1)[-1] not in _SERVING_ENTRY_LEAVES:
                continue
            args = list(node.args) + [kw.value for kw in node.keywords]
            if any(_subtree_has_pad_evidence(index, a) for a in args):
                continue
            if any(_has_raw_length_source(index, a) for a in args):
                findings.append(
                    Finding(
                        self.id,
                        module.rel_path,
                        node.lineno,
                        node.col_offset,
                        "raw request-length shape flows into captured serving "
                        f"entry '{resolved.rsplit('.', 1)[-1]}' without "
                        "bucketing — route lengths through "
                        "serving.bucket_length (or pad to a bucket) or every "
                        "distinct request length compiles a fresh program",
                    )
                )
        return findings

    # -- capture-cache hazard ------------------------------------------------
    def _scan_capture_loops(self, module):
        """``for batch in loader: step(batch)`` where ``step`` is a
        compile_step-captured callable and the loader shows no bucketing
        evidence: every distinct batch shape compiles a fresh program
        (CapturedStep keys on (treedef, shapes, dtypes, ...))."""
        captured = _captured_names(module)
        if not captured:
            return []
        findings = []
        scopes = [module.tree] + [
            info.node for info in module.callgraph.functions.values()
        ]
        for scope in scopes:
            findings.extend(self._scan_scope_loops(module, scope, captured))
        return findings

    def _scan_scope_loops(self, module, scope, captured):
        findings = []
        index = module.index
        for loop in index.own(scope, ast.For, ast.AsyncFor):
            loader = _loader_expr(module, loop.iter, scope)
            if loader is None or _subtree_has_pad_evidence(index, loader):
                continue
            targets = {n.id for n in index.walk(loop.target, ast.Name)}
            for node in index.walk(loop, ast.Call):
                if not (isinstance(node.func, ast.Name) and node.func.id in captured):
                    continue
                feeds_batch = any(
                    n.id in targets
                    for a in list(node.args) + [kw.value for kw in node.keywords]
                    for n in index.walk(a, ast.Name)
                )
                if feeds_batch:
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            node.lineno,
                            node.col_offset,
                            f"loader batches flow into captured step "
                            f"'{node.func.id}' without PaddingCollate/"
                            "TPU_PAD_MULTIPLE bucketing — CapturedStep's "
                            "cache keys on (treedef, shapes, dtypes, "
                            "sync_gradients, training), so every distinct "
                            "batch shape compiles a fresh program",
                        )
                    )
        return findings

    def _scan_body(self, module, info, dynamic):
        findings = []
        qual = info.qualname

        def hit(node, msg):
            findings.append(
                Finding(self.id, module.rel_path, node.lineno, node.col_offset, msg, symbol=qual)
            )

        index = module.index
        for node in index.walk(info.node, ast.If, ast.While, ast.Call):
            if isinstance(node, (ast.If, ast.While)):
                used = _free_names(index, node.test, _trace_safe_test) & dynamic
                for p in sorted(used):
                    hit(
                        node,
                        f"python control flow on traced argument '{p}' of jitted "
                        f"'{qual}' — mark it static_argnums/static_argnames or "
                        "use lax.cond/jnp.where",
                    )
            elif isinstance(node, ast.Call):
                fn = node.func
                resolved = module.resolve(fn) or ""
                leaf = resolved.rsplit(".", 1)[-1]
                if isinstance(fn, ast.Name) and fn.id == "range":
                    used = {
                        n.id for a_ in node.args for n in index.walk(a_, ast.Name)
                    } & dynamic
                    for p in sorted(used):
                        hit(
                            node,
                            f"range() over traced argument '{p}' of jitted '{qual}' "
                            "— mark it static or use lax.fori_loop",
                        )
                elif leaf in _SHAPE_CREATORS and resolved.startswith(("jax.numpy", "numpy")):
                    pos = _SHAPE_CREATORS[leaf]
                    shape_arg = node.args[pos] if len(node.args) > pos else None
                    for kw in node.keywords:
                        if kw.arg == "shape":
                            shape_arg = kw.value
                    if shape_arg is not None:
                        used = _dynamic_shape_names(index, shape_arg) & dynamic
                        for p in sorted(used):
                            hit(
                                node,
                                f"shape of {leaf}() derives from traced argument "
                                f"'{p}' of jitted '{qual}' — shapes must be static "
                                "under jit (static_argnums, or pad to a bucket)",
                            )
                elif (
                    isinstance(fn, ast.Attribute)
                    and fn.attr in _SHAPE_METHODS
                    and not resolved.startswith(("jax.", "numpy"))
                ):
                    used = set().union(
                        set(), *(_dynamic_shape_names(index, a_) for a_ in node.args)
                    ) & dynamic
                    for p in sorted(used):
                        hit(
                            node,
                            f".{fn.attr}() shape derives from traced argument '{p}' "
                            f"of jitted '{qual}' — shapes must be static under jit",
                        )
        return findings

"""Per-module call graph + traced-region reachability.

A function is a *trace root* when it is handed to a tracing transform —
decorated with ``jax.jit`` / ``partial(jax.jit, ...)``, or passed by name to
``jax.jit`` / ``shard_map`` / ``shard_map_compat`` / ``pl.pallas_call`` /
``lax.scan``-family / ``accelerator.compile_step``.  Everything reachable
from a root through same-module calls (including functions passed as
callbacks and ``self.method()`` dispatch) executes under trace, so the
trace-safety rules (host-sync, blocking) only fire inside that region.

This module is the *per-file* half of the analysis: it collects functions,
call edges (bare names, ``self.method``, and dotted ``alias.fn`` forms) and
local roots.  ``program.py`` stitches the per-module graphs into a
whole-program one — resolving ``from .x import f`` / ``import pkg.mod as m``
edges and ``__init__.py`` re-exports — and injects the extra cross-module
reachability back into each module's ``reached`` map before rules run.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Iterator, Optional

from .engine import _SCOPES

# Leaves that are tracing transforms regardless of prefix (project- or
# jax-specific spellings that never collide with stdlib/user names).
_WRAPPER_LEAVES = {
    "jit",
    "pjit",
    "pmap",
    "shard_map",
    "shard_map_compat",
    "pallas_call",
    "compile_step",
    "CapturedStep",
    "remat",
    "xmap",
}
# Generic leaves that only count when the dotted path shows they come from
# jax (``lax.scan`` yes, ``self.scan`` no).
_JAX_ONLY_LEAVES = {
    "scan",
    "fori_loop",
    "while_loop",
    "cond",
    "switch",
    "associative_scan",
    "map",
    "vmap",
    "grad",
    "value_and_grad",
    "vjp",
    "jvp",
    "linearize",
    "checkpoint",
    "custom_vjp",
    "custom_jvp",
    "eval_shape",
    "make_jaxpr",
}


def is_trace_wrapper(resolved: Optional[str]) -> bool:
    if not resolved:
        return False
    parts = resolved.split(".")
    leaf = parts[-1]
    if leaf in _WRAPPER_LEAVES:
        return True
    if leaf in _JAX_ONLY_LEAVES:
        return "jax" in parts or parts[0] in ("lax", "pl", "pallas")
    return False


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.f`` for an Attribute chain bottoming at a Name, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@dataclasses.dataclass
class FunctionInfo:
    name: str
    qualname: str
    node: ast.AST
    edges: set[str] = dataclasses.field(default_factory=set)
    # borg-singleton initializer (`self.__dict__ = cls._shared_state`): its
    # body runs once per process, so constructing the class under trace does
    # NOT execute it — reachability must not propagate through it
    barrier: bool = False


def factory_returned_classes(module) -> dict[str, str]:
    """``{factory function name: constructed class name}`` for every
    MODULE-LEVEL function whose returns are ALL ``SomeClass(...)`` calls of
    the SAME constructor — the receiver-type source behind factory-return
    dispatch inference (``obj = make_runner(); obj.work(x)`` →
    ``Runner.work``).

    Deliberately strict, mirroring the join-over-branches rule for direct
    constructor rebinds: one ``return`` of anything else (a bare value, a
    different constructor, ``self``/``cls``/parameter-rooted calls), or no
    return at all, leaves the function out — and two same-named functions
    that disagree on the class knock the name out entirely (the caller
    resolves factories by bare name, and a wrong guess would cross-wire
    reachability).  Only top-level defs qualify: a METHOD's bare name is
    never callable as ``name()``, and a nested def's name is only live
    inside its enclosing function — mapping either through a module-global
    table would wire edges for unrelated same-named callables (e.g. an
    injected callback parameter).  Async defs are excluded too: a bare
    call of an async factory binds a COROUTINE, not the constructed class
    (and the awaited form is an ``ast.Await``, which never consults the
    map anyway).  Decorated defs are excluded: the wrapper decides what a
    call returns (a future, a memo proxy), not the body's ``return``.
    And a name REBOUND at module level — a later same-named def that does
    not itself qualify with the same class, or any plain assignment — is
    knocked out entirely: the live binding is whatever ran last, and a
    stale mapping would be wrong, not conservative.  Same-module
    factory→factory delegation CHAINS resolve (v12): a delegating factory
    records the inner factory's name, and a cycle-guarded post-pass chases
    the map until it grounds (``make_a`` → ``make_b`` → ``Runner``).  A
    chain whose last link is not in the map (an imported factory, a
    knocked-out name) keeps that link as its ctor — program.py chases the
    cross-module half — and a delegation cycle drops its members entirely
    (no ground class exists)."""
    factories: dict[str, str] = {}
    knocked_out: set[str] = set()
    index = module.index
    for node in module.tree.body:
        name = None
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            name = node.name
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            # module-level rebind of the name shadows any earlier def
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                if isinstance(t, ast.Name):
                    knocked_out.add(t.id)
            continue
        if name is None:
            continue
        qualifies = False
        ctor = None
        if isinstance(node, ast.FunctionDef) and not node.decorator_list:
            params = {a.arg for a in index.walk(node.args, ast.arg)}
            ctors: set[str] = set()
            for ret in index.own(node, ast.Return):
                c = None
                if isinstance(ret.value, ast.Call):
                    fn = ret.value.func
                    c = fn.id if isinstance(fn, ast.Name) else dotted_name(fn)
                if (
                    c is None
                    or c.split(".", 1)[0] in ("self", "cls")
                    or c.split(".", 1)[0] in params
                ):
                    ctors.clear()
                    break
                ctors.add(c)
            if len(ctors) == 1:
                qualifies = True
                ctor = ctors.pop()
        if not qualifies:
            # a non-factory def AFTER a qualifying one is the live binding
            # — the stale mapping must go.  (A non-factory def BEFORE a
            # qualifying one is simply shadowed by it: keep the later.)
            if name in factories:
                knocked_out.add(name)
            continue
        if factories.setdefault(name, ctor) != ctor:
            knocked_out.add(name)
    for name in knocked_out:
        factories.pop(name, None)
    # chase same-module delegation chains to their ground (cycle-guarded)
    resolved: dict[str, str] = {}
    for name in factories:
        seen: set[str] = set()
        tgt = name
        while tgt in factories and tgt not in seen:
            seen.add(tgt)
            tgt = factories[tgt]
        if tgt in seen:
            continue  # delegation cycle: no ground class, drop the chain
        resolved[name] = tgt
    return resolved


def _is_singleton_init(index, fn_node: ast.AST) -> bool:
    return any(
        isinstance(t, ast.Attribute)
        and t.attr == "__dict__"
        and isinstance(t.value, ast.Name)
        and t.value.id == "self"
        for sub in index.own(fn_node, ast.Assign)
        for t in sub.targets
    )


class CallGraph:
    def __init__(self, module):
        self.module = module
        index = module.index
        # same-module factory functions (factory_returned_classes): a
        # receiver bound from `make_runner()` dispatches as the class every
        # return of make_runner constructs.  Exported for the program graph:
        # other modules importing one of these factories resolve their
        # receivers through it (v11)
        self.factories: dict[str, str] = factory_returned_classes(module)
        # qualnames of actual ClassDefs: instance-dispatch edges resolve
        # only through these — a factory FUNCTION with a nested def also
        # owns `outer.inner` qualnames, and treating it as a class would
        # wire phantom method edges into the nested function
        self.classes: set[str] = set()
        self.functions: dict[str, FunctionInfo] = {}
        enclosing: list[tuple[int, str]] = []  # (end of scope, name)
        for node in index.of_type(*_SCOPES):
            i = index.pos[node]
            while enclosing and enclosing[-1][0] <= i:
                enclosing.pop()
            qual = ".".join([name for _, name in enclosing] + [node.name])
            if isinstance(node, ast.ClassDef):
                self.classes.add(qual)
            else:
                self.functions[qual] = self._function(node, qual)
            enclosing.append((index.end[i], node.name))
        self.by_leaf: dict[str, list[FunctionInfo]] = {}
        for f in self.functions.values():
            self.by_leaf.setdefault(f.name, []).append(f)
        # calls of a tracing transform, in ast.walk order (the first root
        # reason a function gets is the one it keeps)
        self.wrapper_calls = index.as_walked(
            [c for c in index.of_type(ast.Call) if is_trace_wrapper(module.resolve(c.func))]
        )
        # reached: qualname -> human-readable reason ("root ..." / "via ...")
        self.reached: dict[str, str] = {}
        self._find_roots()
        self._propagate()

    def _function(self, node, qual: str) -> FunctionInfo:
        index = self.module.index
        info = FunctionInfo(node.name, qual, node, barrier=_is_singleton_init(index, node))
        # names bound as data in this scope (params, assignments, loop vars):
        # a data binding passed as an argument is a value, not a reference to
        # a same-named module function — without this, a parameter named like
        # a method creates phantom edges
        params = {a.arg for a in index.walk(node.args, ast.arg)}
        store_counts: dict[str, int] = {}
        for sub in index.own(node, ast.Name):
            if isinstance(sub.ctx, (ast.Store, ast.Del)):
                store_counts[sub.id] = store_counts.get(sub.id, 0) + 1
        local_data = params | set(store_counts)
        # cheap type inference over locals bound to constructor calls:
        # `obj = Ctor(...)` pins obj's type to Ctor for the whole function —
        # then `obj.method(x)` dispatches to ``Ctor.method`` (resolved by
        # qualname same-module, through the class's import in program.py).
        # Join-over-branches: a receiver rebound across branches counts too,
        # as long as EVERY binding of the name is a call of the SAME
        # constructor (`obj = Cls() if fast else Cls(opts)`) — the join of
        # identical types is that type.  Any other binding shape (a
        # parameter, a different ctor, a non-call assignment, a loop/del
        # rebind) leaves the receiver uninferred: its type is not knowable,
        # and a wrong guess would cross-wire reachability.
        ctor_assigns: dict[str, list[str]] = {}
        for sub in index.own(node, ast.Assign):
            if (
                len(sub.targets) == 1
                and isinstance(sub.targets[0], ast.Name)
                and isinstance(sub.value, ast.Call)
            ):
                target = sub.targets[0].id
                fn = sub.value.func
                ctor = fn.id if isinstance(fn, ast.Name) else dotted_name(fn)
                if ctor and ctor.split(".", 1)[0] not in ("self", "cls"):
                    # factory-return inference (v10): a bare-name call of a
                    # same-module factory binds the CLASS the factory
                    # constructs, so it joins over branches with direct
                    # constructor binds (`r = Runner() if fast else
                    # make_runner()` is still Runner).  A locally-bound
                    # name (parameter, assignment) is DATA shadowing the
                    # module function — any callable could be injected, so
                    # the factory map must not apply (same guard the plain
                    # call edges use)
                    if (
                        isinstance(fn, ast.Name)
                        and ctor in self.factories
                        and ctor not in local_data
                    ):
                        ctor = self.factories[ctor]
                    elif isinstance(fn, ast.Name) and ctor in local_data:
                        # v11: bare-name ctor shadowed by local data — with
                        # factory maps now resolving through IMPORTS
                        # (program.py), an unresolved name edge could later
                        # mis-bind to an imported factory/class the local
                        # binding actually shadows; record nothing so the
                        # receiver stays uninferred
                        ctor = None
                    if ctor is not None:
                        ctor_assigns.setdefault(target, []).append(ctor)
        ctor_of: dict[str, str] = {}
        for target, ctors in ctor_assigns.items():
            if target in params:
                continue
            # every Store/Del of the name must be one of these ctor calls
            # (a non-call rebind wouldn't appear in ctor_assigns and makes
            # the counts disagree), and they must all name the same class
            if store_counts.get(target) == len(ctors) and len(set(ctors)) == 1:
                ctor_of[target] = ctors[0]
        for sub in index.own(node, ast.Call):
            # direct calls: f(...), self.f(...) / cls.f(...), and dotted
            # alias.f(...) — the dotted form is what program.py resolves
            # across module boundaries (``utils.sync(x)``)
            fn = sub.func
            if isinstance(fn, ast.Name):
                info.edges.add(fn.id)
            elif isinstance(fn, ast.Attribute):
                d = dotted_name(fn)
                if d is None:
                    pass
                elif isinstance(fn.value, ast.Name) and fn.value.id in ("self", "cls"):
                    info.edges.add(fn.attr)
                elif isinstance(fn.value, ast.Name) and fn.value.id in ctor_of:
                    # inferred instance dispatch: obj = Ctor(); obj.m(x)
                    info.edges.add(f"{ctor_of[fn.value.id]}.{fn.attr}")
                elif d.split(".", 1)[0] in ("self", "cls"):
                    # deeper chains (self.state.update()): the receiver's
                    # type is unknown — a bare-leaf edge would collide
                    # with any same-module function named `update`
                    pass
                elif d.split(".", 1)[0] not in local_data:
                    info.edges.add(d)
            # callback pattern: names passed as arguments may be called
            # by the callee (ring hops, pipeline schedules do this).
            # Nested defs are not Store bindings, so they stay eligible.
            for arg in list(sub.args) + [kw.value for kw in sub.keywords]:
                if isinstance(arg, ast.Name) and arg.id not in local_data:
                    info.edges.add(arg.id)
        return info

    # -- roots --------------------------------------------------------------
    def _mark(self, info: FunctionInfo, reason: str) -> None:
        self.reached.setdefault(info.qualname, reason)

    def _find_roots(self) -> None:
        mod = self.module
        for info in self.functions.values():
            for dec in getattr(info.node, "decorator_list", []):
                target = dec.func if isinstance(dec, ast.Call) else dec
                resolved = mod.resolve(target)
                if is_trace_wrapper(resolved):
                    self._mark(info, f"decorated with {resolved}")
                elif (
                    isinstance(dec, ast.Call)
                    and resolved
                    and resolved.rsplit(".", 1)[-1] == "partial"
                ):
                    for a in dec.args:
                        wr = mod.resolve(a)
                        if is_trace_wrapper(wr):
                            self._mark(info, f"decorated with partial({wr}, ...)")
        # call-form: jax.jit(f, ...), shard_map_compat(f, ...), lax.scan(f, ...)
        for node in self.wrapper_calls:
            resolved = mod.resolve(node.func)
            # walk the whole argument expressions, not just bare Names: the
            # `shard_map_compat(partial(local_fn, ...), ...)` idiom buries the
            # traced function one call deep
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                for sub in mod.index.walk(arg, ast.Name):
                    for info in self.by_leaf.get(sub.id, []):
                        self._mark(info, f"passed to {resolved}")

    # -- reachability -------------------------------------------------------
    def _propagate(self) -> None:
        frontier = list(self.reached)
        while frontier:
            qual = frontier.pop()
            info = self.functions[qual]
            for name in info.edges:
                callees = self.by_leaf.get(name, [])
                if not callees and "." in name:
                    # instance-dispatch edge (``Cls.method``): same-module
                    # resolution is an exact qualname lookup, restricted to
                    # REAL classes — a factory function's nested defs share
                    # the qualname shape but are not dispatch targets;
                    # imported-class forms resolve in program.py
                    target = self.functions.get(name)
                    if target is not None and name.rsplit(".", 1)[0] in self.classes:
                        callees = [target]
                for callee in callees:
                    if callee.barrier:
                        continue  # singleton init: runs once, never in-trace
                    if callee.qualname not in self.reached:
                        root = self.reached[qual].split(" via ")[0]
                        self.reached[callee.qualname] = f"{root} via {qual}"
                        frontier.append(callee.qualname)

    def traced_functions(self) -> Iterator[tuple[FunctionInfo, str]]:
        for qual, reason in sorted(self.reached.items()):
            yield self.functions[qual], reason


# ---------------------------------------------------------------------------
# donation helpers (shared by rules/donation.py, rules/transitive_donation.py
# and program.py — living here keeps the import graph acyclic)
# ---------------------------------------------------------------------------

_JIT_LEAVES = {"jit", "pjit"}


def donated_positions(call: ast.Call) -> Optional[list[int]]:
    """Literal ``donate_argnums`` positions of a jit(...) call, or None."""
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            out = [
                e.value
                for e in elts
                if isinstance(e, ast.Constant) and isinstance(e.value, int)
            ]
            return out or None
    return None


def donating_callables(module) -> dict[str, list[int]]:
    """name -> donated positions, for `g = jax.jit(f, donate_argnums=...)`
    assignments and `@partial(jax.jit, donate_argnums=...)` decorated defs
    (computed once per module: the summary and two rules read it)."""
    if getattr(module, "_donors", None) is not None:
        return module._donors
    index = module.index
    found: dict[ast.AST, tuple[list[str], list[int]]] = {}
    for node in index.of_type(ast.Assign):
        if isinstance(node.value, ast.Call):
            resolved = module.resolve(node.value.func) or ""
            pos = donated_positions(node.value)
            if resolved.rsplit(".", 1)[-1] in _JIT_LEAVES and pos:
                found[node] = ([t.id for t in node.targets if isinstance(t, ast.Name)], pos)
    for node in index.of_type(ast.FunctionDef, ast.AsyncFunctionDef):
        for dec in node.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            resolved = module.resolve(dec.func) or ""
            leaf = resolved.rsplit(".", 1)[-1]
            is_jit_factory = leaf in _JIT_LEAVES
            is_partial_jit = leaf == "partial" and any(
                (module.resolve(a) or "").rsplit(".", 1)[-1] in _JIT_LEAVES
                for a in dec.args
            )
            pos = donated_positions(dec)
            if (is_jit_factory or is_partial_jit) and pos:
                found[node] = ([node.name], pos)
    # a later binding of a name wins, in the order ast.walk meets them
    out: dict[str, list[int]] = {}
    for node in index.as_walked(list(found)):
        names, pos = found[node]
        out.update((name, pos) for name in names)
    module._donors = out
    return out

"""Whole-program half of the graftlint call graph.

``callgraph.py`` sees one module at a time; this module stitches those
per-file graphs into a package-wide one:

* **Module naming** — each analyzed file gets its dotted module name by
  walking the ``__init__.py`` chain on disk, so ``pkg/ops/matmul.py`` is
  ``pkg.ops.matmul`` and relative imports can be resolved against it.
* **Import resolution** — ``from .x import f``, ``from ..utils import g as
  h``, ``import pkg.mod as m`` and re-exports through ``__init__.py``
  (``pkg/__init__.py: from .impl import f`` makes ``from pkg import f``
  land on ``pkg.impl.f``) all become call-graph edges.
* **Cross-module reachability** — trace roots propagate through those
  edges, so a jitted body in ``ops/`` calling a helper in ``utils/`` marks
  that helper traced and every reachability rule (host-sync-in-trace,
  dtype-widen, donation, blocking) sees it.
* **Derived whole-program facts** — per module, the visible donating
  callables (`donate_argnums`), the helpers that *store* a parameter beyond
  the call (transitive-donation), and the functions that transitively hit
  ``block_until_ready`` (blocking-in-hot-loop).

Everything here works off :class:`ModuleSummary` — a small, JSON-able
digest of one module — so the on-disk cache (``cache.py``) can replay a
summary by content hash without re-parsing the file.

With ``cross=False`` (the ``--no-cross-module`` escape hatch) import
resolution is disabled AND the transitive maps (escapers, blockers) stay
empty, so behavior matches the historical per-module linter: direct calls
only, local reachability only.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Optional

from .callgraph import donating_callables, dotted_name
from .engine import _SCOPES, GUARD_NAME_RE, is_guard_expr
from .taint import _call_leaf

# methods whose argument escapes into the receiver (stored beyond the call)
_STORE_METHODS = {
    "append",
    "add",
    "extend",
    "insert",
    "appendleft",
    "setdefault",
    "update",
    "put",
    "register",
}
_BLOCKING_LEAVES = {"block_until_ready", "effects_barrier"}

_MAX_REEXPORT_DEPTH = 8


# ---------------------------------------------------------------------------
# per-module summary (the cacheable digest)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FunctionSummary:
    name: str
    qualname: str
    edges: list  # bare and dotted call-edge names
    escapes: list  # positional parameter indices stored beyond the call
    blocks: bool  # unguarded block_until_ready/effects_barrier in own body
    guard: bool  # function name marks it as profiling/bench plumbing
    barrier: bool = False  # borg-singleton init: reachability stops here
    # rank-divergence digest (taint.py, v12): the return value is divergent
    # directly, or becomes divergent when one of the named callees is
    div_direct: bool = False
    div_via: list = dataclasses.field(default_factory=list)
    # collective-sink tokens issued directly in the body (taint.py)
    collectives: list = dataclasses.field(default_factory=list)

    def to_list(self) -> list:
        return [
            self.name, self.qualname, self.edges, self.escapes, self.blocks,
            self.guard, self.barrier, self.div_direct, self.div_via,
            self.collectives,
        ]

    @classmethod
    def from_list(cls, row: list) -> "FunctionSummary":
        return cls(*row)


@dataclasses.dataclass
class ModuleSummary:
    """Everything the program graph needs to know about one module, without
    its AST.  Serializable: this is what ``.graftlint_cache`` stores."""

    functions: list = dataclasses.field(default_factory=list)
    reached: dict = dataclasses.field(default_factory=dict)  # local roots + local closure
    wrapper_passed: list = dataclasses.field(default_factory=list)  # [wrapper, name]
    donors: dict = dataclasses.field(default_factory=dict)  # name -> positions
    axes: list = dataclasses.field(default_factory=list)  # declared mesh axes
    imports: list = dataclasses.field(default_factory=list)  # raw import records
    classes: list = dataclasses.field(default_factory=list)  # ClassDef qualnames
    # {factory fn name: constructed class name} (callgraph.py v10 map) — the
    # program graph resolves IMPORTED factories' receivers through it (v11)
    factories: dict = dataclasses.field(default_factory=dict)
    error: Optional[str] = None  # set when the file failed to parse
    error_line: int = 0

    def to_dict(self) -> dict:
        return {
            "functions": [f.to_list() for f in self.functions],
            "reached": self.reached,
            "wrapper_passed": self.wrapper_passed,
            "donors": self.donors,
            "axes": list(self.axes),
            "imports": self.imports,
            "classes": list(self.classes),
            "factories": dict(self.factories),
            "error": self.error,
            "error_line": self.error_line,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "ModuleSummary":
        return cls(
            functions=[FunctionSummary.from_list(row) for row in d.get("functions", [])],
            reached=dict(d.get("reached", {})),
            wrapper_passed=[list(w) for w in d.get("wrapper_passed", [])],
            donors={k: list(v) for k, v in d.get("donors", {}).items()},
            axes=list(d.get("axes", [])),
            imports=d.get("imports", []),
            classes=list(d.get("classes", [])),
            factories=dict(d.get("factories", {})),
            error=d.get("error"),
            error_line=d.get("error_line", 0),
        )


def escaping_params(index, fn_node: ast.AST) -> list[int]:
    """Positional-parameter indices of ``fn_node`` that are *stored* beyond
    the call: appended/added to a container, assigned to an attribute or
    subscript, or bound to a ``global`` name.  A caller that passes a buffer
    at such a position has leaked an alias that outlives the call — which is
    exactly what donation must not coexist with."""
    args = fn_node.args
    params = [a.arg for a in args.posonlyargs + args.args]
    # drop a leading self/cls so indices line up with the CALLER's positional
    # arguments (constructors resolve to Cls.__init__, whose arg 0 is self —
    # the caller's arg 0 is the init's arg 1)
    if params and params[0] in ("self", "cls"):
        params = params[1:]
    pset = set(params)
    if not pset:
        return []
    global_names: set[str] = set()
    escaped: set[str] = set()
    for node in index.own(fn_node, ast.Global, ast.Nonlocal):
        global_names.update(node.names)
    for node in index.own(fn_node, ast.Call, ast.Assign, ast.AnnAssign, ast.AugAssign):
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr in _STORE_METHODS:
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if isinstance(arg, ast.Name) and arg.id in pset:
                        escaped.add(arg.id)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            value = node.value
            if value is None:
                continue
            # only storing the buffer ITSELF leaks an alias: a bare param
            # name, possibly inside a tuple/list/set/dict literal — storing
            # a derived value (x.shape[0], float(x)) does not.  `acc += x`
            # stores old+x (a NEW array), so a bare-Name AugAssign is
            # derived too; `log += [x]` is list-extend and keeps the alias
            if isinstance(value, ast.Name):
                candidates = [] if isinstance(node, ast.AugAssign) else [value]
            elif isinstance(value, (ast.Tuple, ast.List, ast.Set)):
                candidates = value.elts
            elif isinstance(value, ast.Dict):
                candidates = value.values
            else:
                candidates = []
            value_names = {
                n.id for n in candidates if isinstance(n, ast.Name) and n.id in pset
            }
            if not value_names:
                continue
            for t in targets:
                if isinstance(t, (ast.Attribute, ast.Subscript)):
                    escaped |= value_names
                elif isinstance(t, ast.Name) and t.id in global_names:
                    escaped |= value_names
                elif isinstance(t, (ast.Tuple, ast.List)):
                    stores = [
                        isinstance(e, (ast.Attribute, ast.Subscript))
                        or (isinstance(e, ast.Name) and e.id in global_names)
                        for e in t.elts
                    ]
                    if not any(stores):
                        continue
                    if isinstance(value, (ast.Tuple, ast.List)) and len(
                        value.elts
                    ) == len(t.elts):
                        # pairwise unpack: only values landing in a storing
                        # slot escape (`local, STATE[k] = buf, cfg` stores
                        # cfg, not buf)
                        for stored, v in zip(stores, value.elts):
                            if stored and isinstance(v, ast.Name) and v.id in pset:
                                escaped.add(v.id)
                    else:
                        escaped |= value_names
    return sorted(params.index(p) for p in escaped if p in params)


def _has_unguarded_block(index, fn_node: ast.AST) -> bool:
    """True when the function body reaches block_until_ready/effects_barrier
    outside any profiling-guard ``if`` body (at any nesting depth) — i.e.
    calling this function blocks unconditionally.  Nested defs are their own
    functions, not this one's behavior."""
    body = fn_node.body
    lo, hi = index.pos[body[0]], index.end[index.pos[body[-1]]]
    nested = [(index.pos[s], index.end[index.pos[s]]) for s in index.own(fn_node, *_SCOPES)]
    blocking = [
        i
        for i in map(index.pos.__getitem__, index.own(fn_node, ast.Call))
        if lo <= i < hi
        and not any(a <= i < b for a, b in nested)
        and _call_leaf(index.order[i].func) in _BLOCKING_LEAVES
    ]
    if not blocking:
        return False
    guarded = [
        (index.pos[s.body[0]], index.end[index.pos[s.body[-1]]])
        for s in index.own(fn_node, ast.If)
        if is_guard_expr(index, s.test)
    ]
    return any(not any(a <= i < b for a, b in guarded) for i in blocking)


def extract_summary(module) -> ModuleSummary:
    """Digest one parsed :class:`ModuleInfo` into its cacheable summary."""
    from .engine import collect_axes

    from .taint import collective_leaves, return_flow

    cg = module.callgraph
    index = module.index
    functions = []
    for info in cg.functions.values():
        self_prefix = (
            info.qualname.rsplit(".", 1)[0] if "." in info.qualname else None
        )
        div_direct, div_via = return_flow(module, info.node, self_prefix)
        functions.append(
            FunctionSummary(
                name=info.name,
                qualname=info.qualname,
                edges=sorted(info.edges),
                escapes=escaping_params(index, info.node),
                blocks=_has_unguarded_block(index, info.node),
                guard=bool(GUARD_NAME_RE.search(info.name)),
                barrier=info.barrier,
                div_direct=div_direct,
                div_via=div_via,
                collectives=collective_leaves(module, info.node),
            )
        )
    # names (bare or dotted) appearing inside trace-wrapper call arguments:
    # the per-module graph already rooted same-module matches; the program
    # graph resolves the rest through imports (`jax.jit(ops.step)`,
    # `shard_map_compat(partial(do_step, cfg), ...)` with do_step imported)
    wrapper_passed: list[list] = []
    for node in cg.wrapper_calls:
        resolved = module.resolve(node.func)
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in index.walk(arg, ast.Name, ast.Attribute):
                if isinstance(sub, ast.Name):
                    wrapper_passed.append([resolved, sub.id])
                elif isinstance(sub, ast.Attribute):
                    d = dotted_name(sub)
                    if d and "." in d and d.split(".", 1)[0] not in ("self", "cls"):
                        wrapper_passed.append([resolved, d])
    return ModuleSummary(
        functions=functions,
        reached=dict(cg.reached),
        wrapper_passed=wrapper_passed,
        donors=donating_callables(module),
        axes=collect_axes(module),
        imports=module.import_records,
        classes=sorted(cg.classes),
        factories=dict(getattr(cg, "factories", {})),
    )


# ---------------------------------------------------------------------------
# module naming
# ---------------------------------------------------------------------------

def module_name_for(path: str) -> str:
    """Dotted module name from the on-disk package layout: walk parent
    directories while they contain ``__init__.py``.  A file outside any
    package is just its stem."""
    path = os.path.abspath(path)
    stem = os.path.splitext(os.path.basename(path))[0]
    parts = [] if stem == "__init__" else [stem]
    d = os.path.dirname(path)
    while os.path.isfile(os.path.join(d, "__init__.py")):
        parts.append(os.path.basename(d))
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return ".".join(reversed(parts)) if parts else stem


# ---------------------------------------------------------------------------
# the whole-program graph
# ---------------------------------------------------------------------------

class ProgramGraph:
    """Cross-module import + call graph over the analyzed file set.

    Consumes the per-file records from ``engine.run_analysis`` (anything
    with ``.path`` / ``.rel_path`` / ``.summary``).  Produces, keyed by
    rel_path: ``cross_reached`` (extra traced functions beyond the module's
    own roots), ``donor_aliases``, ``escape_aliases`` and
    ``blocking_aliases`` (visible-name maps merged over local definitions
    and imports).
    """

    def __init__(self, records, cross: bool = True):
        self.cross = cross
        self.records = [r for r in records if r.summary.error is None]
        self.names = [module_name_for(r.path) for r in self.records]
        self.is_pkg = [
            os.path.basename(r.path) == "__init__.py" for r in self.records
        ]
        self.by_name: dict[str, int] = {}
        dupes: set[str] = set()
        for i, n in enumerate(self.names):
            if n in self.by_name:
                dupes.add(n)
            else:
                self.by_name[n] = i
        for n in dupes:
            # two analyzed files claim the same dotted name (same-stem
            # scripts outside any package, src/ + build/ copies): resolving
            # either would cross-wire facts to an arbitrary file — treat
            # the name as unresolvable instead
            del self.by_name[n]
        self.fn_by_qual = [
            {f.qualname: f for f in r.summary.functions} for r in self.records
        ]
        self.class_sets = [set(r.summary.classes) for r in self.records]
        self.fn_by_leaf: list[dict[str, list[FunctionSummary]]] = []
        for r in self.records:
            leafed: dict[str, list[FunctionSummary]] = {}
            for f in r.summary.functions:
                leafed.setdefault(f.name, []).append(f)
            self.fn_by_leaf.append(leafed)
        # per-module import bindings (empty maps when cross is off)
        self.mod_aliases: list[dict[str, str]] = []
        self.sym_aliases: list[dict[str, tuple[str, str]]] = []
        for i in range(len(self.records)):
            ma, sa = self._import_bindings(i) if cross else ({}, {})
            self.mod_aliases.append(ma)
            self.sym_aliases.append(sa)

        self._propagate()
        self._collect_aliases_maps()

    # -- imports ------------------------------------------------------------
    def _import_bindings(self, i: int):
        """(module aliases, symbol aliases) bound by module *i*'s imports."""
        mod_alias: dict[str, str] = {}
        sym_alias: dict[str, tuple[str, str]] = {}
        mn = self.names[i]
        pkg = mn if self.is_pkg[i] else (mn.rsplit(".", 1)[0] if "." in mn else "")
        for rec in self.records[i].summary.imports:
            if rec["kind"] == "import":
                for name, asname in rec["names"]:
                    if asname:
                        if name in self.by_name:
                            mod_alias[asname] = name
                    else:
                        # `import a.b.c` binds `a`; dotted call edges carry
                        # the full path, resolved in _resolve_dotted
                        parts = name.split(".")
                        mod_alias.setdefault(parts[0], parts[0])
                        # every analyzed dotted prefix is callable through
                        # the binding too (`a.b.fn(x)`) — register it so the
                        # donor/escape/blocking fact maps get full-path keys
                        for k in range(2, len(parts) + 1):
                            prefix = ".".join(parts[:k])
                            if prefix in self.by_name:
                                mod_alias.setdefault(prefix, prefix)
                continue
            base = rec["module"]
            level = rec.get("level", 0)
            if level:
                parts = pkg.split(".") if pkg else []
                if level - 1 > len(parts):
                    continue  # relative import escapes the analyzed tree
                parts = parts[: len(parts) - (level - 1)]
                base = ".".join(parts + ([base] if base else []))
            if not base:
                continue
            for name, asname in rec["names"]:
                bound = asname or name
                sub = f"{base}.{name}"
                if sub in self.by_name:
                    mod_alias[bound] = sub
                else:
                    sym_alias[bound] = (base, name)
        return mod_alias, sym_alias

    def _resolve_symbol(self, module_name: str, sym: str, depth: int = 0):
        """(module index, qualname) a symbol of ``module_name`` refers to,
        chasing ``__init__.py`` re-export chains."""
        i = self.by_name.get(module_name)
        if i is None or depth > _MAX_REEXPORT_DEPTH:
            return None
        fns = self.fn_by_qual[i]
        if sym in fns:
            return (i, sym)
        if f"{sym}.__init__" in fns:
            # calling an imported class runs its __init__ (under trace when
            # the construction site is traced)
            return (i, f"{sym}.__init__")
        sa = self.sym_aliases[i]
        if sym in sa:
            return self._resolve_symbol(sa[sym][0], sa[sym][1], depth + 1)
        ma = self.mod_aliases[i]
        if sym in ma and ma[sym] != module_name:
            # `from . import ops` style: the bound name IS a module — not a
            # callable, nothing to link here
            return None
        return None

    def _resolve_dotted(self, i: int, dotted: str):
        """Resolve a dotted edge (``alias.fn`` / ``pkg.mod.fn``) from module
        *i* to a function somewhere in the analyzed set."""
        parts = dotted.split(".")
        head = parts[0]
        ma = self.mod_aliases[i]
        if head not in ma:
            return None
        base = ma[head]
        if len(parts) == 2:
            return self._resolve_symbol(base, parts[1])
        mod = ".".join([base] + parts[1:-1])
        return self._resolve_symbol(mod, parts[-1])

    def _is_class(self, i: int, sym: str) -> bool:
        """``sym`` names an actual ClassDef in module *i*.  Qualname shape
        is NOT enough: a factory function's nested defs also own
        ``sym.<member>`` qualnames, and dispatching "methods" into them
        would wire phantom reachability."""
        return sym in self.class_sets[i]

    def _resolve_class(self, module_name: str, sym: str, depth: int = 0):
        """(module index, class qualname) a symbol refers to when it is a
        class in the analyzed set, chasing ``__init__.py`` re-export chains
        exactly like :meth:`_resolve_symbol`."""
        i = self.by_name.get(module_name)
        if i is None or depth > _MAX_REEXPORT_DEPTH:
            return None
        if self._is_class(i, sym):
            return (i, sym)
        sa = self.sym_aliases[i]
        if sym in sa:
            return self._resolve_class(sa[sym][0], sa[sym][1], depth + 1)
        return None

    def _resolve_factory_class(self, module_name: str, sym: str, depth: int = 0):
        """(module index, class qualname) constructed by factory ``sym`` of
        ``module_name``.  v11 resolved a single import hop only; v12 chases
        the full chain, bounded by ``_MAX_REEXPORT_DEPTH``: ``sym`` may be a
        RE-EXPORT of a factory defined elsewhere (``__init__.py`` chains,
        like ``_resolve_class``), and the factory's recorded ctor may itself
        be another factory — local (``make_a`` returning ``make_b()``,
        pre-resolved same-module by ``factory_returned_classes`` but still
        chased here for the knocked-out interplay), imported by symbol, or
        dotted through a module alias (``helper.make_base()``), resolved
        through THAT module's own import bindings.  Every link that fails to
        ground in a real ClassDef leaves the receiver uninferred — silent,
        never wrong."""
        if depth > _MAX_REEXPORT_DEPTH:
            return None
        j = self.by_name.get(module_name)
        if j is None:
            return None
        ctor = self.records[j].summary.factories.get(sym)
        if ctor is None:
            # not a factory of this module: chase a re-exported name
            sa = self.sym_aliases[j]
            if sym in sa:
                return self._resolve_factory_class(
                    sa[sym][0], sa[sym][1], depth + 1
                )
            return None
        mn = self.names[j]
        if "." in ctor:
            # dotted ctor (`alias.Cls` / `alias.make_thing`): resolve through
            # module j's own import bindings
            head, _, rest = ctor.partition(".")
            ma = self.mod_aliases[j]
            if head not in ma or "." in rest:
                return None
            r = self._resolve_class(ma[head], rest)
            if r is not None:
                return r
            return self._resolve_factory_class(ma[head], rest, depth + 1)
        r = self._resolve_class(mn, ctor)
        if r is not None:
            return r
        sa = self.sym_aliases[j]
        if ctor in sa:
            r = self._resolve_class(sa[ctor][0], sa[ctor][1])
            if r is not None:
                return r
            return self._resolve_factory_class(sa[ctor][0], sa[ctor][1], depth + 1)
        if ctor != sym and ctor in self.records[j].summary.factories:
            return self._resolve_factory_class(mn, ctor, depth + 1)
        return None

    def _resolve_method(self, i: int, dotted: str):
        """Resolve an instance-dispatch edge — ``Cls.method`` with ``Cls``
        local or imported, or ``mod.Cls.method`` through a module alias —
        to the method's summary.  The cross-module half of the single-
        assignment type inference (callgraph.py): the edge names the
        receiver's inferred constructor, this walks it to the class.  When
        the owner is not a class anywhere, it may be an IMPORTED factory
        (``from mod import make_thing``): v12 resolves the class its
        returns construct, chasing re-export and factory→factory
        delegation chains (bounded)."""
        owner, _, method = dotted.rpartition(".")
        if not owner or not method:
            return None
        cls = None
        if "." not in owner:
            if self._is_class(i, owner):
                cls = (i, owner)
            else:
                sa = self.sym_aliases[i]
                if owner in sa:
                    cls = self._resolve_class(sa[owner][0], sa[owner][1])
                    if cls is None:
                        cls = self._resolve_factory_class(sa[owner][0], sa[owner][1])
        else:
            head, _, rest = owner.partition(".")
            ma = self.mod_aliases[i]
            if head in ma:
                if "." not in rest:
                    cls = self._resolve_class(ma[head], rest)
                else:
                    mod = ".".join([ma[head]] + rest.split(".")[:-1])
                    cls = self._resolve_class(mod, rest.rsplit(".", 1)[-1])
        if cls is None:
            return None
        j, cls_name = cls
        target = f"{cls_name}.{method}"
        if target in self.fn_by_qual[j]:
            return (j, target)
        return None

    def _resolve_edge(self, i: int, edge: str) -> list[tuple[int, str]]:
        out: list[tuple[int, str]] = []
        if "." not in edge:
            for f in self.fn_by_leaf[i].get(edge, []):
                out.append((i, f.qualname))
            if not out and self.cross:
                sa = self.sym_aliases[i]
                if edge in sa:
                    r = self._resolve_symbol(sa[edge][0], sa[edge][1])
                    if r is not None:
                        out.append(r)
            return out
        # same-module instance dispatch (``Cls.method``) resolves even with
        # cross-module OFF — it is an exact qualname lookup restricted to
        # REAL classes (a factory function's nested defs share the qualname
        # shape), the per-module graph's behavior; import-crossing forms
        # need cross mode below
        if (
            edge in self.fn_by_qual[i]
            and edge.rsplit(".", 1)[0] in self.class_sets[i]
        ):
            out.append((i, edge))
            return out
        if self.cross:
            r = self._resolve_dotted(i, edge)
            if r is None:
                r = self._resolve_method(i, edge)
            if r is not None:
                out.append(r)
        return out

    # -- reachability -------------------------------------------------------
    def _propagate(self) -> None:
        reached: dict[tuple[int, str], str] = {}
        for i, r in enumerate(self.records):
            for qual, reason in r.summary.reached.items():
                reached[(i, qual)] = reason
        if self.cross:
            # call-form roots whose function lives in another module:
            # jax.jit(ops.step), compile_step(imported_fn), ...
            for i, r in enumerate(self.records):
                for wrapper, name in r.summary.wrapper_passed:
                    targets = self._resolve_edge(i, name)
                    for (j, qual) in targets:
                        if j != i:
                            reached.setdefault(
                                (j, qual),
                                f"passed to {wrapper} in {self.records[i].rel_path}",
                            )
        frontier = list(reached)
        while frontier:
            node = frontier.pop()
            i, qual = node
            f = self.fn_by_qual[i].get(qual)
            if f is None:
                continue
            root = reached[node].split(" via ")[0]
            for edge in f.edges:
                for (j, q2) in self._resolve_edge(i, edge):
                    if (j, q2) in reached or self.fn_by_qual[j][q2].barrier:
                        continue
                    where = qual if j == i else f"{self.records[i].rel_path}:{qual}"
                    reached[(j, q2)] = f"{root} via {where}"
                    frontier.append((j, q2))
        self.reached = reached
        self.cross_reached: dict[str, dict[str, str]] = {}
        for (i, qual), reason in reached.items():
            if qual not in self.records[i].summary.reached:
                self.cross_reached.setdefault(self.records[i].rel_path, {})[qual] = reason

    # -- derived whole-program fact maps ------------------------------------
    def _reverse_edges(self):
        """caller-by-callee map, built once and shared by every reverse
        closure (blocking, collective)."""
        if getattr(self, "_rev_edges_cache", None) is None:
            rev: dict[tuple[int, str], list[tuple[tuple[int, str], str]]] = {}
            for i, r in enumerate(self.records):
                for f in r.summary.functions:
                    for edge in f.edges:
                        for tgt in self._resolve_edge(i, edge):
                            rev.setdefault(tgt, []).append(((i, f.qualname), edge))
            self._rev_edges_cache = rev
        return self._rev_edges_cache

    def _blocking_closure(self) -> dict[tuple[int, str], str]:
        """node -> human-readable chain, for functions that transitively call
        block_until_ready/effects_barrier.  Guard-named functions neither
        seed nor relay the closure (bench helpers sync on purpose)."""
        blocking: dict[tuple[int, str], str] = {}
        for i, r in enumerate(self.records):
            for f in r.summary.functions:
                if f.blocks and not f.guard:
                    blocking[(i, f.qualname)] = "calls block_until_ready"
        rev = self._reverse_edges()
        frontier = list(blocking)
        while frontier:
            node = frontier.pop()
            for caller, edge in rev.get(node, []):
                if caller in blocking:
                    continue
                i, qual = caller
                f = self.fn_by_qual[i][qual]
                if f.guard:
                    continue
                j, q2 = node
                where = q2 if j == i else f"{self.records[j].rel_path}:{q2}"
                blocking[caller] = f"via {where}, which {blocking[node]}"
                frontier.append(caller)
        return blocking

    def _collective_closure(self) -> dict[tuple[int, str], str]:
        """node -> chain, for functions that (transitively) issue a
        collective op every rank must enter together (taint.collective_sink
        tokens).  Unlike blocking there is no guard exemption: a deliberate
        sync is still a deadlock when only some ranks reach it."""
        coll: dict[tuple[int, str], str] = {}
        for i, r in enumerate(self.records):
            for f in r.summary.functions:
                if f.collectives:
                    coll[(i, f.qualname)] = "issues " + "/".join(f.collectives)
        rev = self._reverse_edges()
        frontier = list(coll)
        while frontier:
            node = frontier.pop()
            for caller, _edge in rev.get(node, []):
                if caller in coll:
                    continue
                j, q2 = node
                i, _ = caller
                where = q2 if j == i else f"{self.records[j].rel_path}:{q2}"
                coll[caller] = f"reaches {where}, which {coll[node]}"
                frontier.append(caller)
        return coll

    def _divergence_closure(self) -> dict[tuple[int, str], str]:
        """node -> chain, for functions whose RETURN VALUE is rank-divergent
        (taint.return_flow digests).  Forward fixpoint: a function whose
        return pends on a callee (``div_via``) becomes divergent when that
        callee does — `local_restore_candidates` (fs probes) infects
        `latest_local_checkpoint` infects its callers, until a symmetry
        kill at some call site stops the chain."""
        div: dict[tuple[int, str], str] = {}
        for i, r in enumerate(self.records):
            for f in r.summary.functions:
                if f.div_direct:
                    div[(i, f.qualname)] = "returns rank-divergent state"
        changed = True
        while changed:
            changed = False
            for i, r in enumerate(self.records):
                for f in r.summary.functions:
                    node = (i, f.qualname)
                    if node in div or not f.div_via:
                        continue
                    for edge in f.div_via:
                        hit = None
                        for tgt in self._resolve_edge(i, edge):
                            if tgt in div:
                                hit = tgt
                                break
                        if hit is not None:
                            j, q2 = hit
                            where = (
                                q2 if j == i
                                else f"{self.records[j].rel_path}:{q2}"
                            )
                            div[node] = f"via {where}, which {div[hit]}"
                            changed = True
                            break
        return div

    def _visible_callables(self, i: int):
        """Yield (visible name, (module idx, qualname)) for everything module
        *i* can call by a bare or dotted name: its own top-level functions,
        symbols it imported, and ``alias.fn`` for imported modules."""
        for f in self.records[i].summary.functions:
            if "." not in f.qualname:
                yield f.qualname, (i, f.qualname)
            elif f.qualname.count(".") == 1 and f.qualname.endswith(".__init__"):
                # Cls(...) runs Cls.__init__ — a same-module constructor
                # stores buffers exactly like an imported one
                yield f.qualname.rsplit(".", 1)[0], (i, f.qualname)
        for bound, (bm, nm) in self.sym_aliases[i].items():
            r = self._resolve_symbol(bm, nm)
            if r is not None:
                yield bound, r
        for bound, target_mod in self.mod_aliases[i].items():
            j = self.by_name.get(target_mod)
            if j is None or j == i:
                continue
            for f in self.records[j].summary.functions:
                if "." not in f.qualname:
                    yield f"{bound}.{f.qualname}", (j, f.qualname)
                elif f.qualname.count(".") == 1 and f.qualname.endswith(".__init__"):
                    yield f"{bound}.{f.qualname.rsplit('.', 1)[0]}", (j, f.qualname)

    def _resolve_donor(self, module_name: str, name: str, depth: int = 0):
        i = self.by_name.get(module_name)
        if i is None or depth > _MAX_REEXPORT_DEPTH:
            return None
        donors = self.records[i].summary.donors
        if name in donors:
            return donors[name]
        sa = self.sym_aliases[i]
        if name in sa:
            return self._resolve_donor(sa[name][0], sa[name][1], depth + 1)
        return None

    def _collect_aliases_maps(self) -> None:
        # The transitive capabilities (helper-stores-a-buffer, helper-blocks)
        # are part of whole-program mode even for same-module helpers: with
        # --no-cross-module the maps stay EMPTY so the escape hatch really is
        # the historical per-module behavior (direct calls only).
        blocking = self._blocking_closure() if self.cross else {}
        divergence = self._divergence_closure() if self.cross else {}
        collective = self._collective_closure() if self.cross else {}
        self.donor_aliases: dict[str, dict[str, list[int]]] = {}
        self.escape_aliases: dict[str, dict[str, dict]] = {}
        self.blocking_aliases: dict[str, dict[str, str]] = {}
        self.divergent_aliases: dict[str, dict[str, str]] = {}
        self.collective_aliases: dict[str, dict[str, str]] = {}
        for i, r in enumerate(self.records):
            rel = r.rel_path
            donors = dict(r.summary.donors)
            escapes: dict[str, dict] = {}
            blocks: dict[str, str] = {}
            divergent: dict[str, str] = {}
            coll: dict[str, str] = {}
            if self.cross:
                for visible, (j, qual) in self._visible_callables(i):
                    f = self.fn_by_qual[j][qual]
                    if f.escapes:
                        where = qual if j == i else f"{self.records[j].rel_path}:{qual}"
                        escapes.setdefault(
                            visible, {"positions": list(f.escapes), "where": where}
                        )
                    chain = blocking.get((j, qual))
                    if chain is not None:
                        blocks.setdefault(visible, chain)
                    chain = divergence.get((j, qual))
                    if chain is not None:
                        divergent.setdefault(visible, chain)
                    chain = collective.get((j, qual))
                    if chain is not None:
                        coll.setdefault(visible, chain)
                # own methods by qualname, so `self.helper()` call sites
                # (candidate `Cls.helper`) resolve through the maps too
                for f in r.summary.functions:
                    if "." not in f.qualname:
                        continue
                    chain = divergence.get((i, f.qualname))
                    if chain is not None:
                        divergent.setdefault(f.qualname, chain)
                    chain = collective.get((i, f.qualname))
                    if chain is not None:
                        coll.setdefault(f.qualname, chain)
            if self.cross:
                for bound, (bm, nm) in self.sym_aliases[i].items():
                    pos = self._resolve_donor(bm, nm)
                    if pos:
                        donors.setdefault(bound, list(pos))
                for bound, target_mod in self.mod_aliases[i].items():
                    j = self.by_name.get(target_mod)
                    if j is None or j == i:
                        continue
                    for dn, pos in self.records[j].summary.donors.items():
                        donors.setdefault(f"{bound}.{dn}", list(pos))
            if donors:
                self.donor_aliases[rel] = donors
            if escapes:
                self.escape_aliases[rel] = escapes
            if blocks:
                self.blocking_aliases[rel] = blocks
            if divergent:
                self.divergent_aliases[rel] = divergent
            if coll:
                self.collective_aliases[rel] = coll

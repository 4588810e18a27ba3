"""Rank-divergence taint model — the dataflow core behind the
``collective-divergence`` rule family.

The worst bug class in a multi-process mesh program is a collective
(gather, vote, ``load_state``, ``fleet.resize``) guarded by **rank-divergent
state**: only some ranks enter the collective and the mesh deadlocks.  This
module gives the analyzer a semantics for "rank-divergent":

* **sources** mint divergent values — rank identity reads
  (``process_index`` / ``is_main_process``), rank-local retained telemetry
  records (``serving_signal`` / ``serving_events``, docs/telemetry.md), env
  vars documented as per-host (``LOCAL_RANK``-shaped keys), filesystem
  probes (each host sees its own disk), wall-clock reads, and host identity;
* **propagation** carries taint through assignments, returns, call
  arguments, method calls on a tainted receiver, and attribute/subscript
  stores on local (non-``self``) receivers;
* **kills** erase taint at the documented symmetry points: a value derived
  from an all-ranks merge (``gather_object`` / ``all_gather`` / ``psum`` /
  ``broadcast``) or from an ``agree_*`` pure merge is the SAME on every
  rank, however rank-local its inputs were (docs/elastic.md);
* **exemption** — a branch conjoined with a single-process world-size test
  (``not _multi_process()``, ``num_processes == 1``) never executes on a
  multi-process run, so divergence inside it is moot.  This is exactly the
  sanctioned PR-13 fix shape for the serving-signal gate
  (fleet/autopilot.py), so the linter recognizes the fix it once forced.

:class:`FunctionTaint` runs a per-function fixpoint at Name granularity.
It serves two callers: ``program.extract_summary`` uses it with no
cross-module knowledge to digest each function's *return-divergence*
(direct, or pending on named callees — the whole-program fixpoint in
``program.ProgramGraph`` resolves those), and the rule re-runs it with the
resolved ``divergent_aliases`` map so call sites of divergent-returning
functions taint immediately.

Documented approximations (kept deliberately, each in the safe direction
for its purpose): parameters start clean (cross-function argument taint is
not tracked — a false-negative risk only); ``self.x = tainted`` does not
taint other methods' ``self.x`` reads (false-negative); comprehension
binders leak into the function scope (false-positive, caught by fixtures);
seeded ``random`` streams are NOT sources (seeding is the documented way to
keep them symmetric).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .callgraph import dotted_name

# ---------------------------------------------------------------------------
# source tables
# ---------------------------------------------------------------------------

# attribute reads (and accessor calls) that ARE rank identity / rank-local
# state wherever they appear.  ``serving_events`` is the rank-local retained
# record list (docs/telemetry.md: serving records live on the rank that owns
# the hub); ``fleet_events`` is deliberately absent — the kind="fleet" skew
# record is REQUIRED to be rank-symmetric (built from an all-ranks gather,
# the PR-13 contract documented in docs/telemetry.md).
DIVERGENT_ATTRS = frozenset(
    {
        "process_index",
        "local_process_index",
        "is_main_process",
        "is_local_main_process",
        "is_last_process",
        "serving_events",
    }
)

# call leaves that mint a rank-divergent value regardless of receiver
_DIVERGENT_CALL_LEAVES = frozenset(
    {
        "serving_signal",  # newest rank-local serving record
        "gethostname",
        "getfqdn",
    }
)

_HOST_IDENT_CALLS = frozenset(
    {
        "os.getpid",
        "socket.gethostname",
        "socket.getfqdn",
        "platform.node",
        "uuid.getnode",
        "uuid.uuid1",
        "uuid.uuid4",
    }
)

_WALL_CLOCK_CALLS = frozenset(
    {
        "time.time",
        "time.time_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.clock_gettime",
    }
)

# fs-probe call forms: full dotted stdlib paths, plus method leaves that are
# probes on ANY receiver (pathlib.Path and os.path share these spellings)
_FS_PROBE_CALLS = frozenset(
    {
        "os.listdir",
        "os.scandir",
        "os.walk",
        "os.stat",
        "os.path.exists",
        "os.path.isfile",
        "os.path.isdir",
        "os.path.islink",
        "os.path.getmtime",
        "os.path.getsize",
        "glob.glob",
        "glob.iglob",
    }
)
_FS_PROBE_METHOD_LEAVES = frozenset(
    {
        "exists",
        "is_file",
        "is_dir",
        "is_symlink",
        "listdir",
        "scandir",
        "glob",
        "iglob",
        "rglob",
        "getmtime",
        "getsize",
    }
)

# env keys documented as per-host/per-rank; symmetric config flags
# (ACCELERATE_*, TPU_PAD_MULTIPLE) deliberately don't match
_PER_HOST_ENV_RE = re.compile(
    r"(?:^|_)(LOCAL|HOST(?:NAME)?|RANK|NODE|WORKER)(?:_|$)|PROCESS_INDEX|PROCESS_ID"
)

# ---------------------------------------------------------------------------
# kills — documented symmetry points (docs/elastic.md, docs/telemetry.md)
# ---------------------------------------------------------------------------

_SYMMETRY_KILL_LEAVES = frozenset(
    {
        "gather_object",
        "all_gather",
        "all_gather_object",
        "allgather",
        "broadcast",
        "broadcast_object_list",
        "psum",
        "psum_scatter",
        "pmean",
        "pmax",
        "pmin",
        "all_to_all",
        "all_reduce",
        "sync_global_devices",
    }
)
_AGREE_PREFIX = "agree_"  # fleet pure merges: same inputs -> same answer

# ---------------------------------------------------------------------------
# collective sinks — ops every rank must enter together
# ---------------------------------------------------------------------------

_JAX_COLLECTIVE_LEAVES = frozenset(
    {
        "psum",
        "psum_scatter",
        "pmean",
        "pmax",
        "pmin",
        "all_gather",
        "all_to_all",
        "ppermute",
        "pshuffle",
    }
)
_JAX_PREFIXES = frozenset({"jax", "lax", "jnp"})
_FRAMEWORK_COLLECTIVE_LEAVES = frozenset(
    {
        "gather_object",
        "broadcast",
        "broadcast_object_list",
        "wait_for_everyone",
        "sync_global_devices",
        "vote_restore_point",
        "coordinated_rollback",
        "load_state",
        "save_state",
    }
)
_FLEET_VERB_LEAVES = frozenset({"resize", "grow"})

# builtins whose pending-callee edges are pure noise for the closure
_BUILTIN_NOISE = frozenset(
    {
        "abs", "all", "any", "bool", "bytes", "dict", "enumerate", "filter",
        "float", "format", "frozenset", "getattr", "hasattr", "id", "int",
        "isinstance", "issubclass", "iter", "len", "list", "map", "max",
        "min", "next", "print", "range", "repr", "reversed", "round", "set",
        "setattr", "sorted", "str", "sum", "tuple", "type", "vars", "zip",
    }
)

_MULTI_PROCESS_RE = re.compile(r"multi_process|is_distributed", re.IGNORECASE)
_WORLD_SIZE_RE = re.compile(
    r"num_processes|world_size|process_count", re.IGNORECASE
)

# ---------------------------------------------------------------------------
# rank-local-by-design modules (docs/telemetry.md §flight recorder)
# ---------------------------------------------------------------------------

# Postmortem writers run while the mesh may already be deadlocked: they read
# rank identity, the wall clock and the filesystem ON PURPOSE (the dump must
# name its rank and stamp its time), so the divergence scan would drown them
# in by-design findings.  The exemption is a CONTRACT, not a blanket waiver:
# in exchange, these modules must never contain a collective sink — a
# watchdog that gathers about the hang deadlocks the postmortem too.  The
# collective-divergence rule enforces the inverted direction on exactly this
# set (tests/test_graftlint.py pins both).
RANK_LOCAL_MODULE_SUFFIXES = frozenset(
    {
        "telemetry/flightrec.py",
        "telemetry/watchdog.py",
        "telemetry/trace_export.py",
    }
)


def rank_local_by_design(rel_path: str) -> bool:
    """True when ``rel_path`` names a module declared rank-local by design
    (per-rank postmortem writers — exempt from the divergence scan, but
    forbidden from ever issuing a collective)."""
    path = rel_path.replace("\\", "/")
    return any(path.endswith(suffix) for suffix in RANK_LOCAL_MODULE_SUFFIXES)


def _call_leaf(fn: ast.AST) -> Optional[str]:
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _resolved(fn: ast.AST, module) -> str:
    r = module.resolve(fn) if module is not None else None
    return r or (dotted_name(fn) or "")


# ---------------------------------------------------------------------------
# world-size guards (the sanctioned single-process gate)
# ---------------------------------------------------------------------------

def _leaf_dotted(node: ast.AST) -> str:
    if isinstance(node, ast.Call):
        node = node.func
    return dotted_name(node) or ""


def _world_size_expr(node: ast.AST) -> bool:
    d = _leaf_dotted(node)
    return bool(d and _WORLD_SIZE_RE.search(d))


def _multi_process_expr(node: ast.AST) -> bool:
    d = _leaf_dotted(node)
    return bool(d and _MULTI_PROCESS_RE.search(d))


def _world_size_is_many(node: ast.AST) -> bool:
    """``num_processes > 1`` / ``>= 2`` / ``!= 1`` shapes."""
    if isinstance(node, ast.Compare) and len(node.ops) == 1:
        op, l, r = node.ops[0], node.left, node.comparators[0]
        if _world_size_expr(l) and isinstance(r, ast.Constant):
            return (
                (isinstance(op, ast.Gt) and r.value == 1)
                or (isinstance(op, ast.GtE) and r.value == 2)
                or (isinstance(op, ast.NotEq) and r.value == 1)
            )
    return False


def single_process_conjunct(test: ast.AST) -> bool:
    """True when ``test`` (or one of its AND-conjuncts) restricts the branch
    to single-process runs — on a multi-process run the whole conjunction is
    uniformly False on EVERY rank, so nothing inside can diverge a mesh.
    Recognized spellings: ``not _multi_process()``, ``not state.use_distributed``
    -style multi-process predicates under ``not``, and world-size compares
    (``num_processes == 1`` / ``<= 1`` / ``< 2``, either operand order)."""
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        return any(single_process_conjunct(v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _multi_process_expr(test.operand) or _world_size_is_many(
            test.operand
        )
    if isinstance(test, ast.Compare) and len(test.ops) == 1:
        op, l, r = test.ops[0], test.left, test.comparators[0]
        if _world_size_expr(l) and isinstance(r, ast.Constant):
            return (
                (isinstance(op, ast.Eq) and r.value == 1)
                or (isinstance(op, ast.LtE) and r.value == 1)
                or (isinstance(op, ast.Lt) and r.value == 2)
            )
        if _world_size_expr(r) and isinstance(l, ast.Constant):
            return (
                (isinstance(op, ast.Eq) and l.value == 1)
                or (isinstance(op, ast.GtE) and l.value == 1)
                or (isinstance(op, ast.Gt) and l.value == 2)
            )
    return False


# ---------------------------------------------------------------------------
# node classifiers
# ---------------------------------------------------------------------------

def divergence_source_call(node: ast.Call, module) -> Optional[str]:
    """Token naming the divergence source when this call mints one."""
    fn = node.func
    leaf = _call_leaf(fn)
    if leaf is None:
        return None
    if leaf in _DIVERGENT_CALL_LEAVES or leaf in DIVERGENT_ATTRS:
        return leaf
    resolved = _resolved(fn, module)
    if resolved in _HOST_IDENT_CALLS or resolved in _WALL_CLOCK_CALLS:
        return resolved
    if resolved in _FS_PROBE_CALLS or leaf in _FS_PROBE_METHOD_LEAVES:
        return resolved or leaf
    if leaf in ("now", "utcnow", "today") and "date" in resolved:
        return resolved
    if resolved in ("os.environ.get", "os.getenv") and node.args:
        key = node.args[0]
        if (
            isinstance(key, ast.Constant)
            and isinstance(key.value, str)
            and _PER_HOST_ENV_RE.search(key.value)
        ):
            return f"os.environ[{key.value!r}]"
    return None


def divergence_source_subscript(node: ast.Subscript, module) -> Optional[str]:
    """``os.environ["LOCAL_RANK"]``-style per-host env reads."""
    base = _resolved(node.value, module)
    if base != "os.environ":
        return None
    key = node.slice
    if (
        isinstance(key, ast.Constant)
        and isinstance(key.value, str)
        and _PER_HOST_ENV_RE.search(key.value)
    ):
        return f"os.environ[{key.value!r}]"
    return None


def symmetry_kill(node: ast.Call) -> bool:
    """The call's RESULT is rank-symmetric by construction (an all-ranks
    merge or an ``agree_*`` pure merge) — taint dies here, including taint
    in the arguments (merging rank-local inputs is the point)."""
    leaf = _call_leaf(node.func)
    if leaf is None:
        return False
    return leaf in _SYMMETRY_KILL_LEAVES or leaf.startswith(_AGREE_PREFIX)


def collective_sink(node: ast.Call, module) -> Optional[str]:
    """Token when this call is a collective every rank must enter together:
    framework collectives by leaf, jax collectives under a jax/lax prefix,
    and ``resize``/``grow`` on a fleet-named receiver (docs/elastic.md)."""
    fn = node.func
    leaf = _call_leaf(fn)
    if leaf is None:
        return None
    if leaf in _FRAMEWORK_COLLECTIVE_LEAVES:
        return leaf
    if leaf in _JAX_COLLECTIVE_LEAVES:
        resolved = _resolved(fn, module)
        if _JAX_PREFIXES & set(resolved.split(".")):
            return leaf
    if leaf in _FLEET_VERB_LEAVES and isinstance(fn, ast.Attribute):
        recv = dotted_name(fn.value) or ""
        if "fleet" in recv.lower():
            return f"fleet.{leaf}"
    return None


def collective_leaves(module, fn_node: ast.AST) -> List[str]:
    """Sorted collective-sink tokens issued directly in ``fn_node``'s own
    body (nested defs excluded — they are their own call-graph nodes)."""
    calls = module.index.own(fn_node, ast.Call)
    return sorted({tok for tok in (collective_sink(c, module) for c in calls) if tok})


# ---------------------------------------------------------------------------
# the per-function fixpoint
# ---------------------------------------------------------------------------

class FunctionTaint:
    """Which local names of one function can hold a rank-divergent value.

    Order-insensitive: the statement walk repeats until the tainted set and
    the pending-callee map stop changing, so uses before (textual) defs in
    loops converge.  Control context is tracked for implicit flows — an
    assignment under a tainted test taints its target (``flag = True`` under
    ``if is_main_process:`` makes ``flag`` divergent), and a ``return``
    under a tainted test makes the RETURN divergent (callers branch on a
    value that differs per rank).

    ``known`` maps callable names (visible names, ``Cls.method`` qualnames)
    to human-readable chains for functions the whole-program fixpoint proved
    divergent-returning; without it, unresolved callee names accumulate as
    *pending* edges in :attr:`via` / :attr:`return_via` for the program
    graph to resolve later.
    """

    MAX_PASSES = 10

    def __init__(self, module, fn_node, known=None, self_prefix=None):
        self.module = module
        self.fn = fn_node
        self.known: Dict[str, str] = dict(known or {})
        self.self_prefix = self_prefix
        self.tainted: Set[str] = set()
        self.via: Dict[str, Set[str]] = {}
        self.return_direct = False
        self.return_via: Set[str] = set()
        self._run()

    # -- public ------------------------------------------------------------
    def expr_tainted(self, node: ast.AST) -> bool:
        t, pending = self.eval(node)
        return t or any(p in self.known for p in pending)

    def describe(self, node: ast.AST) -> str:
        """Best-effort token naming WHY an expression is divergent, for
        finding messages."""
        index = self.module.index
        for sub in index.as_walked(index.walk(node, ast.Call, ast.Attribute, ast.Subscript)):
            if isinstance(sub, ast.Call):
                src = divergence_source_call(sub, self.module)
                if src:
                    return f"{src}(...)" if not src.endswith("]") else src
            elif isinstance(sub, ast.Attribute) and sub.attr in DIVERGENT_ATTRS:
                return sub.attr
            elif isinstance(sub, ast.Subscript):
                src = divergence_source_subscript(sub, self.module)
                if src:
                    return src
        for sub in index.as_walked(index.walk(node, ast.Call, ast.Name)):
            if isinstance(sub, ast.Call):
                for cand in callee_names(sub.func, self.self_prefix):
                    if cand in self.known:
                        return f"{cand}() [{self.known[cand]}]"
            elif isinstance(sub, ast.Name) and sub.id in self.tainted:
                return sub.id
        return "rank-divergent state"

    # -- fixpoint driver -----------------------------------------------------
    def _snapshot(self):
        return (
            frozenset(self.tainted),
            {k: frozenset(v) for k, v in self.via.items()},
            self.return_direct,
            frozenset(self.return_via),
        )

    def _run(self) -> None:
        for _ in range(self.MAX_PASSES):
            before = self._snapshot()
            self._walk(self.fn.body, False, set(), False)
            if self._snapshot() == before:
                break

    # -- statements ----------------------------------------------------------
    def _walk(self, stmts, ctx_t: bool, ctx_p: Set[str], killed: bool) -> None:
        for stmt in stmts:
            if isinstance(
                stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue  # nested defs are their own call-graph nodes
            if isinstance(stmt, ast.Assign):
                self._assign(stmt.targets, stmt.value, ctx_t, ctx_p, killed)
            elif isinstance(stmt, ast.AnnAssign):
                if stmt.value is not None:
                    self._assign([stmt.target], stmt.value, ctx_t, ctx_p, killed)
            elif isinstance(stmt, ast.AugAssign):
                self._assign([stmt.target], stmt.value, ctx_t, ctx_p, killed)
            elif isinstance(stmt, ast.Return):
                t, p = (
                    self.eval(stmt.value)
                    if stmt.value is not None
                    else (False, set())
                )
                if not killed:
                    # a return under a divergent test is itself divergent:
                    # which value comes back differs per rank
                    self.return_direct = self.return_direct or t or ctx_t
                    self.return_via |= p | ctx_p
            elif isinstance(stmt, ast.If):
                t, p = self.eval(stmt.test)
                if single_process_conjunct(stmt.test):
                    # the branch never executes multi-process: values born
                    # here cannot diverge a mesh (the PR-13 gate shape); the
                    # else-side entry is uniformly multi-process — symmetric
                    self._walk(stmt.body, False, set(), True)
                    self._walk(stmt.orelse, ctx_t, ctx_p, killed)
                else:
                    bt = ctx_t or (t and not killed)
                    bp = ctx_p | p
                    self._walk(stmt.body, bt, bp, killed)
                    self._walk(stmt.orelse, bt, bp, killed)
            elif isinstance(stmt, ast.While):
                t, p = self.eval(stmt.test)
                if single_process_conjunct(stmt.test):
                    self._walk(stmt.body, False, set(), True)
                else:
                    self._walk(stmt.body, ctx_t or t, ctx_p | p, killed)
                self._walk(stmt.orelse, ctx_t, ctx_p, killed)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                t, p = self.eval(stmt.iter)
                if not killed:
                    self._bind(stmt.target, t, p)
                self._walk(stmt.body, ctx_t or t, ctx_p | p, killed)
                self._walk(stmt.orelse, ctx_t, ctx_p, killed)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    t, p = self.eval(item.context_expr)
                    if item.optional_vars is not None and not killed:
                        self._bind(item.optional_vars, t, p)
                self._walk(stmt.body, ctx_t, ctx_p, killed)
            elif isinstance(stmt, ast.Try) or (
                hasattr(ast, "TryStar") and isinstance(stmt, ast.TryStar)
            ):
                self._walk(stmt.body, ctx_t, ctx_p, killed)
                for h in stmt.handlers:
                    self._walk(h.body, ctx_t, ctx_p, killed)
                self._walk(stmt.orelse, ctx_t, ctx_p, killed)
                self._walk(stmt.finalbody, ctx_t, ctx_p, killed)
            elif isinstance(stmt, ast.Match):
                t, p = self.eval(stmt.subject)
                for case in stmt.cases:
                    self._walk(case.body, ctx_t or t, ctx_p | p, killed)
            elif isinstance(stmt, ast.Expr):
                self.eval(stmt.value)
            elif isinstance(stmt, ast.Assert):
                self.eval(stmt.test)
            # Raise/Pass/Break/Continue/Import/Global/Delete: nothing tracked

    def _assign(self, targets, value, ctx_t, ctx_p, killed) -> None:
        t, p = self.eval(value)
        if killed:
            return  # single-process-only values never diverge a mesh
        t = t or ctx_t
        p = p | ctx_p
        for tgt in targets:
            self._bind(tgt, t, p)

    def _bind(self, target, t: bool, p: Set[str]) -> None:
        if isinstance(target, ast.Name):
            if t:
                self.tainted.add(target.id)
            if p:
                self.via.setdefault(target.id, set()).update(p)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._bind(e, t, p)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, t, p)
        elif isinstance(target, (ast.Attribute, ast.Subscript)):
            # a store INTO a local object taints the object (`cfg.rank = idx`
            # makes every later `cfg.*` read divergent); `self`/`cls` stores
            # are out of scope (documented approximation)
            base = target
            while isinstance(base, (ast.Attribute, ast.Subscript)):
                base = base.value
            if isinstance(base, ast.Name) and base.id not in ("self", "cls"):
                self._bind(base, t, p)

    # -- expressions ---------------------------------------------------------
    def eval(self, node) -> Tuple[bool, Set[str]]:
        if node is None or isinstance(node, ast.Constant):
            return False, set()
        if isinstance(node, ast.Name):
            return node.id in self.tainted, set(self.via.get(node.id, ()))
        if isinstance(node, ast.Attribute):
            if node.attr in DIVERGENT_ATTRS:
                return True, set()
            return self.eval(node.value)
        if isinstance(node, ast.Subscript):
            if divergence_source_subscript(node, self.module):
                return True, set()
            t1, p1 = self.eval(node.value)
            t2, p2 = self.eval(node.slice)
            return t1 or t2, p1 | p2
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, ast.NamedExpr):
            t, p = self.eval(node.value)
            self._bind(node.target, t, p)
            return t, p
        if isinstance(node, ast.Lambda):
            return False, set()
        if isinstance(node, ast.IfExp):
            tt, tp = self.eval(node.test)
            bt, bp = self.eval(node.body)
            ot, op = self.eval(node.orelse)
            return tt or bt or ot, tp | bp | op
        if isinstance(
            node, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.DictComp)
        ):
            t, p = False, set()
            for gen in node.generators:
                it, ip = self.eval(gen.iter)
                self._bind(gen.target, it, ip)
                t, p = t or it, p | ip
                for cond in gen.ifs:
                    ct, cp = self.eval(cond)
                    t, p = t or ct, p | cp
            elts = (
                (node.key, node.value)
                if isinstance(node, ast.DictComp)
                else (node.elt,)
            )
            for e in elts:
                et, ep = self.eval(e)
                t, p = t or et, p | ep
            return t, p
        # generic fold over child expressions: BoolOp, BinOp, Compare,
        # UnaryOp, f-strings, containers, starred, slices, await, yield
        t, p = False, set()
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.expr, ast.keyword)):
                ct, cp = self.eval(child)
                t, p = t or ct, p | cp
        return t, p

    def _eval_call(self, node: ast.Call) -> Tuple[bool, Set[str]]:
        fn = node.func
        if symmetry_kill(node):
            return False, set()
        if divergence_source_call(node, self.module):
            return True, set()
        t, p = False, set()
        if isinstance(fn, ast.Attribute):
            # a method on a divergent object returns divergent data
            # (`record.get("queue_depth")` with record rank-local)
            rt, rp = self.eval(fn.value)
            t, p = t or rt, p | rp
        for arg in node.args:
            at, ap = self.eval(arg)
            t, p = t or at, p | ap
        for kw in node.keywords:
            at, ap = self.eval(kw.value)
            t, p = t or at, p | ap
        for cand in callee_names(fn, self.self_prefix):
            if cand in self.known:
                t = True
            else:
                p.add(cand)
        return t, p


def callee_names(fn: ast.AST, self_prefix: Optional[str]) -> List[str]:
    """Candidate callable names a Call's func may resolve to, in the edge
    conventions ``program._resolve_edge`` / the alias maps use: bare names
    for Name calls and ``self.x()`` (plus the enclosing ``Cls.x`` qualname,
    ``self_prefix``, when known), full dotted names otherwise."""
    if isinstance(fn, ast.Name):
        return [] if fn.id in _BUILTIN_NOISE else [fn.id]
    if isinstance(fn, ast.Attribute):
        dotted = dotted_name(fn)
        if dotted is None:
            return []
        parts = dotted.split(".")
        if parts[0] in ("self", "cls"):
            if len(parts) != 2:
                # self.logger.log(): the receiver is an attribute object
                # of unknown type, not the enclosing class — resolving
                # the leaf against our own methods would be a lie
                return []
            leaf = parts[1]
            return [leaf, f"{self_prefix}.{leaf}"] if self_prefix else [leaf]
        return [dotted]
    return []


def return_flow(module, fn_node, self_prefix=None) -> Tuple[bool, List[str]]:
    """Summary-mode digest for one function: (returns-divergent-directly,
    sorted pending callee names whose divergence would make the return
    divergent).  The pending list is capped to bound cache entries."""
    ft = FunctionTaint(module, fn_node, known=None, self_prefix=self_prefix)
    return ft.return_direct, sorted(ft.return_via)[:64]

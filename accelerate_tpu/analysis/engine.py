"""graftlint engine: discovery, suppression comments, baseline, rule runner.

Pure-stdlib AST analysis — importing this module must never import jax (the
CLI runs it in a few hundred milliseconds so it can sit inside ``make test``).
"""

from __future__ import annotations

import ast
import bisect
import dataclasses
import hashlib
import io
import json
import os
import re
import time
import tokenize
from typing import Iterable, Optional, Sequence, Union

# Suppression comment grammar (the leading hash is spelled \x23 here so this
# very comment can't register itself): "\x23 graftlint: disable=rule-a,rule-b"
# on (or as the comment line above) the offending line;
# "\x23 graftlint: disable-file=rule-a" anywhere silences a whole file.  A
# bare "disable" with no =list silences every rule.  Anything after the rule
# list (a justification like "-- profiling only") is ignored.
_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable(?P<scope>-file)?(?:\s*=\s*(?P<rules>[A-Za-z0-9_,\- ]+))?"
)
_RULE_TOKEN_RE = re.compile(r"^[A-Za-z][A-Za-z0-9_-]*$")

# Bumping this invalidates every on-disk cache entry (cache.py keys on it):
# bump whenever a rule or the graph machinery changes what it reports for
# unchanged source.  v3: dtype-widen gained the quantized-payload check.
# v4: recompile-hazard gained the serving bucketing contract (raw request
# lengths into run_prefill/run_decode).
# v5: blocking-in-hot-loop gained the profiler-session check
# (jax.profiler start/stop_trace in a loop without sampled-cadence
# evidence; a profiling-knob guard alone no longer exempts those calls).
# v6: recompile-hazard gained the AOT executable cache-key contract
# (deserialize_and_load of a serialized executable without a fingerprint/
# cache-key check in scope — a stale entry from another topology or jax
# version must fall through to a compile, never dispatch; docs/aot_cache.md).
# v7: the call graph resolves instance-method dispatch through cheap type
# inference over single-assignment locals (`obj = SomeClass(); obj.method(x)`
# links to SomeClass.method, same-module and through imports), so every
# reachability rule sees traced code calling into helper-object methods.
# v8: new pallas-hazard rule — host callbacks / python-side branches on ref
# parameters inside pl.pallas_call kernel bodies, and pallas_call sites
# without an interpret=/policy-gated fallback in scope (docs/kernels.md).
# v9: instance-dispatch inference joins over branches — a receiver rebound
# across branches to the SAME class (`obj = Cls() if fast else Cls(opts)`)
# now links `obj.method` to Cls.method; receivers rebound to different
# classes (or to non-constructor values) stay uninferred.
# v10: instance-dispatch inference through factory returns — a receiver
# bound from a same-module TOP-LEVEL function whose returns are ALL
# `SomeClass(...)` constructors of one class (`obj = make_runner();
# obj.work(x)`) resolves to SomeClass.work, joining over branches with
# direct constructor binds.  Mixed-class or non-constructor returns,
# same-named factories that disagree, methods/nested defs (bare name not
# module-callable), and locally-shadowed names (an injected callable
# parameter is DATA, not the module factory) all leave the receiver
# uninferred.
# v12: (a) new collective-divergence rule family — an interprocedural
# rank-divergence taint pass (taint.py: rank-identity/rank-local-record/
# fs-probe/wall-clock/per-host-env sources, gather/agree_* symmetry kills,
# single-process world-size exemption) feeds three checks: a collective
# sink guarded by rank-divergent control flow, early return/raise on a
# tainted branch before a later collective, and mismatched collective
# counts across sibling branches of a tainted conditional; the program
# graph grew divergent-return and reaches-collective closures
# (divergent_aliases / collective_aliases) to carry both facts across
# modules.  (b) factory-return dispatch inference now chases
# factory→factory delegation chains (same-module pre-resolution in
# callgraph.py, cross-module chasing in program.py) and multi-hop
# re-export paths, closing the v11 single-hop carve-out.
# v11: (a) new stage-boundary-vs-plan rule — pp axis sizes / stage layer
# spans derived outside the resolved ParallelPlan (mesh.shape pp reads,
# literal P('pp') specs, hand-sliced layers-per-stage arithmetic) fire in
# consumer modules (docs/parallel_plan.md); (b) factory-return dispatch
# inference through SINGLE-HOP imports — `from mod import make_thing;
# obj = make_thing(); obj.m(x)` resolves through mod's v10 factory map to
# the constructed class (factory→factory chains and re-exported factories
# stay uninferred); (c) a bare-name constructor call whose name is locally
# bound (parameter/assignment) now records NO ctor bind at all, so
# shadowed names can never mis-resolve through the new import hop.
# v13: stage-boundary-vs-plan learned the prepare-time layer-layout
# contract — jnp.take/jnp.argsort driven by a layer-order index (an
# in-program stacked-layer permutation inside a captured pipeline body)
# fires in consumer modules with a commit-at-prepare fix hint
# (docs/parallel_plan.md §layout contract).
# v14: every rule and the summary read one per-module index (ModuleIndex)
# instead of re-walking the tree; a summary's axes are bare names.
ANALYSIS_VERSION = "14"

# Names that mark a branch/function as profiling/benchmark plumbing, where a
# deliberate host sync is legitimate.  Shared by blocking-in-hot-loop and the
# whole-program transitive-blocking closure (program.py).
GUARD_NAME_RE = re.compile(
    r"profil|debug|verbose|bench|warmup|timing|timeit|trace|sync_every|"
    r"sync_each|log_every|barrier|measure",
    re.IGNORECASE,
)


def is_guard_expr(index: "ModuleIndex", test: ast.AST) -> bool:
    """True when a test expression mentions a profiling/debug knob."""
    return any(
        GUARD_NAME_RE.search(node.id if isinstance(node, ast.Name) else node.attr)
        for node in index.walk(test, ast.Name, ast.Attribute)
    )


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    symbol: str = ""  # enclosing function qualname (stable across line drift)

    def fingerprint(self) -> str:
        """Line-number-free identity used by the baseline file, so grandfathered
        findings survive unrelated edits above them."""
        key = "|".join((self.rule, self.path.replace(os.sep, "/"), self.symbol, self.message))
        return hashlib.sha1(key.encode("utf-8")).hexdigest()[:16]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["fingerprint"] = self.fingerprint()
        return d

    def render(self) -> str:
        loc = f"{self.path}:{self.line}:{self.col}"
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{loc}: {self.rule}: {self.message}{sym}"


class Rule:
    """Base class: subclasses set ``id``/``description``/``kind`` and
    implement check().  ``kind`` is "reachability" when the rule consumes the
    traced-region call graph (so it benefits from cross-module analysis) and
    "syntactic" when it fires on local syntax alone — `--list-rules` prints
    it so suppression triage knows which findings can shift when
    whole-program mode is toggled."""

    id: str = ""
    description: str = ""
    kind: str = "syntactic"
    # one-line remediation shown in SARIF output (rule help + appended to
    # each result message) so CI annotations carry the fix, not just the
    # diagnosis
    fix_hint: str = ""

    def check(self, module: "ModuleInfo", ctx: "AnalysisContext") -> list[Finding]:
        raise NotImplementedError


def _dotted(node: ast.AST) -> Optional[str]:
    from .callgraph import dotted_name

    return dotted_name(node)


_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


class ModuleIndex:
    """One module's tree, walked once and read by every rule and by the
    summary.  Nodes are held in preorder with each subtree's end, so a
    subtree is a slice; each type's positions are sorted, so the nodes of a
    type under any node are a bisected slice; and a scope's own nodes (its
    body short of nested def/class bodies, but with their decorators and
    defaults, which run in the enclosing scope) are a few cached ranges.
    Expression contexts and operators are shared between nodes by the
    parser: they are in the walks, but no query starts from one."""

    def __init__(self, tree: ast.AST):
        order: list[ast.AST] = []
        end: list[int] = []
        depth: list[int] = []
        pos: dict[ast.AST, int] = {}
        by_type: dict[type, list[int]] = {}

        # iterative, so a deep expression (a long chain of `+`) cannot
        # exhaust the interpreter's recursion limit; (None, i) closes node i
        stack: list = [(tree, 0)]
        while stack:
            node, d = stack.pop()
            if node is None:
                end[d] = len(order)
                continue
            i = len(order)
            order.append(node)
            end.append(0)
            depth.append(d)
            pos[node] = i
            by_type.setdefault(type(node), []).append(i)
            stack.append((None, i))
            children = []
            for field in node._fields:
                v = getattr(node, field, None)
                if isinstance(v, list):
                    children += [(x, d + 1) for x in v if isinstance(x, ast.AST)]
                elif isinstance(v, ast.AST):
                    children.append((v, d + 1))
            stack += reversed(children)
        self.order, self.end, self.depth, self.pos = order, end, depth, pos
        self.by_type = by_type
        self._own: dict[ast.AST, list[tuple[int, int]]] = {}

    def _select(self, ranges, types) -> list:
        hits: list[int] = []
        for t in types:
            at = self.by_type.get(t)
            if at:
                for lo, hi in ranges:
                    hits += at[bisect.bisect_left(at, lo):bisect.bisect_left(at, hi)]
        if len(types) > 1:
            hits.sort()
        order = self.order
        return [order[i] for i in hits]

    def walk(self, node: ast.AST, *types: type) -> list:
        """``node`` and its descendants in preorder (of ``types`` only, when
        given) — what ``ast.walk`` yields, in source order."""
        i = self.pos[node]
        if not types:
            return self.order[i:self.end[i]]
        return self._select([(i, self.end[i])], types)

    def of_type(self, *types: type) -> list:
        """Every node of ``types`` in the module, in preorder."""
        return self._select([(0, len(self.order))], types)

    def own(self, scope: ast.AST, *types: type) -> list:
        """``scope``'s own nodes (of ``types`` only, when given), in preorder."""
        ranges = self._own.get(scope)
        if ranges is None:
            ranges = self._own[scope] = self._own_ranges(scope)
        if not types:
            return [n for lo, hi in ranges for n in self.order[lo:hi]]
        return self._select(ranges, types)

    def _own_ranges(self, scope) -> list[tuple[int, int]]:
        start, stop = self.pos[scope] + 1, self.end[self.pos[scope]]
        ranges, cur = [], start
        for node in self._select([(start, stop)], _SCOPES):
            i = self.pos[node]
            if i < cur:
                continue  # inside a nested scope already cut out
            ranges.append((cur, i + 1))
            evaluated = list(node.decorator_list)
            if not isinstance(node, ast.ClassDef):
                evaluated += node.args.defaults + [d for d in node.args.kw_defaults if d]
            for sub in evaluated:
                j = self.pos[sub]
                ranges.append((j, self.end[j]))
            cur = self.end[i]
        ranges.append((cur, stop))
        return sorted(r for r in ranges if r[0] < r[1])

    def as_walked(self, nodes) -> list:
        """``nodes`` (given in preorder) in the order ``ast.walk`` would meet
        them — breadth first — for the maps where the first or last binding
        of a name wins."""
        return sorted(nodes, key=lambda n: self.depth[self.pos[n]])


def _imports(index: ModuleIndex) -> tuple[dict[str, str], list[dict]]:
    """(alias -> canonical dotted prefix, raw import records) from every
    import in the file.

    ``import jax.numpy as jnp`` → jnp: jax.numpy; ``from jax import lax`` →
    lax: jax.lax; relative imports keep their module tail in the aliases
    (suffix matching in the rules absorbs the missing package prefix), and
    their level in the records, which the program graph resolves against the
    package layout on disk.
    """
    aliases: dict[str, str] = {}
    records: list[dict] = []
    for node in index.as_walked(index.of_type(ast.Import, ast.ImportFrom)):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
            records.append(
                {"kind": "import", "names": [[a.name, a.asname] for a in node.names]}
            )
            continue
        base = node.module or ""
        for a in node.names:
            if a.name != "*":
                aliases[a.asname or a.name] = f"{base}.{a.name}" if base else a.name
        records.append(
            {
                "kind": "from",
                "module": base,
                "level": node.level,
                "names": [[a.name, a.asname] for a in node.names if a.name != "*"],
            }
        )
    return aliases, records


def _parse_rule_list(raw: Optional[str]) -> set[str]:
    """Rule ids from the text after `disable=`, tolerating a trailing
    justification: each comma part contributes its first word, and parsing
    stops at the first word that isn't a rule-shaped token (`-- because...`)."""
    if raw is None:
        return {"all"}
    rules: set[str] = set()
    for part in raw.split(","):
        words = part.split()
        if not words or not _RULE_TOKEN_RE.match(words[0]):
            break
        rules.add(words[0])
    return rules or {"all"}


def _collect_suppressions(source: str):
    """Suppressions from real COMMENT tokens only — a docstring that merely
    *mentions* the syntax must not disable anything, so the raw-line regex
    approach is out; we tokenize."""
    per_line: dict[int, set[str]] = {}
    per_file: set[str] = set()
    if "graftlint" not in source:
        return per_line, per_file
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(source).readline))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return per_line, per_file  # ast.parse already vets the file upstream
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_RE.search(tok.string)
        if not m:
            continue
        rules = _parse_rule_list(m.group("rules"))
        if m.group("scope"):
            per_file |= rules
        else:
            line = tok.start[0]
            per_line.setdefault(line, set()).update(rules)
            if tok.line[: tok.start[1]].strip() == "":
                # comment-only line: also covers the next line (pylint-style)
                per_line.setdefault(line + 1, set()).update(rules)
    return per_line, per_file


class ModuleInfo:
    """One parsed file plus the derived maps every rule shares."""

    def __init__(self, path: str, rel_path: str, source: str):
        self.path = path
        self.rel_path = rel_path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.index = ModuleIndex(self.tree)
        self.aliases, self.import_records = _imports(self.index)
        self.line_suppressions, self.file_suppressions = _collect_suppressions(source)
        # module-level `NAME = "literal"` string constants (axis-name rule
        # resolves bare-Name axis arguments through this)
        self.str_constants: dict[str, str] = {}
        for node in self.tree.body:
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                self.str_constants[node.targets[0].id] = node.value.value
        self._callgraph = None
        self._resolved: dict[ast.AST, Optional[str]] = {}

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of an expression, with import aliases applied
        to the head segment (``jnp.zeros`` → ``jax.numpy.zeros``)."""
        if node in self._resolved:
            return self._resolved[node]
        d = _dotted(node)
        if d is not None:
            head, _, rest = d.partition(".")
            base = self.aliases.get(head, head)
            d = f"{base}.{rest}" if rest else base
        self._resolved[node] = d
        return d

    @property
    def callgraph(self):
        if self._callgraph is None:
            from .callgraph import CallGraph

            self._callgraph = CallGraph(self)
        return self._callgraph

    def is_suppressed(self, finding: Finding) -> bool:
        if {"all", finding.rule} & self.file_suppressions:
            return True
        rules = self.line_suppressions.get(finding.line, ())
        return "all" in rules or finding.rule in rules


@dataclasses.dataclass
class AnalysisContext:
    """Cross-file facts collected in a first pass before rules run."""

    axis_universe: set[str] = dataclasses.field(default_factory=set)
    # tensor → recorded PartitionSpec (JSON form) from a checkpoint
    # index.json, when the caller passed one (sharding-spec-drift input)
    ckpt_specs: dict[str, list] = dataclasses.field(default_factory=dict)
    # whole-program facts (program.ProgramGraph output), keyed by rel_path.
    # Filled from the per-module summaries in both modes; with cross-module
    # analysis off the maps only carry same-module entries.
    cross_module: bool = True
    # extra traced functions per module, beyond its own local roots:
    # rel_path -> {qualname: reason}
    cross_reached: dict = dataclasses.field(default_factory=dict)
    # rel_path -> {visible callable name (bare or dotted): donated positions}
    donor_aliases: dict = dataclasses.field(default_factory=dict)
    # rel_path -> {visible callable name: {"positions": [...], "where": ...}}
    # for helpers that STORE a parameter beyond the call (transitive-donation)
    escape_aliases: dict = dataclasses.field(default_factory=dict)
    # rel_path -> {visible callable name: chain} for functions that
    # transitively hit block_until_ready/effects_barrier (blocking rule)
    blocking_aliases: dict = dataclasses.field(default_factory=dict)
    # rel_path -> {visible callable name / Cls.method qualname: chain} for
    # functions whose RETURN VALUE is rank-divergent (taint.py sources
    # propagated through the program graph's divergence closure)
    divergent_aliases: dict = dataclasses.field(default_factory=dict)
    # rel_path -> {visible callable name / qualname: chain} for functions
    # that transitively issue a collective op (collective-divergence sinks)
    collective_aliases: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class AnalysisResult:
    findings: list[Finding]
    new_findings: list[Finding]  # findings minus the baseline
    files_analyzed: int
    duration_s: float
    suppressed: int
    cross_module: bool = True
    cache_hits: int = 0
    cache_misses: int = 0
    # baseline fingerprints that matched NO current finding: the grand-
    # fathered debt was paid (or the code moved), so the stale entry must
    # leave the baseline — "exits 0 on exact matches only"
    baseline_stale: list = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "files_analyzed": self.files_analyzed,
            "duration_s": round(self.duration_s, 3),
            "suppressed": self.suppressed,
            "baseline_filtered": len(self.findings) - len(self.new_findings),
            "baseline_stale": list(self.baseline_stale),
            "cross_module": self.cross_module,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "findings": [f.to_dict() for f in self.new_findings],
        }


_SKIP_DIRS = {"__pycache__", ".git", ".hg", "node_modules", "build", "dist"}


def discover_files(paths: Iterable[str]) -> list[str]:
    out: list[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs if d not in _SKIP_DIRS and not d.startswith(".")
                )
                out.extend(
                    os.path.join(root, f) for f in sorted(files) if f.endswith(".py")
                )
        else:
            raise FileNotFoundError(p)
    return out


# ---------------------------------------------------------------------------
# axis-universe collection (first pass; consumed by the axis-name rule)
# ---------------------------------------------------------------------------

# Fallback when the analyzed tree declares no mesh at all (e.g. a lone
# fixture file): the framework's canonical axes from utils/constants.py.
# Named so the harvester below does NOT match it ("AXES"/"MESH_AXIS"
# patterns) — the linter's own fallback must never feed the harvested
# universe when this package is itself the analysis target.
FALLBACK_AXIS_UNIVERSE = ("dp", "fsdp", "tp", "sp", "ep", "pp")


def _literal_strs(node: ast.AST) -> list[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        return [
            e.value
            for e in node.elts
            if isinstance(e, ast.Constant) and isinstance(e.value, str)
        ]
    return []


def collect_axes(module: ModuleInfo) -> list[str]:
    """The mesh axes one module declares.  Pure so the result can live in
    the per-module summary cache."""
    out: set[str] = set()
    for node in module.index.of_type(ast.Assign):
        # MESH_AXIS_DP = "dp" / ALL_MESH_AXES = (MESH_AXIS_DP, ...)
        tgt = node.targets[0]
        if len(node.targets) != 1 or not isinstance(tgt, ast.Name):
            continue
        if tgt.id.startswith("MESH_AXIS"):
            out.update(_literal_strs(node.value))
        elif "AXES" in tgt.id:
            out.update(_literal_strs(node.value))
            out.update(
                module.str_constants[e.id]
                for e in getattr(node.value, "elts", ())
                if isinstance(node.value, (ast.Tuple, ast.List))
                and isinstance(e, ast.Name)
                and e.id in module.str_constants
            )
    for node in module.index.of_type(ast.Call):
        leaf = (module.resolve(node.func) or "").rsplit(".", 1)[-1]
        # Mesh(devs, axis_names=(...)) / Mesh(devs, ("dp", ...))
        if leaf in ("Mesh", "AbstractMesh", "make_mesh"):
            for kw in node.keywords:
                if kw.arg == "axis_names":
                    out.update(_literal_strs(kw.value))
            if leaf in ("Mesh", "AbstractMesh") and len(node.args) >= 2:
                out.update(_literal_strs(node.args[1]))
            # make_mesh({"dp": 2, ...})
            if leaf == "make_mesh" and node.args and isinstance(node.args[0], ast.Dict):
                out.update(
                    k.value
                    for k in node.args[0].keys
                    if isinstance(k, ast.Constant) and isinstance(k.value, str)
                )
    return sorted(out)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> set[str]:
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return {e["fingerprint"] for e in data.get("findings", [])}


def write_baseline(findings: Sequence[Finding], path: str) -> None:
    data = {
        "comment": (
            "graftlint baseline: grandfathered findings (by line-free "
            "fingerprint). Regenerate with --write-baseline."
        ),
        "findings": [
            {
                "fingerprint": f.fingerprint(),
                "rule": f.rule,
                "path": f.path,
                "symbol": f.symbol,
                "message": f.message,
            }
            for f in findings
        ],
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


# ---------------------------------------------------------------------------
# SARIF (CI annotation format; tools/sarif_check.py validates the shape)
# ---------------------------------------------------------------------------

SARIF_VERSION = "2.1.0"
SARIF_SCHEMA = "https://json.schemastore.org/sarif-2.1.0.json"


def sarif_report(result: "AnalysisResult", rules: Sequence[Rule]) -> dict:
    """Minimal SARIF 2.1.0 document for ``result.new_findings``: one run,
    the rule table (with each rule's fix hint as its help text), and one
    result per finding with rule id, level, message and a physical region.
    The line-free fingerprint rides along as a partialFingerprint so SARIF
    consumers dedupe across line drift exactly like the baseline does."""
    by_id = {r.id: r for r in rules}
    rules_meta = []
    listed: set[str] = set()

    def add_rule(rule_id: str, description: str, hint: str) -> None:
        if rule_id in listed:
            return
        listed.add(rule_id)
        meta = {
            "id": rule_id,
            "shortDescription": {"text": description},
            "defaultConfiguration": {"level": "error"},
        }
        if hint:
            meta["help"] = {"text": hint}
        rules_meta.append(meta)

    for r in rules:
        add_rule(r.id, r.description, r.fix_hint)
    results = []
    for f in result.new_findings:
        rule = by_id.get(f.rule)
        if rule is None:
            # syntax-error findings carry no Rule instance
            add_rule(f.rule, "file failed to parse", "fix the syntax error")
        message = f.message
        hint = rule.fix_hint if rule is not None else ""
        if hint:
            message = f"{message} — fix: {hint}"
        results.append(
            {
                "ruleId": f.rule,
                "level": "error",
                "message": {"text": message},
                "locations": [
                    {
                        "physicalLocation": {
                            "artifactLocation": {
                                "uri": f.path.replace(os.sep, "/")
                            },
                            "region": {
                                "startLine": max(f.line, 1),
                                "startColumn": f.col + 1,
                            },
                        }
                    }
                ],
                "partialFingerprints": {"graftlint/v1": f.fingerprint()},
            }
        )
    return {
        "$schema": SARIF_SCHEMA,
        "version": SARIF_VERSION,
        "runs": [
            {
                "tool": {
                    "driver": {
                        "name": "graftlint",
                        "version": ANALYSIS_VERSION,
                        "informationUri": "docs/graftlint.md",
                        "rules": rules_meta,
                    }
                },
                "results": results,
            }
        ],
    }


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def load_ckpt_specs(path: str) -> dict[str, list]:
    """Recorded {tensor: PartitionSpec-as-JSON} from a sharded checkpoint.

    ``path`` may be one ``*.index.json`` file or a checkpoint directory, in
    which case every ``*.index.json`` inside contributes.  Tensors whose
    entry predates the spec record (older checkpoints) are skipped.
    """
    index_files = []
    if os.path.isdir(path):
        index_files = [
            os.path.join(path, f)
            for f in sorted(os.listdir(path))
            if f.endswith(".index.json")
        ]
        if not index_files:
            raise FileNotFoundError(f"no *.index.json files under {path}")
    else:
        index_files = [path]
    specs: dict[str, list] = {}
    for f in index_files:
        with open(f, encoding="utf-8") as fh:
            data = json.load(fh)
        for tensor, entry in data.get("tensors", {}).items():
            if isinstance(entry, dict) and "spec" in entry:
                specs[tensor] = entry["spec"]
    return specs


@dataclasses.dataclass
class _FileRecord:
    """One discovered file through the pipeline: parsed eagerly on a cache
    miss, replayed from its cached summary otherwise."""

    path: str
    rel_path: str
    content_hash: str
    source: str
    module: Optional[ModuleInfo]
    summary: object  # program.ModuleSummary
    cache_entry: Optional[dict]


def _module_env_hash(rel: str, rule_ids: Sequence[str], ctx: AnalysisContext, ckpt_hash: str) -> str:
    """Everything OUTSIDE a module's own text that its findings depend on.
    The findings cache is keyed on (content hash, this) — so editing file A
    re-analyzes A via the content hash, and re-analyzes B only when A's edit
    actually changed what B sees (its cross-module reached set, the axis
    universe, visible donors/escapers/blockers, the checkpoint specs)."""
    payload = {
        "version": ANALYSIS_VERSION,
        "rules": list(rule_ids),
        "cross": ctx.cross_module,
        "axes": sorted(ctx.axis_universe),
        "reached": sorted(ctx.cross_reached.get(rel, {}).items()),
        "donors": sorted(
            (k, list(v)) for k, v in ctx.donor_aliases.get(rel, {}).items()
        ),
        "escapes": sorted(
            (k, sorted(v["positions"]), v["where"])
            for k, v in ctx.escape_aliases.get(rel, {}).items()
        ),
        "blocking": sorted(ctx.blocking_aliases.get(rel, {}).items()),
        "divergent": sorted(ctx.divergent_aliases.get(rel, {}).items()),
        "collective": sorted(ctx.collective_aliases.get(rel, {}).items()),
        "ckpt": ckpt_hash,
    }
    blob = json.dumps(payload, sort_keys=True).encode("utf-8")
    return hashlib.sha256(blob).hexdigest()[:24]


def run_analysis(
    paths: Sequence[str],
    rules: Optional[Sequence[Rule]] = None,
    baseline: Optional[set[str]] = None,
    ckpt_index: Optional[Union[str, dict]] = None,
    cross_module: bool = True,
    cache_dir: Optional[str] = None,
) -> AnalysisResult:
    from .cache import AnalysisCache
    from .program import ModuleSummary, ProgramGraph, extract_summary

    if rules is None:
        from .rules import ALL_RULES

        rules = [cls() for cls in ALL_RULES]
    rule_ids = sorted(r.id for r in rules)
    t0 = time.monotonic()
    files = discover_files(paths)
    cwd = os.getcwd()
    ctx = AnalysisContext(cross_module=cross_module)
    if ckpt_index:
        # a dict is an already-loaded {tensor: spec} mapping (the CLI
        # validates + loads once and hands it over); a str is a path
        ctx.ckpt_specs = (
            dict(ckpt_index)
            if isinstance(ckpt_index, dict)
            else load_ckpt_specs(ckpt_index)
        )
    # the branch namespace must come from the *analyzed* tree, which need
    # not be the process CWD (out-of-tree `graftlint /path/to/checkout`)
    analysis_root = os.path.dirname(files[0]) if files else cwd
    cache = AnalysisCache(cache_dir, root=analysis_root) if cache_dir else None

    # -- pass 1: summaries (cache-replayed or freshly extracted) ------------
    records: list[_FileRecord] = []
    for path in files:
        rel = os.path.relpath(path, cwd) if os.path.isabs(path) else path
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except UnicodeDecodeError as e:
            records.append(
                _FileRecord(
                    path, rel, "", "", None,
                    ModuleSummary(error=f"cannot parse: {e}"), None,
                )
            )
            continue
        content_hash = hashlib.sha256(source.encode("utf-8")).hexdigest()
        entry = cache.load(rel, content_hash) if cache else None
        if entry is not None:
            summary = ModuleSummary.from_dict(entry["summary"])
            records.append(
                _FileRecord(path, rel, content_hash, source, None, summary, entry)
            )
            continue
        try:
            module = ModuleInfo(path, rel, source)
        except SyntaxError as e:
            lineno = getattr(e, "lineno", 0) or 0
            summary = ModuleSummary(error=f"cannot parse: {e}", error_line=lineno)
            module = None
        else:
            summary = extract_summary(module)
        entry = {"summary": summary.to_dict(), "results": {}} if cache else None
        records.append(
            _FileRecord(path, rel, content_hash, source, module, summary, entry)
        )

    # -- pass 2: cross-file facts (axis universe + whole-program graph) -----
    for r in records:
        ctx.axis_universe.update(r.summary.axes)
    if not ctx.axis_universe:
        ctx.axis_universe = set(FALLBACK_AXIS_UNIVERSE)
    program = ProgramGraph(records, cross=cross_module)
    ctx.cross_reached = program.cross_reached
    ctx.donor_aliases = program.donor_aliases
    ctx.escape_aliases = program.escape_aliases
    ctx.blocking_aliases = program.blocking_aliases
    ctx.divergent_aliases = program.divergent_aliases
    ctx.collective_aliases = program.collective_aliases

    ckpt_hash = (
        hashlib.sha256(
            json.dumps(ctx.ckpt_specs, sort_keys=True).encode("utf-8")
        ).hexdigest()
        if ctx.ckpt_specs
        else ""
    )

    # -- pass 3: rules (per module, findings cache-keyed on content + env) --
    findings: list[Finding] = []
    suppressed = 0
    cache_hits = cache_misses = 0
    for r in records:
        if r.summary.error:
            findings.append(
                Finding("syntax-error", r.rel_path, r.summary.error_line, 0, r.summary.error)
            )
            continue
        env = _module_env_hash(r.rel_path, rule_ids, ctx, ckpt_hash)
        cached = r.cache_entry["results"].get(env) if r.cache_entry else None
        if cached is not None:
            for fd in cached["findings"]:
                findings.append(
                    Finding(
                        fd["rule"], fd["path"], fd["line"], fd["col"],
                        fd["message"], fd.get("symbol", ""),
                    )
                )
            suppressed += cached["suppressed"]
            cache_hits += 1
            results = r.cache_entry["results"]
            if next(reversed(results)) != env:
                # LRU refresh: move the env just used to most-recent, so the
                # eviction below drops stale variants, not the busiest one
                results[env] = results.pop(env)
                cache.store(r.rel_path, r.content_hash, r.cache_entry)
            continue
        module = r.module
        if module is None:  # cached summary but stale/absent findings: parse
            # the pass-1 source (NOT a re-read — the file may have changed
            # since, and findings are stored under the pass-1 content hash)
            module = ModuleInfo(r.path, r.rel_path, r.source)
        # inject the whole-program reachability before any rule looks at it
        module.callgraph.reached.update(ctx.cross_reached.get(r.rel_path, {}))
        mod_findings: list[Finding] = []
        mod_suppressed = 0
        for rule in rules:
            for f in rule.check(module, ctx):
                if module.is_suppressed(f):
                    mod_suppressed += 1
                else:
                    mod_findings.append(f)
        findings.extend(mod_findings)
        suppressed += mod_suppressed
        if cache is not None and r.cache_entry is not None:
            cache_misses += 1
            results = r.cache_entry["results"]
            results[env] = {
                "findings": [dataclasses.asdict(f) for f in mod_findings],
                "suppressed": mod_suppressed,
            }
            while len(results) > 8:  # drop the least-recently-used variants
                results.pop(next(iter(results)))
            cache.store(r.rel_path, r.content_hash, r.cache_entry)

    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule, f.message))
    stale: list[str] = []
    if baseline:
        prints = {f.fingerprint() for f in findings}
        new = [f for f in findings if f.fingerprint() not in baseline]
        stale = sorted(baseline - prints)
    else:
        new = list(findings)
    return AnalysisResult(
        findings=findings,
        new_findings=new,
        files_analyzed=len(files),
        duration_s=time.monotonic() - t0,
        suppressed=suppressed,
        cross_module=cross_module,
        cache_hits=cache_hits,
        cache_misses=cache_misses,
        baseline_stale=stale,
    )

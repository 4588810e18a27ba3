"""donation-reuse: reading a buffer after handing it to ``donate_argnums``.

Donation aliases the input buffer to an output — after the call the python
reference points at freed/overwritten device memory.  JAX only *warns* (and
only sometimes), the read returns garbage or raises much later.  The rule
tracks, per function body and in execution order, names passed at donated
positions of a known donating callable; any later read before a rebind is
flagged.  Donating callables are resolved whole-program: one defined in
another module and imported (``from .opt import apply_grads``) or called
through a module alias (``opt.apply_grads(state)``) counts the same as a
local ``g = jax.jit(f, donate_argnums=...)``.

Loop bodies get a second pass: a read that *precedes* the donation in source
order is fine on iteration 1 but reads a dead buffer on iteration 2 unless
the name was rebound in between — the scanner visits each loop body twice
(with the loop-carried donation state) and deduplicates against the linear
findings, so straight-line reuse is reported once and loop-carried reuse is
caught at all.
"""

from __future__ import annotations

import ast

from ..callgraph import donating_callables, dotted_name
from ..engine import Finding, Rule


def visible_donors(module, ctx) -> dict[str, list[int]]:
    """Donating callables this module can name: its own (`g = jax.jit(f,
    donate_argnums=...)` / decorated defs) merged with what the program
    graph resolved through imports — `from .opt import apply_grads` and
    `opt.apply_grads` both land here when `apply_grads` donates."""
    donors = dict(ctx.donor_aliases.get(module.rel_path, {}))
    for name, pos in donating_callables(module).items():
        donors.setdefault(name, pos)
    return donors


def calls_a_donor(module, fn_node, donors) -> bool:
    """Whether a scope's own calls name a donating callable: without one
    nothing in it can be donated."""
    return any(
        (fn.id if isinstance(fn, ast.Name) else dotted_name(fn)) in donors
        for fn in (c.func for c in module.index.own(fn_node, ast.Call))
    )


class _LinearScanner(ast.NodeVisitor):
    """Emit (use/store/donate) events in approximate execution order; the
    default field order of Assign (targets before value) is the one place
    AST order disagrees with evaluation order, so it's special-cased."""

    def __init__(self, rule, module, fn_qual, donors):
        self.rule = rule
        self.module = module
        self.fn_qual = fn_qual
        self.donors = donors
        self.dead: dict[str, tuple[str, int]] = {}  # name -> (donor, lineno)
        self.findings: list[Finding] = []

    def visit_Assign(self, node):
        self.visit(node.value)
        for t in node.targets:
            self.visit(t)

    def visit_AugAssign(self, node):
        self.visit(node.value)
        # target is read-then-write: the read part sees the donated state
        if isinstance(node.target, ast.Name):
            self._use(node.target, node.target.id)
            self.dead.pop(node.target.id, None)
        else:
            self.visit(node.target)

    def visit_AnnAssign(self, node):
        if node.value:
            self.visit(node.value)
        self.visit(node.target)

    def visit_Name(self, node):
        if isinstance(node.ctx, ast.Load):
            self._use(node, node.id)
        else:  # Store/Del rebinds the name away from the dead buffer
            self.dead.pop(node.id, None)

    def visit_Call(self, node):
        fn = node.func
        donor = None
        if isinstance(fn, ast.Name) and fn.id in self.donors:
            donor = fn.id
        elif isinstance(fn, ast.Attribute):
            d = dotted_name(fn)
            if d in self.donors:
                donor = d
        if donor is not None:
            for arg in node.args:
                self.visit(arg)
            for kw in node.keywords:
                self.visit(kw.value)
            for pos in self.donors[donor]:
                if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                    self.dead[node.args[pos].id] = (donor, node.lineno)
        else:
            self.generic_visit(node)

    def visit_FunctionDef(self, node):
        pass  # nested defs: separate scope, scanned separately

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef

    # -- loop bodies: second pass ------------------------------------------
    # A read BEFORE the donation in source order is fine on iteration 1 but
    # reads freed memory on iteration 2 unless the name was rebound; walking
    # the body twice with the carried `dead` state is exactly iteration-2
    # semantics.  Duplicate straight-line findings (same line, re-reported by
    # the second pass) are dropped in DonationReuse.check.
    def visit_For(self, node):
        self.visit(node.iter)
        self.visit(node.target)
        for _ in range(2):
            for stmt in node.body:
                self.visit(stmt)
            self.visit(node.target)  # re-bound from the iterator each pass
        for stmt in node.orelse:
            self.visit(stmt)

    visit_AsyncFor = visit_For

    def visit_While(self, node):
        for _ in range(2):
            self.visit(node.test)
            for stmt in node.body:
                self.visit(stmt)
        for stmt in node.orelse:
            self.visit(stmt)

    def _use(self, node, name):
        if name in self.dead:
            donor, _line = self.dead.pop(name)  # report once per donation
            self.findings.append(
                Finding(
                    self.rule.id,
                    self.module.rel_path,
                    node.lineno,
                    node.col_offset,
                    # no line numbers in the message: it feeds the baseline
                    # fingerprint, which must survive unrelated line drift
                    f"'{name}' is read after being donated to '{donor}' "
                    "(donate_argnums aliases its buffer to an output; "
                    "rebind the result or drop the donation)",
                    symbol=self.fn_qual,
                )
            )


class DonationReuse(Rule):
    id = "donation-reuse"
    description = "buffer read after appearing at a donate_argnums position"
    kind = "reachability"
    fix_hint = (
        "rebind the result over the donated name (x = step(x)) so the stale "
        "buffer is unreachable, or drop donate_argnums for this argument"
    )

    def check(self, module, ctx):
        donors = visible_donors(module, ctx)
        if not donors:
            return []
        findings = []
        for info in module.callgraph.functions.values():
            if not calls_a_donor(module, info.node, donors):
                continue
            scanner = _LinearScanner(self, module, info.qualname, donors)
            for stmt in info.node.body:
                scanner.visit(stmt)
            findings.extend(scanner.findings)
        # module top level
        scanner = _LinearScanner(self, module, "<module>", donors)
        for stmt in module.tree.body:
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                scanner.visit(stmt)
        findings.extend(scanner.findings)
        # the loop second pass re-reports straight-line reuse at the same
        # location; keep the first occurrence only
        seen: set = set()
        unique = []
        for f in findings:
            if f not in seen:
                seen.add(f)
                unique.append(f)
        return unique

"""transitive-donation: a buffer is stashed by a helper, then donated.

``donation-reuse`` catches the *local* reads of a donated name.  What it
cannot see is an alias that **escaped** before the donation: a helper —
typically in another module — that stores its argument (appends it to a
cache, assigns it to ``self.something`` or a global) keeps a reference to
the buffer that outlives the call.  Donating the buffer afterwards leaves
that stored alias pointing at freed/overwritten device memory, even though
the local name was correctly rebound:

```python
# utils/stash.py
_HISTORY = []
def remember(x):
    _HISTORY.append(x)          # alias escapes into module state

# ops/train.py
from ..utils.stash import remember
g = jax.jit(f, donate_argnums=(0,))
def train(x):
    remember(x)                 # x now aliased by utils._HISTORY
    x = g(x)                    # BAD: donation frees the stored alias
    return x
```

Which helpers store which parameters comes from the whole-program graph
(``program.escaping_params`` per function, resolved through imports), so
the helper can live anywhere in the analyzed tree.  Donors are the same
whole-program set ``donation-reuse`` uses.
"""

from __future__ import annotations

import ast

from ..callgraph import dotted_name
from ..engine import Finding, Rule
from .donation import calls_a_donor, visible_donors


class _EscapeScanner(ast.NodeVisitor):
    """Track, in execution order: names whose buffer escaped into a storing
    helper, and donation events.  A donation of an escaped name fires."""

    def __init__(self, rule, module, fn_qual, donors, escapers):
        self.rule = rule
        self.module = module
        self.fn_qual = fn_qual
        self.donors = donors
        self.escapers = escapers  # visible name -> {"positions", "where"}
        self.escaped: dict[str, tuple[str, str]] = {}  # name -> (helper, where)
        self.findings: list[Finding] = []

    def visit_Assign(self, node):
        self.visit(node.value)
        for t in node.targets:
            self.visit(t)

    # AnnAssign/AugAssign default field order is target-first; evaluation is
    # value-first — without these, `x: Array = g(x)` would clear the escaped
    # state before the donor check sees the donation
    def visit_AnnAssign(self, node):
        if node.value is not None:
            self.visit(node.value)
        self.visit(node.target)

    def visit_AugAssign(self, node):
        self.visit(node.value)
        self.visit(node.target)

    def visit_Name(self, node):
        # rebinding a name detaches it from the OLD buffer; the stored alias
        # still exists but donating the NEW buffer is unrelated to it
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            self.escaped.pop(node.id, None)

    def visit_FunctionDef(self, node):
        pass  # nested defs scan as their own functions

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef

    def _callee_name(self, fn) -> str:
        if isinstance(fn, ast.Name):
            return fn.id
        d = dotted_name(fn)
        return d or ""

    def visit_Call(self, node):
        for arg in node.args:
            self.visit(arg)
        for kw in node.keywords:
            self.visit(kw.value)
        name = self._callee_name(node.func)
        esc = self.escapers.get(name)
        if esc:
            for pos in esc["positions"]:
                if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                    self.escaped.setdefault(
                        node.args[pos].id, (name, esc["where"])
                    )
        donated = self.donors.get(name)
        if donated:
            for pos in donated:
                if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                    buf = node.args[pos].id
                    if buf in self.escaped:
                        helper, where = self.escaped.pop(buf)
                        self.findings.append(
                            Finding(
                                self.rule.id,
                                self.module.rel_path,
                                node.lineno,
                                node.col_offset,
                                f"'{buf}' was stored by '{helper}' ({where}) "
                                f"before being donated to '{name}' — the "
                                "stored alias dangles once donation frees the "
                                "buffer; copy before stashing or drop the "
                                "donation",
                                symbol=self.fn_qual,
                            )
                        )


class TransitiveDonation(Rule):
    id = "transitive-donation"
    description = (
        "buffer stored by a helper (possibly in another module), then donated "
        "— the stored alias outlives the donation"
    )
    kind = "reachability"
    fix_hint = (
        "hand the helper a copy (helper(x.copy())) so the stored alias owns "
        "its buffer, or drop the donation"
    )

    def check(self, module, ctx):
        donors = visible_donors(module, ctx)
        escapers = ctx.escape_aliases.get(module.rel_path, {})
        if not donors or not escapers:
            return []
        findings: list[Finding] = []
        for info in module.callgraph.functions.values():
            if not calls_a_donor(module, info.node, donors):
                continue
            scanner = _EscapeScanner(self, module, info.qualname, donors, escapers)
            for stmt in info.node.body:
                scanner.visit(stmt)
            findings.extend(scanner.findings)
        scanner = _EscapeScanner(self, module, "<module>", donors, escapers)
        for stmt in module.tree.body:
            if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                scanner.visit(stmt)
        findings.extend(scanner.findings)
        return findings

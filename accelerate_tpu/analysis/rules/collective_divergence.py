"""collective-divergence: a collective op reachable only under rank-divergent
control flow.

The mesh's lockstep contract (docs/elastic.md): every rank issues the same
collective sequence, in the same order, or the mesh hangs.  This rule runs
the rank-divergence taint engine (``analysis/taint.py``) over each function
— seeded with the whole-program facts from ``ctx.divergent_aliases``
(functions proven to RETURN rank-divergent state) and
``ctx.collective_aliases`` (functions that transitively ISSUE a collective)
— and flags three shapes:

* **branch mismatch** — sibling branches of a rank-divergent conditional
  issue different collective sequences (including the degenerate and most
  common case: a collective on one side, nothing on the other — only the
  ranks taking that side enter it);
* **early exit** — a ``return``/``raise`` on a rank-divergent branch while
  a collective still follows in the function: the exiting ranks never reach
  it, the remaining ranks block in it forever;
* **divergent loop** — a collective inside a loop whose condition (or
  iterable) is rank-divergent: trip counts differ per rank, so the
  collective sequence does too.

The sanctioned fix shapes the rule recognizes (no suppression needed):
deriving the guard from an all-ranks merge (``gather_object`` /
``agree_*`` kill taint), and conjoining the branch with a single-process
world-size test (``not _multi_process()``, ``num_processes == 1``) — the
PR-13 serving-signal gate — which makes the branch unreachable on any
multi-process run.
"""

from __future__ import annotations

import ast

from ..engine import Finding, Rule
from ..engine import _SCOPES as _NESTED_DEFS
from ..taint import (
    FunctionTaint,
    callee_names,
    collective_sink,
    rank_local_by_design,
    single_process_conjunct,
)


class _Tokens:
    """The collective tokens of one function's statements — a direct sink,
    or a call into a function the program graph proved collective-bearing —
    each statement's computed once, from its calls in the module index."""

    def __init__(self, module, coll_map, self_prefix):
        self.module, self.coll_map, self.self_prefix = module, coll_map, self_prefix
        self.memo: dict[ast.stmt, list] = {}

    def call(self, call):
        tok = collective_sink(call, self.module)
        if tok is not None:
            return tok
        for cand in callee_names(call.func, self.self_prefix):
            if cand in self.coll_map:
                return cand
        return None

    def expr(self, node):
        index = self.module.index
        hits = [(c, t) for c in index.walk(node, ast.Call) if (t := self.call(c))]
        hits.sort(key=lambda h: index.depth[index.pos[h[0]]])  # ast.walk's order
        return [t for _, t in hits]

    def stmts(self, stmts):
        """Tokens issued by a statement list, skipping nested defs (their
        own call-graph nodes) and single-process-guarded branches
        (unreachable on a multi-process run)."""
        return [t for stmt in stmts for t in self.stmt(stmt)]

    def stmt(self, stmt):
        out = self.memo.get(stmt)
        if out is not None:
            return out
        out = []
        if isinstance(stmt, _NESTED_DEFS):
            pass
        elif isinstance(stmt, ast.If) and single_process_conjunct(stmt.test):
            out = self.expr(stmt.test) + self.stmts(stmt.orelse)
        else:
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.stmt):
                    out += self.stmt(child)
                elif isinstance(child, ast.ExceptHandler):
                    if child.type is not None:
                        out += self.expr(child.type)
                    out += self.stmts(child.body)
                elif isinstance(child, ast.withitem):
                    out += self.expr(child.context_expr)
                elif isinstance(child, ast.match_case):
                    out += self.stmts(child.body)
                elif isinstance(child, ast.expr):
                    out += self.expr(child)
        self.memo[stmt] = out
        return out


class CollectiveDivergence(Rule):
    id = "collective-divergence"
    kind = "reachability"
    description = (
        "collective op (gather/broadcast/barrier/load_state/fleet resize) "
        "guarded by rank-divergent state — only some ranks enter it and "
        "the mesh deadlocks"
    )
    fix_hint = (
        "derive the guard from an all-ranks merge (gather_object + agree_*) "
        "so every rank sees the same value, or gate the branch single-"
        "process (num_processes == 1 / not _multi_process())"
    )

    def check(self, module, ctx) -> list[Finding]:
        if rank_local_by_design(module.rel_path):
            # the postmortem-writer exemption (taint.RANK_LOCAL_MODULE_
            # SUFFIXES): rank identity / wall clock / fs probes here are the
            # point, so the divergence scan is waived — and the INVERTED
            # contract is enforced instead: a module that may run while the
            # mesh is deadlocked must never bear a collective at all.
            return self._check_rank_local_contract(module)
        findings: list[Finding] = []
        div_map = ctx.divergent_aliases.get(module.rel_path, {})
        coll_map = ctx.collective_aliases.get(module.rel_path, {})
        for info in module.callgraph.functions.values():
            self_prefix = (
                info.qualname.rsplit(".", 1)[0]
                if "." in info.qualname
                else None
            )
            tokens = _Tokens(module, coll_map, self_prefix)
            if not any(map(tokens.call, module.index.own(info.node, ast.Call))):
                continue  # no collective to branch, loop or exit around
            taint = FunctionTaint(
                module, info.node, known=div_map, self_prefix=self_prefix
            )
            seen: set[tuple[int, str]] = set()

            def fire(node, kind, message):
                key = (node.lineno, kind)
                if key in seen:
                    return
                seen.add(key)
                findings.append(
                    Finding(
                        self.id,
                        module.rel_path,
                        node.lineno,
                        node.col_offset,
                        message,
                        symbol=info.qualname,
                    )
                )

            self._scan(info.node.body, [], tokens, taint, fire)
        return findings

    def _check_rank_local_contract(self, module) -> list[Finding]:
        """The no-collective contract for rank-local-by-design modules: every
        collective sink anywhere in the module (function bodies AND module
        level) is a finding, unconditionally — divergence analysis does not
        apply because the module must not collectivize at all."""
        findings: list[Finding] = []
        for node in module.index.of_type(ast.Call):
            tok = collective_sink(node, module)
            if tok is None:
                continue
            findings.append(
                Finding(
                    self.id,
                    module.rel_path,
                    node.lineno,
                    node.col_offset,
                    f"collective ({tok}) in a rank-local-by-design module: "
                    "the postmortem/watchdog path may run while the mesh is "
                    "deadlocked — coordinating over the stalled mesh hangs "
                    "the postmortem too.  Move the collective out of this "
                    "module; the rank-local exemption is conditional on "
                    "bearing none",
                )
            )
        return findings

    def _exits(self, stmts):
        """Top-to-bottom ``return``/``raise`` statements inside a branch (any
        nesting short of nested defs) — the exits that abandon the rest of
        the function for the ranks that took this branch."""
        out = []
        for stmt in stmts:
            if isinstance(stmt, _NESTED_DEFS):
                continue
            if isinstance(stmt, (ast.Return, ast.Raise)):
                out.append(stmt)
                continue
            if isinstance(stmt, ast.If) and single_process_conjunct(stmt.test):
                out += self._exits(stmt.orelse)
                continue
            for child in ast.iter_child_nodes(stmt):
                if isinstance(child, ast.stmt):
                    out += self._exits([child])
                elif isinstance(child, ast.ExceptHandler):
                    out += self._exits(child.body)
                elif isinstance(child, ast.match_case):
                    out += self._exits(child.body)
        return out

    # -- the statement scan ----------------------------------------------------
    def _scan(self, stmts, tail, tokens, taint, fire):
        """``tail`` carries the collective tokens that follow the current
        block at every enclosing level — what an early exit would skip."""
        suffix = [tail]  # suffix[k]: the tokens of stmts[-k:], then tail
        for stmt in reversed(stmts):
            suffix.append(tokens.stmt(stmt) + suffix[-1])
        for idx, stmt in enumerate(stmts):
            if isinstance(stmt, _NESTED_DEFS):
                continue
            after = suffix[len(stmts) - idx - 1]
            if isinstance(stmt, ast.If):
                if single_process_conjunct(stmt.test):
                    # the branch never executes multi-process: nothing inside
                    # it can diverge a mesh (the sanctioned PR-13 gate)
                    self._scan(stmt.orelse, after, tokens, taint, fire)
                    continue
                if taint.expr_tainted(stmt.test):
                    desc = taint.describe(stmt.test)
                    body_toks = tokens.stmts(stmt.body)
                    else_toks = tokens.stmts(stmt.orelse)
                    if sorted(body_toks) != sorted(else_toks):
                        fire(
                            stmt,
                            "branch",
                            "collective sequence diverges across ranks: "
                            f"branch on rank-divergent {desc} issues "
                            f"[{', '.join(sorted(body_toks)) or 'nothing'}] vs "
                            f"[{', '.join(sorted(else_toks)) or 'nothing'}] "
                            "on the sibling path — only some ranks enter, "
                            "the mesh deadlocks",
                        )
                    if after:
                        for branch in (stmt.body, stmt.orelse):
                            for exit_stmt in self._exits(branch):
                                word = (
                                    "return"
                                    if isinstance(exit_stmt, ast.Return)
                                    else "raise"
                                )
                                fire(
                                    exit_stmt,
                                    "exit",
                                    f"early {word} on a rank-divergent "
                                    f"branch ({desc}) skips the later "
                                    f"collective ({after[0]}) — exiting "
                                    "ranks never reach it, the rest block "
                                    "in it forever",
                                )
                self._scan(stmt.body, after, tokens, taint, fire)
                self._scan(stmt.orelse, after, tokens, taint, fire)
            elif isinstance(stmt, ast.While):
                if not single_process_conjunct(stmt.test) and taint.expr_tainted(
                    stmt.test
                ):
                    toks = tokens.stmts(stmt.body)
                    if toks:
                        fire(
                            stmt,
                            "loop",
                            f"collective ({toks[0]}) inside a loop whose "
                            "condition is rank-divergent "
                            f"({taint.describe(stmt.test)}) — trip counts "
                            "differ per rank, so the collective sequence "
                            "does too",
                        )
                self._scan(stmt.body, after, tokens, taint, fire)
                self._scan(stmt.orelse, after, tokens, taint, fire)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                if taint.expr_tainted(stmt.iter):
                    toks = tokens.stmts(stmt.body)
                    if toks:
                        fire(
                            stmt,
                            "loop",
                            f"collective ({toks[0]}) inside a loop over a "
                            "rank-divergent iterable "
                            f"({taint.describe(stmt.iter)}) — trip counts "
                            "differ per rank, so the collective sequence "
                            "does too",
                        )
                self._scan(stmt.body, after, tokens, taint, fire)
                self._scan(stmt.orelse, after, tokens, taint, fire)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                self._scan(stmt.body, after, tokens, taint, fire)
            elif isinstance(stmt, ast.Try) or (
                hasattr(ast, "TryStar") and isinstance(stmt, ast.TryStar)
            ):
                self._scan(stmt.body, after, tokens, taint, fire)
                for h in stmt.handlers:
                    self._scan(h.body, after, tokens, taint, fire)
                self._scan(stmt.orelse, after, tokens, taint, fire)
                self._scan(stmt.finalbody, after, tokens, taint, fire)
            elif isinstance(stmt, ast.Match):
                for case in stmt.cases:
                    self._scan(case.body, after, tokens, taint, fire)

"""blocking-in-hot-loop: per-iteration host synchronization in step loops.

``x.block_until_ready()`` inside a training loop serializes host and device
— the async dispatch queue (the thing hiding all python overhead between
step launches) drains to depth 0 every iteration.  Legitimate uses are
profiling/benchmark timers, so calls under an ``if`` whose condition
mentions profiling/debug knobs, or inside functions whose name says
bench/profile/warmup, are exempt.

Direct calls are matched syntactically; *indirect* ones come from the
whole-program blocking closure (``program.ProgramGraph``): a loop body
calling ``utils.sync_all(x)`` where ``sync_all`` — in another module —
unconditionally hits ``block_until_ready`` is the same per-step sync, and
is flagged with the chain that proves it.

**Profiler-session extension**: ``jax.profiler.start_trace``/``stop_trace``
inside a step loop is *worse* than a bare sync — each iteration opens a
global trace session, blocks the pipeline, and writes a dump to disk.  A
plain profiling-knob guard (``if profiling:``) does NOT exempt it: the knob
turns every-step tracing on, which is exactly the hazard.  What exempts it
is **sampled-cadence evidence** in a guarding condition — a modulus test
(``step % profile_every_n == 0``) or a cadence-named predicate
(``should_sample``/``every_n``/...) — the pattern the telemetry profiler's
``profile_every_n`` knob implements (docs/telemetry.md).
"""

from __future__ import annotations

import ast
import re

from ..callgraph import dotted_name
from ..engine import Finding, GUARD_NAME_RE, Rule, is_guard_expr

_BLOCKING_LEAVES = {"block_until_ready", "effects_barrier"}

# per-iteration trace sessions: flagged in loops unless a guarding
# condition carries sampled-cadence evidence (a knob guard alone is not it)
_PROFILER_SESSION_LEAVES = {"start_trace", "stop_trace"}

_CADENCE_NAME_RE = re.compile(
    r"every_n|_every\b|every_|sampl|cadence|interval",
    re.IGNORECASE,
)


def is_cadence_expr(index, test: ast.AST) -> bool:
    """True when a guard condition shows sampled-cadence evidence: a
    modulus test (``i % n == 0``) or a cadence-named knob/predicate."""
    for node in index.walk(test, ast.BinOp, ast.Name, ast.Attribute):
        if isinstance(node, ast.BinOp):
            if isinstance(node.op, ast.Mod):
                return True
        elif _CADENCE_NAME_RE.search(node.id if isinstance(node, ast.Name) else node.attr):
            return True
    return False


class _LoopVisitor(ast.NodeVisitor):
    def __init__(self, rule, module, fn_qual, blocking_callables):
        self.rule = rule
        self.module = module
        self.fn_qual = fn_qual
        self.blocking_callables = blocking_callables  # visible name -> chain
        self.loop_depth = 0
        self.guard_depth = 0
        self.cadence_depth = 0
        self.findings: list[Finding] = []

    def visit_For(self, node):
        # the iterable expression evaluates once, outside the hot body
        self.visit(node.iter)
        self.loop_depth += 1
        for stmt in node.body:
            self.visit(stmt)
        self.loop_depth -= 1
        for stmt in node.orelse:
            self.visit(stmt)

    def visit_While(self, node):
        # unlike For.iter, the While test re-evaluates EVERY iteration — a
        # blocking call in the condition is a per-step sync too
        self.loop_depth += 1
        self.visit(node.test)
        for stmt in node.body:
            self.visit(stmt)
        self.loop_depth -= 1
        for stmt in node.orelse:
            self.visit(stmt)

    def visit_If(self, node):
        self.visit(node.test)
        guarded = is_guard_expr(self.module.index, node.test)
        cadenced = is_cadence_expr(self.module.index, node.test)
        self.guard_depth += guarded
        self.cadence_depth += cadenced
        for stmt in node.body:
            self.visit(stmt)
        self.guard_depth -= guarded
        self.cadence_depth -= cadenced
        for stmt in node.orelse:
            self.visit(stmt)

    def visit_Call(self, node):
        if self.loop_depth > 0 and self.cadence_depth == 0:
            # profiler sessions first: a profiling-knob guard (which exempts
            # plain syncs below) deliberately does NOT exempt these — an
            # `if profiling:` knob is what turns the every-step session ON
            fn = node.func
            leaf_attr = fn.attr if isinstance(fn, ast.Attribute) else None
            resolved_name = self.module.resolve(fn) or ""
            resolved_leaf = resolved_name.rsplit(".", 1)[-1]
            if (
                leaf_attr in _PROFILER_SESSION_LEAVES
                or resolved_leaf in _PROFILER_SESSION_LEAVES
            ):
                self.findings.append(
                    Finding(
                        self.rule.id,
                        self.module.rel_path,
                        node.lineno,
                        node.col_offset,
                        f"{leaf_attr or resolved_leaf}() inside a loop opens a "
                        "profiler trace session every iteration — sample it "
                        "(e.g. `if step % profile_every_n == 0:`) so only the "
                        "sampled step pays the sync+dump",
                        symbol=self.fn_qual,
                    )
                )
        if self.loop_depth > 0 and self.guard_depth == 0:
            fn = node.func
            resolved = self.module.resolve(fn) or ""
            leaf = resolved.rsplit(".", 1)[-1]
            is_blocking = leaf in _BLOCKING_LEAVES or (
                isinstance(fn, ast.Attribute) and fn.attr in _BLOCKING_LEAVES
            )
            if is_blocking:
                self.findings.append(
                    Finding(
                        self.rule.id,
                        self.module.rel_path,
                        node.lineno,
                        node.col_offset,
                        f"{leaf}() inside a loop drains the async dispatch queue "
                        "every iteration — gate it behind a profiling flag or "
                        "sync once after the loop",
                        symbol=self.fn_qual,
                    )
                )
            else:
                callee = (
                    fn.id if isinstance(fn, ast.Name) else (dotted_name(fn) or "")
                )
                chain = self.blocking_callables.get(callee)
                if chain is not None:
                    self.findings.append(
                        Finding(
                            self.rule.id,
                            self.module.rel_path,
                            node.lineno,
                            node.col_offset,
                            f"'{callee}()' blocks every iteration of this loop "
                            f"({chain}) — gate it behind a profiling flag or "
                            "sync once after the loop",
                            symbol=self.fn_qual,
                        )
                    )
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        pass  # nested defs are scanned as their own functions

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef


class BlockingInHotLoop(Rule):
    id = "blocking-in-hot-loop"
    description = (
        "block_until_ready/effects_barrier inside a step loop outside a "
        "profiling guard (direct, or through a helper in any module); "
        "jax.profiler start/stop_trace inside a loop without sampled-"
        "cadence evidence"
    )
    kind = "reachability"
    fix_hint = (
        "sync once after the loop, or gate the barrier behind a sampled "
        "profiling cadence (step % PROFILE_EVERY == 0)"
    )

    def check(self, module, ctx):
        blocking_callables = ctx.blocking_aliases.get(module.rel_path, {})
        findings = []
        for info in module.callgraph.functions.values():
            if GUARD_NAME_RE.search(info.name):
                continue  # bench/profiling helpers sync on purpose
            if not module.index.own(info.node, ast.For, ast.While):
                continue  # a finding needs a loop of this function's own
            v = _LoopVisitor(self, module, info.qualname, blocking_callables)
            for stmt in info.node.body:
                v.visit(stmt)
            findings.extend(v.findings)
        return findings

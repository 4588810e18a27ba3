"""pallas-hazard: host leaks inside Pallas kernel bodies, and kernel call
sites with no lowering-mode fallback.

Two hazard shapes (docs/kernels.md §graftlint):

1. **Host work in a kernel body.** The function handed to
   ``pl.pallas_call`` executes on the accelerator core (or the
   interpreter): a host callback (``jax.debug.callback`` /
   ``io_callback`` / ``pure_callback``), a python ``print``/``breakpoint``,
   or a python-side ``if``/``while`` branching on a kernel *ref* parameter
   either fails to lower (Mosaic has no host channel) or silently bakes
   one trace-time branch into every invocation.  ``pl.debug_print`` and
   branches on static (keyword-only / closure) config are fine — the rule
   only fires on tests that reference the kernel's positional (ref)
   parameters.

2. **Un-gated call site.** A ``pl.pallas_call`` invocation with no
   ``interpret=`` argument and no interpret/backend-gated branch in scope
   compiles Mosaic unconditionally — the program is then TPU-only, which
   breaks the policy discipline this repo's kernels follow (the
   ``KernelPolicy.interpret`` mode must reach every call so tier-1 can run
   the kernel under the CPU interpreter; docs/kernels.md §policy).
"""

from __future__ import annotations

import ast
import re

from ..engine import Finding, Rule

# host-side calls that cannot (or must not) live in a kernel body;
# pl.debug_print is the sanctioned in-kernel print and does not match
_HOST_CALLBACK_LEAVES = {
    "debug_callback",
    "io_callback",
    "pure_callback",
    "breakpoint",
}

_FALLBACK_GUARD_RE = re.compile(r"interpret|backend|platform|tpu", re.IGNORECASE)


def _call_leaf(node: ast.Call, module) -> str:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        resolved = module.resolve(fn) or fn.id
        return resolved.rsplit(".", 1)[-1]
    return ""


def _kernel_fn_name(call: ast.Call) -> str | None:
    """The kernel function a ``pallas_call`` receives: a bare name, or the
    first argument of a ``functools.partial(...)`` wrapper."""
    if not call.args:
        return None
    first = call.args[0]
    if isinstance(first, ast.Name):
        return first.id
    if isinstance(first, ast.Call):
        inner = first.func
        leaf = inner.attr if isinstance(inner, ast.Attribute) else getattr(
            inner, "id", ""
        )
        if leaf == "partial" and first.args and isinstance(first.args[0], ast.Name):
            return first.args[0].id
    return None


def _positional_params(fn_node) -> set[str]:
    """The kernel's ref parameters: Pallas passes refs positionally, so
    keyword-only params (static config bound via functools.partial) are
    excluded on purpose — branching on those is trace-time specialization,
    not a host leak."""
    args = fn_node.args
    return {a.arg for a in list(args.posonlyargs) + list(args.args)}


def _mentions_any(index, test: ast.AST, names: set[str]) -> bool:
    return any(node.id in names for node in index.walk(test, ast.Name))


class _KernelBodyVisitor(ast.NodeVisitor):
    """Scan one kernel function's body for host leaks."""

    def __init__(self, rule, module, fn_info):
        self.rule = rule
        self.module = module
        self.fn = fn_info
        self.ref_params = _positional_params(fn_info.node)
        self.findings: list[Finding] = []

    def _flag(self, node, message: str) -> None:
        self.findings.append(
            Finding(
                self.rule.id,
                self.module.rel_path,
                node.lineno,
                node.col_offset,
                message,
                symbol=self.fn.qualname,
            )
        )

    def visit_Call(self, node):
        leaf = _call_leaf(node, self.module)
        if leaf in _HOST_CALLBACK_LEAVES:
            self._flag(
                node,
                f"{leaf}() inside a pallas kernel body is a host callback — "
                "Mosaic has no host channel; use pl.debug_print or move the "
                "callback outside the kernel",
            )
        elif isinstance(node.func, ast.Name) and node.func.id == "print":
            self._flag(
                node,
                "print() inside a pallas kernel body runs at trace time only "
                "(or fails to lower) — use pl.debug_print",
            )
        self.generic_visit(node)

    def _check_branch(self, node, kind: str) -> None:
        if _mentions_any(self.module.index, node.test, self.ref_params):
            self._flag(
                node,
                f"python-side {kind} on a kernel ref parameter bakes one "
                "trace-time branch into every invocation — use @pl.when / "
                "jnp.where / jax.lax.cond on the loaded value instead",
            )

    def visit_If(self, node):
        self._check_branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node):
        self._check_branch(node, "while")
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        pass  # nested defs (e.g. run_scoped bodies) scan as their own fns

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef


class _CallSiteVisitor(ast.NodeVisitor):
    """Find pallas_call invocations; collect (call, guarded) pairs."""

    def __init__(self, module):
        self.module = module
        self.guard_depth = 0
        self.sites: list[tuple[ast.Call, bool]] = []

    def visit_If(self, node):
        guarded = bool(
            _FALLBACK_GUARD_RE.search(ast.dump(node.test))
        )
        self.guard_depth += guarded
        self.generic_visit(node)
        self.guard_depth -= guarded

    def visit_Call(self, node):
        if _call_leaf(node, self.module) == "pallas_call":
            self.sites.append((node, self.guard_depth > 0))
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        pass  # nested defs are their own FunctionInfos: scanning them here
        # too would report each of their call sites twice

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_ClassDef = visit_FunctionDef


class PallasHazard(Rule):
    id = "pallas-hazard"
    description = (
        "pl.pallas_call whose kernel body contains a host callback or a "
        "python-side branch on a ref parameter; or a pallas_call site with "
        "no interpret=/policy-gated fallback in scope"
    )
    kind = "syntactic"
    fix_hint = (
        "use pl.when for branches and pl.debug_print for logging inside "
        "kernels; thread KernelPolicy.interpret to the pallas_call site"
    )

    def check(self, module, ctx):
        findings: list[Finding] = []
        index = module.index
        if not any(_call_leaf(c, module) == "pallas_call" for c in index.of_type(ast.Call)):
            return findings
        # kernel functions by bare name, for call-site -> body resolution
        by_name = {}
        for info in module.callgraph.functions.values():
            by_name.setdefault(info.name, info)
        scanned_bodies: set[str] = set()
        for info in module.callgraph.functions.values():
            v = _CallSiteVisitor(module)
            for stmt in info.node.body:
                v.visit(stmt)
            for call, guarded in v.sites:
                has_interpret = any(
                    kw.arg == "interpret" for kw in call.keywords
                )
                if not has_interpret and not guarded:
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            call.lineno,
                            call.col_offset,
                            "pl.pallas_call without an interpret= argument or "
                            "an interpret/backend-gated fallback in scope "
                            "compiles Mosaic unconditionally — thread the "
                            "kernel policy's lowering mode (KernelPolicy."
                            "interpret) so non-TPU backends keep a path",
                            symbol=info.qualname,
                        )
                    )
                kernel_name = _kernel_fn_name(call)
                target = by_name.get(kernel_name) if kernel_name else None
                if target is not None and target.qualname not in scanned_bodies:
                    scanned_bodies.add(target.qualname)
                    findings.extend(self._scan_kernel(module, target))
        return findings

    def _scan_kernel(self, module, target) -> list[Finding]:
        """Scan one kernel function's body, INCLUDING its nested defs —
        a ``pl.run_scoped`` closure executes inside the kernel, so a host
        callback hidden there is the same leak.  Nested defs inherit the
        outer kernel's ref-parameter set (the closure sees those refs)
        plus their own positional params (scoped scratch/semaphores)."""
        body_visitor = _KernelBodyVisitor(self, module, target)
        for stmt in target.node.body:
            body_visitor.visit(stmt)
        findings = list(body_visitor.findings)
        outer_refs = body_visitor.ref_params
        for node in module.index.walk(target.node, ast.FunctionDef):
            if node is not target.node:
                nested = _KernelBodyVisitor(self, module, target)
                nested.ref_params = outer_refs | _positional_params(node)
                for stmt in node.body:
                    nested.visit(stmt)
                findings.extend(nested.findings)
        return findings

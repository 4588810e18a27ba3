"""dtype-widen: accidental float64 on TPU paths, and bare widening of
quantized wire payloads.

TPUs have no f64 ALU: with x64 enabled, every float64 op is emulated at a
fraction of peak FLOPs and doubles HBM traffic; with x64 off (the JAX
default), a float64 dtype request silently truncates to f32 — either way the
author didn't get what they wrote.  Flagged: float64/double dtypes handed to
jnp constructors, ``.astype(jnp.float64)``, ``jnp.float64(...)`` casts, and
library code flipping ``jax_enable_x64`` globally.

The compression layer (``parallel/compress.py``) adds a second widening
hazard: a value returned by ``compress.quantize`` is a *wire payload* whose
magnitudes only mean anything together with its per-block scales — a stray
``payload.astype(float32)`` silently drops the scales and hands downstream
consumers garbage-scaled gradients.  Casts INSIDE the compression layer are
the sanctioned quantize/dequantize boundary, so the check is suppressed for
that module by policy (``_POLICY_MODULES`` — a rule-level scope, not inline
comments); everywhere else, widening a tracked payload local with
``.astype`` fires, and ``compress.dequantize(payload, scales)`` is the fix.
"""

from __future__ import annotations

import ast

from ..engine import Finding, Rule

_WIDE_ATTRS = {"jax.numpy.float64", "jax.numpy.double", "numpy.float64", "numpy.double"}
_WIDE_STRS = {"float64", "double", "f8", "<f8", ">f8"}
# jnp constructors whose dtype can also arrive positionally
_DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "asarray": 1, "array": 1, "full": 2}

# modules where quantize/dequantize casts are the sanctioned policy boundary:
# the payload-widening check below never fires inside them (policy-scoped
# suppression — the layer itself IS the dequantize implementation)
_POLICY_MODULES = ("parallel/compress.py",)


class DtypeWiden(Rule):
    id = "dtype-widen"
    kind = "reachability"
    description = "float64 promotion on a TPU path (jnp dtype, astype, or jax_enable_x64)"
    fix_hint = (
        "use float32 (or bfloat16) — TPUs have no f64 ALU, so x64 silently "
        "emulates at a large cost"
    )

    def _is_wide(self, module, node: ast.AST, allow_builtin_float: bool) -> bool:
        resolved = module.resolve(node)
        if resolved in _WIDE_ATTRS:
            return True
        if isinstance(node, ast.Constant) and node.value in _WIDE_STRS:
            return True
        if allow_builtin_float and isinstance(node, ast.Name) and node.id == "float":
            return True  # dtype=float means float64 under x64
        return False

    def _is_policy_module(self, module) -> bool:
        rel = module.rel_path.replace("\\", "/")
        return any(rel.endswith(p) for p in _POLICY_MODULES)

    def _check_payloads(self, module) -> list[Finding]:
        """Flag ``compress.quantize`` payload locals widened with a bare
        ``.astype`` — per SCOPE, so an unrelated same-named local in another
        function never fires.  A function scope includes its closures (an
        outer payload cast inside a nested def is still the payload); module
        scope skips function bodies (it must not see function locals).  The
        resulting double visit of nested nodes is de-duplicated."""
        index = module.index
        quantized = [
            node
            for node in index.of_type(ast.Assign)
            if isinstance(node.value, ast.Call)
            and (module.resolve(node.value.func) or "").endswith("compress.quantize")
        ]
        if self._is_policy_module(module) or not quantized:
            return []
        defs = index.of_type(ast.FunctionDef, ast.AsyncFunctionDef)
        module_hidden = [(index.pos[d], index.end[index.pos[d]]) for d in defs]
        findings: list[Finding] = []
        seen: set[int] = set()
        for scope in [module.tree] + defs:
            lo, hi = index.pos[scope], index.end[index.pos[scope]]
            hidden = module_hidden if scope is module.tree else []

            def in_scope(node):
                i = index.pos[node]
                return lo < i < hi and not any(a <= i < b for a, b in hidden)

            payloads: set[str] = set()
            for node in filter(in_scope, quantized):
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        payloads.add(target.id)
                    elif (
                        isinstance(target, (ast.Tuple, ast.List))
                        and target.elts
                        and isinstance(target.elts[0], ast.Name)
                    ):
                        payloads.add(target.elts[0].id)
            if not payloads:
                continue
            for node in filter(in_scope, index.walk(scope, ast.Call)):
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and node.args
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in payloads
                    and id(node) not in seen
                ):
                    seen.add(id(node))
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            node.lineno,
                            node.col_offset,
                            "quantized wire payload cast with .astype() outside "
                            "the compression layer — the per-block scales are "
                            "discarded; use compress.dequantize(payload, scales)",
                        )
                    )
        return findings

    def check(self, module, ctx):
        findings = []

        def hit(node, msg):
            findings.append(
                Finding(self.id, module.rel_path, node.lineno, node.col_offset, msg)
            )

        findings.extend(self._check_payloads(module))
        for node in module.index.of_type(ast.Call):
            fn = node.func
            resolved = module.resolve(fn) or ""
            leaf = resolved.rsplit(".", 1)[-1]
            if resolved in ("jax.numpy.float64", "jax.numpy.double"):
                hit(node, f"jnp.{leaf}() cast — TPUs emulate f64; use jnp.float32")
            elif resolved.startswith("jax."):
                # dtype= kwarg on any jax/jnp call, plus positional dtype slots
                dtype_expr = None
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        dtype_expr = kw.value
                if dtype_expr is None and leaf in _DTYPE_POS:
                    pos = _DTYPE_POS[leaf]
                    if len(node.args) > pos:
                        dtype_expr = node.args[pos]
                if dtype_expr is not None and self._is_wide(module, dtype_expr, True):
                    hit(
                        node,
                        f"float64 dtype passed to {leaf}() — TPUs emulate f64 "
                        "(or silently truncate with x64 off); use float32/bfloat16",
                    )
                if resolved == "jax.config.update" and node.args:
                    arg0 = node.args[0]
                    truthy = len(node.args) > 1 and not (
                        isinstance(node.args[1], ast.Constant) and not node.args[1].value
                    )
                    if (
                        isinstance(arg0, ast.Constant)
                        and arg0.value == "jax_enable_x64"
                        and truthy
                    ):
                        hit(
                            node,
                            "jax_enable_x64 flipped globally in library code — "
                            "every downstream op widens to f64 on TPU",
                        )
            elif isinstance(fn, ast.Attribute) and fn.attr == "astype" and node.args:
                # .astype(jnp.float64) is unambiguous; .astype(np.float64) only
                # matters inside traced code (host numpy f64 is fine)
                arg = node.args[0]
                if module.resolve(arg) in ("jax.numpy.float64", "jax.numpy.double"):
                    hit(node, ".astype(jnp.float64) — TPUs emulate f64; use float32")
                elif self._is_wide(module, arg, False):
                    reached = module.callgraph.reached
                    for info, _ in module.callgraph.traced_functions():
                        lo = info.node.lineno
                        hi = getattr(info.node, "end_lineno", lo)
                        if lo <= node.lineno <= hi and info.qualname in reached:
                            hit(
                                node,
                                ".astype(float64) inside traced code — TPUs "
                                "emulate f64; use float32",
                            )
                            break
        return findings

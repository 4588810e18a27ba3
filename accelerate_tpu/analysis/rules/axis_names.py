"""axis-name-mismatch: collective axis names the mesh does not declare.

``lax.psum(x, "batch")`` over a mesh whose axes are ``("dp", "fsdp", "tp",
"sp", "ep", "pp")`` is a NameError *at trace time on hardware* — i.e. in the
one environment tests do not run in.  The declared axis
universe is harvested in the engine's first pass from ``MESH_AXIS_*`` /
``ALL_MESH_AXES`` constants, ``Mesh(..., axis_names=...)`` literals and
``make_mesh({...})`` keys, so the rule checks every literal collective axis,
``PartitionSpec`` entry, and ``axis_name=``-style default against it.
"""

from __future__ import annotations

import ast

from ..engine import Finding, Rule, _literal_strs

# canonical leaf -> positional index of the axis-name argument
_COLLECTIVES = {
    "psum": 1,
    "pmean": 1,
    "pmax": 1,
    "pmin": 1,
    "all_gather": 1,
    "ppermute": 1,
    "pshuffle": 1,
    "all_to_all": 1,
    "psum_scatter": 1,
    "pbroadcast": 1,
    "axis_index": 0,
    "axis_size": 0,
}
_SPEC_LEAVES = {"PartitionSpec"}


def _axis_literals(module, node: ast.AST) -> list[tuple[str, ast.AST]]:
    """String axis names in an expression: literals, tuples of literals, and
    bare Names that resolve to module-level string constants."""
    out = []
    if isinstance(node, ast.Name) and node.id in module.str_constants:
        out.append((module.str_constants[node.id], node))
    for s in _literal_strs(node):
        out.append((s, node))
    if isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Name) and e.id in module.str_constants:
                out.append((module.str_constants[e.id], e))
    return out


class AxisNameMismatch(Rule):
    id = "axis-name-mismatch"
    kind = "syntactic"
    description = (
        "collective/PartitionSpec axis name not declared by any mesh "
        "(MESH_AXIS_* constants, Mesh(axis_names=...), make_mesh({...}))"
    )
    fix_hint = (
        "use an axis name the mesh declares (the MESH_AXIS_* constants) "
        "instead of a free-hand string"
    )

    def check(self, module, ctx):
        findings = []
        universe = ctx.axis_universe

        def verify(name, node, what):
            if name not in universe:
                findings.append(
                    Finding(
                        self.id,
                        module.rel_path,
                        node.lineno,
                        node.col_offset,
                        f"{what} axis name '{name}' is not a declared mesh axis "
                        f"(declared: {sorted(universe)})",
                    )
                )

        for node in module.index.of_type(ast.Call, ast.FunctionDef, ast.AsyncFunctionDef):
            if isinstance(node, ast.Call):
                resolved = module.resolve(node.func) or ""
                leaf = resolved.rsplit(".", 1)[-1]
                if leaf in _COLLECTIVES and (
                    "lax" in resolved.split(".") or resolved.startswith("jax.")
                ):
                    pos = _COLLECTIVES[leaf]
                    axis_expr = node.args[pos] if len(node.args) > pos else None
                    for kw in node.keywords:
                        if kw.arg in ("axis_name", "axis_names"):
                            axis_expr = kw.value
                    if axis_expr is not None:
                        for name, n in _axis_literals(module, axis_expr):
                            verify(name, n, f"lax.{leaf}")
                elif leaf in _SPEC_LEAVES:
                    for arg in list(node.args) + [kw.value for kw in node.keywords]:
                        for name, n in _axis_literals(module, arg):
                            verify(name, n, "PartitionSpec")
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # `axis_name: str = "sp"`-style defaults are axis declarations
                # consumed far from any mesh; check them where they're written
                a = node.args
                pos = [p.arg for p in a.posonlyargs + a.args]
                named = dict(zip(pos[len(pos) - len(a.defaults):], a.defaults))
                named.update(
                    (p.arg, d)
                    for p, d in zip(a.kwonlyargs, a.kw_defaults)
                    if d is not None
                )
                for pname, d in named.items():
                    if "axis" in pname and not pname.endswith("axes"):
                        for name, n in _axis_literals(module, d):
                            verify(name, n, f"default of parameter '{pname}'")
        return findings

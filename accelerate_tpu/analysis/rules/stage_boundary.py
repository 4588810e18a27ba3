"""stage-boundary-vs-plan: pp-axis/stage-layout rediscovery outside the plan.

The resolved ``ParallelPlan`` (parallel/plan.py, docs/parallel_plan.md) is
the ONE owner of the pipeline axis: its size, the stage/virtual-stage layer
spans, and the schedule.  History shows every consumer that re-derived the
axis for itself — ``mesh.shape.get("pp", 1)`` in a model forward, a
hand-sliced ``range(s * per_stage, ...)`` span, a literal ``P("pp")`` in a
subsystem — eventually disagreed with the plan after a layout flip (the
exact drift class the plan refactor deleted).  This rule keeps the
ownership boundary: outside the owner modules, code that

* reads the pp axis off a mesh dict (``*.shape.get("pp", ...)`` or
  ``*.shape["pp"]``),
* lays out a ``PartitionSpec`` naming the literal ``"pp"`` axis,
* passes ``axis_name="pp"`` (or defaults a parameter to it),
* hand-derives a per-stage layer count (``layers // pp``-shaped arithmetic
  rooted in a pp size), or
* permutes a stacked layer axis IN-PROGRAM — ``jnp.take``/``jnp.argsort``
  driven by a layer-order index inside a captured pipeline body.  The
  interleave permutation is committed ONCE at ``prepare()`` (ISSUE 17,
  docs/parallel_plan.md §layout contract); a per-step gather pays
  ``(1−1/V)`` of the stack in permutation bytes every step and silently
  diverges from the layout of record after a plan flip.  Consumers go
  through ``apply_layer_order``/``StagePlan.layer_order`` at relayout
  time (the one blessed restore/transpose path), never inside the step.

fires — the fix is to read ``current_plan()`` / ``plan.stage`` instead.
Owners: the plan itself, the pipeline schedules, mesh construction, the
config layer that RESOLVES the plan, and the launcher env protocol.
"""

from __future__ import annotations

import ast
import os

from ..engine import Finding, Rule

# modules that legitimately spell the pp axis: they DEFINE the plan or the
# schedules/mesh the plan arbitrates, or speak the launcher env protocol
_OWNER_SUFFIXES = (
    "parallel/plan.py",
    "parallel/pipeline.py",
    "parallel/mesh.py",
    "utils/constants.py",
    "utils/dataclasses.py",
    "utils/launch.py",
    "commands/launch.py",
    "commands/config/config_args.py",
    "state.py",
)

_PP = "pp"
_SPEC_LEAVES = {"PartitionSpec"}
# names that mark the pp side of the "layers per stage" arithmetic heuristic
_PPISH = frozenset({"pp", "pp_size", "num_stages", "n_stages"})


def _layer_orderish(name: str) -> bool:
    """A name that denotes the stacked-layer permutation vector (e.g.
    ``layer_order``, ``inverse_layer_order``, ``layer_perm``)."""
    n = name.lower()
    return "layer" in n and ("order" in n or "perm" in n)


def _is_shape_attr(node: ast.AST) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == "shape"


def _names_in(index, node: ast.AST) -> list[str]:
    return [
        sub.id if isinstance(sub, ast.Name) else sub.attr
        for sub in index.walk(node, ast.Name, ast.Attribute)
    ]


class StageBoundaryVsPlan(Rule):
    id = "stage-boundary-vs-plan"
    kind = "syntactic"
    description = (
        "pp axis size / stage layer spans derived outside the resolved "
        "ParallelPlan (mesh.shape pp reads, literal P('pp') specs, "
        "hand-sliced layers-per-stage arithmetic) — read current_plan() "
        "instead (docs/parallel_plan.md)"
    )
    fix_hint = (
        "read current_plan().pp and plan.stage_spans() instead of deriving "
        "stage geometry by hand (docs/parallel_plan.md)"
    )

    def check(self, module, ctx):
        rel = module.rel_path.replace(os.sep, "/")
        if any(rel.endswith(suffix) for suffix in _OWNER_SUFFIXES):
            return []
        findings = []

        def fire(node, what):
            findings.append(
                Finding(
                    self.id,
                    module.rel_path,
                    node.lineno,
                    node.col_offset,
                    f"{what} — stage/pp layout belongs to the resolved "
                    "ParallelPlan (current_plan().pp / plan.stage, "
                    "docs/parallel_plan.md)",
                )
            )

        index = module.index
        for node in index.of_type(
            ast.FunctionDef, ast.AsyncFunctionDef, ast.Call, ast.Subscript, ast.BinOp
        ):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # def f(..., axis_name="pp"): every call site that omits the
                # keyword rediscovers the axis through the default
                args = node.args
                for arg, default in list(
                    zip(reversed(args.args), reversed(args.defaults))
                ) + list(zip(args.kwonlyargs, args.kw_defaults)):
                    if (
                        arg is not None
                        and arg.arg in ("axis_name", "axis_names")
                        and isinstance(default, ast.Constant)
                        and default.value == _PP
                    ):
                        fire(default, "parameter defaulting to the literal 'pp' axis")
            elif isinstance(node, ast.Call):
                fn = node.func
                # jnp.take(stack, layer_order)/jnp.argsort(layer_order): an
                # in-program stacked-layer permutation — the layout is
                # committed once at prepare() (ISSUE 17); per-step gathers
                # move (1-1/V) of the stack and drift after a plan flip
                if isinstance(fn, ast.Attribute) and fn.attr in (
                    "take", "argsort",
                ):
                    involved = [
                        n
                        for a in list(node.args)
                        + [kw.value for kw in node.keywords]
                        for n in _names_in(index, a)
                    ]
                    if any(_layer_orderish(n) for n in involved):
                        fire(
                            node,
                            f"in-program stacked-layer permutation "
                            f"({fn.attr} over a layer-order index) — commit "
                            "the layout at prepare() and consume the stack "
                            "in place (apply_layer_order at relayout time "
                            "only)",
                        )
                        continue
                # mesh.shape.get("pp", ...) — axis-size rediscovery
                if (
                    isinstance(fn, ast.Attribute)
                    and fn.attr == "get"
                    and _is_shape_attr(fn.value)
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value == _PP
                ):
                    fire(node, 'pp axis size read off a mesh dict (.shape.get("pp"))')
                    continue
                # PartitionSpec("pp", ...) with the literal axis
                resolved = module.resolve(fn) or ""
                if resolved.rsplit(".", 1)[-1] in _SPEC_LEAVES:
                    for arg in list(node.args) + [kw.value for kw in node.keywords]:
                        for sub in index.walk(arg, ast.Constant):
                            if sub.value != _PP:
                                continue
                            fire(sub, "literal 'pp' axis in a PartitionSpec")
                    continue
                # axis_name="pp" handed to some consumer-side collective
                for kw in node.keywords:
                    if (
                        kw.arg in ("axis_name", "axis_names")
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value == _PP
                    ):
                        fire(kw.value, "literal axis_name='pp' argument")
            elif isinstance(node, ast.Subscript):
                # mesh.shape["pp"]
                sl = node.slice
                if (
                    _is_shape_attr(node.value)
                    and isinstance(sl, ast.Constant)
                    and sl.value == _PP
                ):
                    fire(node, 'pp axis size read off a mesh dict (.shape["pp"])')
            elif isinstance(node, ast.BinOp) and isinstance(
                node.op, (ast.FloorDiv, ast.Mod)
            ):
                # layers // pp_size-shaped span arithmetic: one side names
                # layers, the other names a pp size — the hand-sliced span
                # the plan's StagePlan.layer_spans replaces
                left = [n.lower() for n in _names_in(index, node.left)]
                right = [n.lower() for n in _names_in(index, node.right)]

                def layerish(names):
                    return any("layer" in n for n in names)

                def ppish(names):
                    return any(n in _PPISH for n in names)

                if (layerish(left) and ppish(right)) or (
                    layerish(right) and ppish(left)
                ):
                    fire(node, "hand-sliced layers-per-stage arithmetic")
        return findings

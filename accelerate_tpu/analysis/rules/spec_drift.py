"""sharding-spec-drift: a sharding plan that disagrees with checkpoint metadata.

Checkpoint index files (``<name>.index.json``, written by
``utils/fsdp_utils.collect_sharded_model_state``) record the save-time
``PartitionSpec`` of every tensor.  Loading reshards by global slice bounds,
so a drifted plan does not corrupt data — it silently *re-lays-out* the
whole model on step one (all-gather + re-shard of every parameter, a
multi-second stall and a new compile on real pods) and invalidates any
capture cache keyed on the old layout.  This rule catches the drift at lint
time: run with ``--ckpt-index <dir-or-index.json>`` and every literal
``tp_plan`` / ``sharding_plan`` dict in the analyzed source is cross-checked
against the recorded specs.

Without ``--ckpt-index`` the rule is inert (there is nothing to compare
against), so it never fires during plain ``make lint``.

Beyond literal ``tp_plan`` edits, the rule also checks the *fsdp strategy*
against the checkpoint: ``plan_param_spec`` only lays an ``fsdp`` axis onto
parameters under ``FULL_SHARD`` / ``HYBRID_SHARD``.  A checkpoint whose
index records fsdp-sharded tensors loaded by source that now says
``sharding_strategy="NO_SHARD"`` (or ``SHARD_GRAD_OP``) will all-gather and
re-lay-out every parameter at step one — the same silent cost as a plan
edit, caught the same way.
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from ..engine import Finding, Rule

_PLAN_NAME_RE = re.compile(r"(tp_plan|sharding_plan)", re.IGNORECASE)


def _template_entries(node: ast.AST) -> Optional[list]:
    """Normalize a literal partition-spec template into per-dim axis lists.

    ``("tp", None)`` → ``[["tp"], []]``; nested tuples collect multi-axis
    dims.  Returns None when any entry is not a literal (runtime-computed
    templates cannot be checked statically).
    """
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims: list = []
    for e in node.elts:
        if isinstance(e, ast.Constant) and e.value is None:
            dims.append([])
        elif isinstance(e, ast.Constant) and isinstance(e.value, str):
            dims.append([e.value])
        elif isinstance(e, (ast.Tuple, ast.List)) and all(
            isinstance(x, ast.Constant) and isinstance(x.value, str) for x in e.elts
        ):
            dims.append([x.value for x in e.elts])
        else:
            return None
    return dims


def _normalize_spec(spec: list) -> list:
    """Recorded JSON spec (str | [str, ...] | null per dim) → per-dim axis
    lists with trailing replicated dims stripped."""
    dims = []
    for e in spec or []:
        if e is None:
            dims.append([])
        elif isinstance(e, str):
            dims.append([e])
        else:
            dims.append(list(e))
    while dims and not dims[-1]:
        dims.pop()
    return dims


def _plan_dicts(module):
    """Yield (plan_name, ast.Dict) for every literal sharding-plan binding."""
    for node in module.index.of_type(ast.Assign, ast.AnnAssign):
        targets = []
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        else:
            continue
        if not isinstance(value, ast.Dict):
            continue
        for t in targets:
            name = t.id if isinstance(t, ast.Name) else (
                t.attr if isinstance(t, ast.Attribute) else None
            )
            if name and _PLAN_NAME_RE.search(name):
                yield name, value
                break


# strategies under which plan_param_spec does NOT shard parameters
_NON_SHARDING = {"NO_SHARD", "SHARD_GRAD_OP"}


def _strategy_literals(module):
    """Yield (value, node) for every literal ``sharding_strategy`` binding:
    a keyword argument (``FullyShardedDataParallelPlugin(sharding_strategy=
    "NO_SHARD")``) or an assignment whose target name says so."""
    for node in module.index.of_type(ast.Call, ast.Assign, ast.AnnAssign):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if (
                    kw.arg == "sharding_strategy"
                    and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)
                ):
                    yield kw.value.value, kw.value
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            value = node.value
            if not (isinstance(value, ast.Constant) and isinstance(value.value, str)):
                continue
            for t in targets:
                name = t.id if isinstance(t, ast.Name) else (
                    t.attr if isinstance(t, ast.Attribute) else None
                )
                if name and name.endswith("sharding_strategy"):
                    yield value.value, value
                    break


class ShardingSpecDrift(Rule):
    id = "sharding-spec-drift"
    kind = "syntactic"
    description = (
        "sharding plan or fsdp strategy disagrees with the checkpoint "
        "metadata records (needs --ckpt-index)"
    )
    fix_hint = (
        "match the plan to the checkpoint's recorded PartitionSpec, or "
        "re-save the checkpoint under the new plan"
    )

    def check(self, module, ctx):
        specs = getattr(ctx, "ckpt_specs", None)
        if not specs:
            return []
        findings: list[Finding] = []
        findings.extend(self._check_strategy(module, specs))
        for plan_name, dict_node in _plan_dicts(module):
            claimed: set = set()  # first matching pattern wins, like plan_param_spec
            for key_node, value_node in zip(dict_node.keys, dict_node.values):
                if not (
                    isinstance(key_node, ast.Constant)
                    and isinstance(key_node.value, str)
                ):
                    continue
                pattern = key_node.value
                template = _template_entries(value_node)
                if template is None:
                    continue
                try:
                    compiled = re.compile(pattern)
                except re.error:
                    continue
                planned = list(template)
                while planned and not planned[-1]:
                    planned.pop()
                mismatched = []
                for tensor, recorded in specs.items():
                    if tensor in claimed:
                        continue
                    if not (compiled.fullmatch(tensor) or compiled.search(tensor)):
                        continue
                    claimed.add(tensor)
                    rec = _normalize_spec(recorded)
                    if not rec:
                        # fully replicated at save time: a size-1 mesh axis
                        # canonicalizes any template away, so this proves
                        # nothing about drift
                        continue
                    # the runtime pads templates with None to the param rank,
                    # and plan_param_spec ADDS "fsdp" onto a template-free dim
                    # on fsdp>1 meshes — a recorded "fsdp" the template never
                    # mentioned is auto-sharding, not drift
                    n = max(len(planned), len(rec))
                    a = planned + [[]] * (n - len(planned))
                    b = [
                        [
                            axis
                            for axis in dim
                            if not (axis == "fsdp" and "fsdp" not in a[i])
                        ]
                        for i, dim in enumerate(rec + [[]] * (n - len(rec)))
                    ]
                    if a != b:
                        mismatched.append((tensor, rec))
                if mismatched:
                    tensor, rec = mismatched[0]
                    more = (
                        f" (+{len(mismatched) - 1} more tensor(s))"
                        if len(mismatched) > 1
                        else ""
                    )
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            key_node.lineno,
                            key_node.col_offset,
                            f"plan entry {pattern!r} assigns axes {planned} "
                            f"but the checkpoint recorded {rec} for "
                            f"'{tensor}'{more}; loading reshards the whole "
                            "tensor at step one — resave the checkpoint or "
                            "revert the plan edit",
                            symbol=plan_name,
                        )
                    )
        return findings

    def _check_strategy(self, module, specs):
        """The plan_param_spec side of drift: fsdp-sharded records vs a
        source strategy that no longer shards parameters."""
        fsdp_tensors = [
            tensor
            for tensor, recorded in specs.items()
            if any("fsdp" in dim for dim in _normalize_spec(recorded))
        ]
        if not fsdp_tensors:
            # no fsdp axis recorded proves nothing: the checkpoint may have
            # been saved on an fsdp:1 mesh, which canonicalizes the axis away
            return []
        findings = []
        for value, node in _strategy_literals(module):
            if value in _NON_SHARDING:
                findings.append(
                    Finding(
                        self.id,
                        module.rel_path,
                        node.lineno,
                        node.col_offset,
                        f"sharding_strategy={value!r} but the checkpoint "
                        f"records fsdp-sharded tensors (e.g. "
                        f"'{fsdp_tensors[0]}'"
                        + (
                            f", +{len(fsdp_tensors) - 1} more"
                            if len(fsdp_tensors) > 1
                            else ""
                        )
                        + ") — plan_param_spec will not shard under this "
                        "strategy, so loading all-gathers and re-lays-out "
                        "every parameter at step one; restore FULL_SHARD or "
                        "resave the checkpoint",
                    )
                )
        return findings

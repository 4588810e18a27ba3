"""host-sync-in-trace: device→host transfers reachable from traced code.

Inside a ``jax.jit`` / ``shard_map`` trace the value is a tracer: ``.item()``,
``float()``, ``np.asarray`` and ``jax.device_get`` either raise a
ConcretizationTypeError outright or — worse, under ``io_callback``-style
escape hatches — silently serialize every device step on a host round-trip.
On a pod that is a cross-host stall per step.  ``jnp.asarray`` (a device op)
is the trace-safe spelling and is deliberately NOT flagged.
"""

from __future__ import annotations

import ast

from ..engine import Finding, Rule

# methods that force a host transfer wherever they appear
_SINK_METHODS = {"item", "tolist"}
# numpy module functions that concretize their argument on host
_NUMPY_SINKS = {"asarray", "array", "ascontiguousarray", "copy"}
_JAX_SINKS = {"jax.device_get"}
_BUILTIN_CASTS = {"float", "int", "bool", "complex"}
# calls whose result is host metadata, never a tracer
_HOST_METADATA = {
    "devices", "local_devices", "device_count", "local_device_count", "process_index",
}


def _is_static_expr(index, node: ast.AST) -> bool:
    """Expressions whose value is known at trace time (no host sync): python
    literals, ``len()``, and shape/ndim/size attribute reads."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
        return node.func.id == "len"
    return any(
        sub.attr in ("shape", "ndim", "size") for sub in index.walk(node, ast.Attribute)
    )


class HostSyncInTrace(Rule):
    id = "host-sync-in-trace"
    kind = "reachability"
    description = (
        "host transfer (.item()/.tolist()/float()/np.asarray/jax.device_get/"
        ".block_until_ready) reachable from jit/shard_map/compile_step-traced code"
    )
    fix_hint = (
        "keep the value on device (jnp ops) or move the read outside the "
        "traced region; use jax.debug.print for trace-time logging"
    )

    def check(self, module, ctx):
        findings = []
        for info, reason in module.callgraph.traced_functions():
            for node in module.index.own(info.node, ast.Call):
                msg = self._sink_message(module, node)
                if msg:
                    findings.append(
                        Finding(
                            self.id,
                            module.rel_path,
                            node.lineno,
                            node.col_offset,
                            f"{msg} in traced code ({reason})",
                            symbol=info.qualname,
                        )
                    )
        return findings

    def _sink_message(self, module, node: ast.Call):
        fn = node.func
        resolved = module.resolve(fn)
        if resolved in _JAX_SINKS or (resolved or "").endswith(".device_get"):
            return "jax.device_get forces a device→host transfer"
        if resolved and "." in resolved:
            head, leaf = resolved.rsplit(".", 1)
            if (
                head in ("numpy", "np")
                and leaf in _NUMPY_SINKS
                and not self._host_metadata_arg(module, node)
            ):
                return f"np.{leaf}() concretizes a tracer on host (use jnp.{leaf})"
        if (
            isinstance(fn, ast.Name)
            and fn.id in _BUILTIN_CASTS
            and not self._host_metadata_arg(module, node)
            and len(node.args) == 1
            and not node.keywords
            and not _is_static_expr(module.index, node.args[0])
        ):
            return f"{fn.id}() concretizes a traced value to a python scalar"
        if isinstance(fn, ast.Attribute):
            if fn.attr in _SINK_METHODS:
                return f".{fn.attr}() forces a device→host transfer"
            if fn.attr == "block_until_ready":
                return ".block_until_ready() blocks the host (tracers don't have it)"
            if fn.attr == "numpy" and not node.args and not node.keywords:
                return ".numpy() forces a device→host transfer"
        return None

    @staticmethod
    def _host_metadata_arg(module, node: ast.Call) -> bool:
        """True when the argument is host metadata, never a tracer: device
        handles (``jax.devices()``), mesh/sharding topology queries."""
        return any(
            (module.resolve(sub.func) or "").rsplit(".", 1)[-1] in _HOST_METADATA
            for arg in node.args
            for sub in module.index.walk(arg, ast.Call)
        )

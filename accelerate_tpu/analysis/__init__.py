"""graftlint — static trace-safety & collective-correctness analysis.

The paper's promise is that one unmodified loop body runs from 1-process CPU
to a multi-chip TPU mesh.  The failure modes that break that promise — host
syncs baked into a ``jax.jit`` trace, per-step recompiles, collectives over
axis names the mesh does not carry — surface only at runtime, often only on
hardware.  This subsystem catches them from the AST,
in CI, on the virtual 8-device CPU mesh.

Layout:
  engine.py     file discovery, suppressions, baseline, rule runner, cache glue
  callgraph.py  per-module call graph + traced-region reachability
  program.py    whole-program import graph: cross-module reachability,
                donors/escapers/blockers resolved through imports
  cache.py      on-disk per-module cache (content hash + environment hash)
  rules/        one module per rule

Entry point: ``tools/graftlint.py`` (also ``make lint``).
"""

from .engine import (
    ANALYSIS_VERSION,
    AnalysisResult,
    Finding,
    ModuleInfo,
    Rule,
    load_baseline,
    load_ckpt_specs,
    run_analysis,
    sarif_report,
    write_baseline,
)
from .program import ModuleSummary, ProgramGraph, module_name_for
from .rules import ALL_RULES, get_rules

__all__ = [
    "ALL_RULES",
    "ANALYSIS_VERSION",
    "AnalysisResult",
    "Finding",
    "ModuleInfo",
    "ModuleSummary",
    "ProgramGraph",
    "Rule",
    "get_rules",
    "load_baseline",
    "load_ckpt_specs",
    "module_name_for",
    "run_analysis",
    "sarif_report",
    "write_baseline",
]

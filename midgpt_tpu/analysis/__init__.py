"""Static HLO/sharding analysis: compiled-step collective audits, a
declarative sharding-invariant ruleset, comms cost reports, and an AST
lint for TPU footguns.

Layering:

- :mod:`~midgpt_tpu.analysis.hlo`, :mod:`~midgpt_tpu.analysis.rules`,
  :mod:`~midgpt_tpu.analysis.cost`, :mod:`~midgpt_tpu.analysis.pylint_pass`,
  :mod:`~midgpt_tpu.analysis.traffic`, :mod:`~midgpt_tpu.analysis.budgets`
  are jax-free (pure text/AST/arithmetic processing) — importable
  anywhere, unit-testable in milliseconds against canned fixtures.
- :mod:`~midgpt_tpu.analysis.harness` imports jax and compiles the real
  train step; :mod:`~midgpt_tpu.analysis.choreo` imports jax and traces
  the serving programs to jaxprs, and
  :mod:`~midgpt_tpu.analysis.dispatch` (the launch auditor) builds on
  its flattener. Their names are
  re-exported lazily so ``import midgpt_tpu.analysis`` stays light (the
  CLI must configure the platform *before* jax loads).

CLI: ``python -m midgpt_tpu.analysis --config <name> --mesh 8`` — see the
README's "Static sharding analysis" section.
"""

from midgpt_tpu.analysis.cost import cost_report
from midgpt_tpu.analysis.hlo import (
    AliasEntry,
    Collective,
    MeshInfo,
    count_entry_parameters,
    dtypes_used,
    parse_collectives,
    parse_input_output_alias,
    parse_replica_groups,
)
from midgpt_tpu.analysis.budgets import (
    budget_for,
    check_budget,
    check_dispatch_budget,
    dispatch_budget,
)
from midgpt_tpu.analysis.pylint_pass import Finding, lint_paths, lint_source
from midgpt_tpu.analysis.traffic import (
    TrafficReport,
    floor_decomposition,
    floor_table_markdown,
    traffic_report,
    weight_stream_bytes,
)
from midgpt_tpu.analysis.rules import (
    Report,
    Rule,
    RuleSet,
    StepAnalysis,
    Violation,
    rules_for_config,
)

_HARNESS_NAMES = (
    "analyze_train_step",
    "audit_config",
    "audit_serving_dispatch",
    "compile_eval_sweep",
    "compile_train_step",
    "override_logical_rules",
    "prove_serving_choreography",
    "serving_dispatch_reports",
    "shrink_for_audit",
    "train_step_comms_summary",
)

__all__ = [
    "AliasEntry",
    "Collective",
    "Finding",
    "MeshInfo",
    "Report",
    "Rule",
    "RuleSet",
    "StepAnalysis",
    "TrafficReport",
    "Violation",
    "budget_for",
    "check_budget",
    "check_dispatch_budget",
    "dispatch_budget",
    "cost_report",
    "floor_decomposition",
    "floor_table_markdown",
    "traffic_report",
    "weight_stream_bytes",
    "count_entry_parameters",
    "dtypes_used",
    "lint_paths",
    "lint_source",
    "parse_collectives",
    "parse_input_output_alias",
    "parse_replica_groups",
    "rules_for_config",
    *_HARNESS_NAMES,
]


def __getattr__(name: str):
    if name in _HARNESS_NAMES:
        from midgpt_tpu.analysis import harness

        return getattr(harness, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

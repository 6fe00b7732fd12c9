"""Trace-contract analyzer: declarative jaxpr lint for cost, communication,
and memory invariants.

The paper's claims are structural: one local factorization per machine, one
O(d*K) aggregation per round, a fused solver that fits its VMEM budget.
This package turns those invariants into machine-checked *contracts*:

- :mod:`repro.analysis.walker` -- recursive jaxpr traversal (pjit / scan /
  while / cond / shard_map / pallas_call sub-jaxprs) with located eqn paths,
  plus the shared :func:`count_eqns` counter used by the test suite and
  :func:`count_executions`, which weighs each site by its scans' trips.
- :mod:`repro.analysis.contracts` -- the contract types: primitive-count
  budgets, execution counts through static scan trips, collective
  payload contracts, VMEM-budget conformance, and a floating-point dtype
  policy.
- :mod:`repro.analysis.registry` -- the ``@trace_contract`` decorator that
  declares contracts next to the code they guard.
- :mod:`repro.analysis.cases` -- representative trace shapes per entry point
  (including the d % model_axis != 0 remainder shapes).
- :mod:`repro.analysis.imports` -- AST-based import-graph rules replacing
  the old source-grep structural pins.
- :mod:`repro.analysis.lint` -- the ``python -m repro.analysis.lint`` CLI.
"""

from repro.analysis.contracts import (  # noqa: F401
    AxisPayloadBits,
    CollectiveContract,
    DtypePolicy,
    ExecutionBudget,
    Param,
    PrimitiveBudget,
    Violation,
    VmemConformance,
    run_contracts,
)
from repro.analysis.registry import (  # noqa: F401
    check_entry,
    contracts_of,
    registered,
    trace_contract,
)
from repro.analysis.walker import (  # noqa: F401
    EqnSite,
    count_eqns,
    count_executions,
    find_eqns,
    format_site,
    iter_eqns,
)

__all__ = [
    "AxisPayloadBits",
    "CollectiveContract",
    "DtypePolicy",
    "EqnSite",
    "ExecutionBudget",
    "Param",
    "PrimitiveBudget",
    "Violation",
    "VmemConformance",
    "check_entry",
    "contracts_of",
    "count_eqns",
    "count_executions",
    "find_eqns",
    "format_site",
    "iter_eqns",
    "registered",
    "run_contracts",
    "trace_contract",
]

"""repro.guard: fault-tolerant partitioning.

Validation front door (:mod:`repro.guard.validate`), solver escalation
policy (:mod:`repro.guard.policy`), typed diagnostics
(:mod:`repro.guard.errors`), and the deterministic fault-injection
harness (:mod:`repro.guard.chaos`).  See ``core/README.md`` ("Failure
modes & degradation ladder") for the full contract.
"""

from repro.guard import chaos
from repro.guard.errors import (
    GuardError,
    GuardIssue,
    GuardReport,
    RefineRejected,
    SolverBreakdown,
)
from repro.guard.policy import (
    GuardPolicy,
    SolverGuard,
    check_output,
    count_disconnected,
    enforce_output,
    failure_reason,
    fallback_vector,
)
from repro.guard.validate import (
    check_positive_int,
    component_labels,
    pack_components,
    proportional_budgets,
    validate_graph,
    validate_mesh,
    validate_nparts,
)

__all__ = [
    "GuardError", "GuardIssue", "GuardReport", "RefineRejected",
    "SolverBreakdown", "chaos",
    "check_positive_int", "component_labels", "pack_components",
    "proportional_budgets", "validate_graph", "validate_mesh",
    "validate_nparts", "GuardPolicy", "SolverGuard", "check_output",
    "count_disconnected", "enforce_output", "failure_reason",
    "fallback_vector",
]

# Static verification layer (DESIGN.md §5): machine-checks the contracts
# the rest of the repo states in prose.  Three analyzers, all pure and
# dependency-light (numpy + ast only — importing this package never pulls
# jax), runnable as `python -m repro.analysis` and as a pytest tier:
#
# plan_check   — runtime/offline verifier over ExtractionPlan invariants
#                (bounds, sortedness, run tiling, §5.2 slice bound,
#                int32 addressability before kernels consume offsets)
# lint         — repo-specific AST rules (float64 discipline in the exact
#                host planner, no load-then-filter in the data plane, no
#                unguarded int32 casts on offset-carrying arrays)
# concurrency  — lock-discipline race detector (attributes written under
#                `with self._lock` must not be touched outside it)
from .concurrency import check_lock_discipline, check_lock_source
from .diagnostics import Diagnostic
from .lint import lint_source, lint_tree
from .plan_check import PlanVerificationError, check_plan, verify_plan

__all__ = [
    "Diagnostic",
    "PlanVerificationError", "check_plan", "verify_plan",
    "lint_source", "lint_tree",
    "check_lock_discipline", "check_lock_source",
]

"""Static + dynamic verification of block-space execution plans.

``verifier``  -- host-side static checks over any GridPlan/ShardedPlan:
                 race freedom, exactly-once coverage, table fidelity,
                 index bounds, aliasing safety.
``sanitizer`` -- interpret-mode access sanitizer: instruments the
                 BlockSpec index maps of emitted ``pallas_call``s and
                 cross-checks the recorded traces against their static
                 evaluation.
``verify``    -- the CLI: ``python -m repro.analysis.verify --matrix``
                 sweeps the feature matrix and emits a JSON report.
"""
from .sanitizer import AccessTrace, verify_launches
from .verifier import (Finding, PlanVerificationError, Report,
                       verify_or_raise, verify_page_table, verify_plan)

__all__ = [
    "AccessTrace",
    "Finding",
    "PlanVerificationError",
    "Report",
    "verify_launches",
    "verify_or_raise",
    "verify_page_table",
    "verify_plan",
]

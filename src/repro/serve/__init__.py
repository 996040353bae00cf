"""Service layer: the async skim job service.

:class:`SkimService` (DESIGN.md §12) puts a job lifecycle in front of
every backend — cost-based admission, per-tenant quotas, a weighted-fair
queue, batch coalescing onto one shared scan (``SkimEngine.iter_batch``,
DESIGN.md §4c), and window-granular streaming of partial results.
"""

from repro.serve.jobs import (
    CANCELLED,
    DONE,
    FAILED,
    PENDING,
    REJECTED,
    RUNNING,
    TERMINAL,
    CostEstimate,
    ManualClock,
    PartialResult,
    SkimJob,
    TenantQuota,
    price_query,
    union_columns,
)
from repro.serve.journal import JOURNAL_EVENTS, JOURNAL_VERSION, JobJournal
from repro.serve.service import (
    ClusterBackend,
    DeterministicExecutor,
    EngineBackend,
    ServiceError,
    SkimService,
)

__all__ = [
    "CANCELLED",
    "DONE",
    "FAILED",
    "PENDING",
    "REJECTED",
    "RUNNING",
    "TERMINAL",
    "ClusterBackend",
    "CostEstimate",
    "DeterministicExecutor",
    "EngineBackend",
    "JOURNAL_EVENTS",
    "JOURNAL_VERSION",
    "JobJournal",
    "ManualClock",
    "PartialResult",
    "ServiceError",
    "SkimJob",
    "SkimService",
    "TenantQuota",
    "price_query",
    "union_columns",
]

"""Continuous-batching serving engine + multi-replica router (docs/serving.md).

``ServingEngine`` multiplexes many heterogeneous generation requests over a
fixed pool of decode slots inside ONE compiled decode step; ``ServingRouter``
fronts N engine replicas with health-checked dispatch, circuit breakers,
deterministic failover, and SLO-aware shedding (docs/reliability.md).
``SlotScheduler`` owns admission/eviction policy, ``EngineMetrics`` /
``RouterMetrics`` the observability surface, and ``RequestJournal`` the
crash-durability layer (write-ahead accept/token/terminal records;
``ServingEngine.recover`` / ``ServingRouter.recover`` rebuild every accepted
session after process death). ``EngineClient`` puts one replica engine in a
separate OS PROCESS behind a CRC-framed, retrying RPC transport
(``ServingRouter(replica_mode="process")`` — a supervisor respawns killed
workers through journal recovery). ``scripts/serve_bench.py`` drives
synthetic workloads through all of it.
"""

from perceiver_io_tpu.serving.engine import (
    TERMINAL_STATUSES,
    RequestStatus,
    ServedRequest,
    ServingEngine,
    SlotState,
    default_prefill_buckets,
)
from perceiver_io_tpu.serving.journal import (
    JournalCorruptError,
    JournalSession,
    JournalTornWrite,
    RequestJournal,
    journal_enabled,
    read_journal,
)
from perceiver_io_tpu.serving.metrics import (
    EngineMetrics,
    RouterMetrics,
    load_metrics_jsonl,
)
from perceiver_io_tpu.serving.paging import (
    PagePool,
    PrefixCache,
    page_keys_for_prompt,
    pages_for_request,
    pages_for_tokens,
)
from perceiver_io_tpu.serving.quant import (
    dequantize_params,
    quantize_params_int8,
    serve_params,
)
from perceiver_io_tpu.serving.router import (
    RoutedRequest,
    ServingRouter,
    fleet_ops_enabled,
)
from perceiver_io_tpu.serving.scheduler import SlotScheduler, preemption_enabled
from perceiver_io_tpu.serving.transport import (
    EngineClient,
    FrameError,
    TransportError,
    WorkerDiedError,
    WorkerOpError,
    proc_replicas_enabled,
)

__all__ = [
    "EngineClient",
    "EngineMetrics",
    "FrameError",
    "TransportError",
    "WorkerDiedError",
    "WorkerOpError",
    "proc_replicas_enabled",
    "JournalCorruptError",
    "JournalSession",
    "JournalTornWrite",
    "RequestJournal",
    "journal_enabled",
    "read_journal",
    "PagePool",
    "PrefixCache",
    "dequantize_params",
    "fleet_ops_enabled",
    "page_keys_for_prompt",
    "quantize_params_int8",
    "serve_params",
    "pages_for_request",
    "pages_for_tokens",
    "preemption_enabled",
    "RequestStatus",
    "RoutedRequest",
    "RouterMetrics",
    "ServedRequest",
    "ServingEngine",
    "ServingRouter",
    "SlotScheduler",
    "SlotState",
    "TERMINAL_STATUSES",
    "default_prefill_buckets",
    "load_metrics_jsonl",
]

# Runtime subsystem: resident serving executors and what serves them.
#   executor    -- jit-cached, shape-bucketed three-stage search pipeline (1 device)
#   sharded     -- the same contract over a device mesh (graph > one device)
#   serving     -- streaming micro-batch serve loop with double buffering
#   hostio      -- async host-I/O subsystem (multi-worker neighbour service,
#                  device-resident hot-adjacency cache, prefetched exchange)
#   mutation    -- streaming mutability: live insert/delete + consolidation
#   resilience  -- fault injection + fault handling for the host-I/O tier
#                  (deadlines/retries/hedging, failover, degraded serving)
#   telemetry   -- unified observability: metrics registry + exporters,
#                  request tracing (Chrome trace JSON), per-hop profiling,
#                  fault flight recorder
from .executor import SearchExecutor, SearchHandle, bucket_size, pad_batch  # noqa: F401
from .hostio import HostIOConfig, HostIORuntime, NeighborService  # noqa: F401
from .resilience import (  # noqa: F401
    FaultInjector,
    FaultSpec,
    ResilienceConfig,
)
from .mutation import DeltaGraph, MutableBangIndex, MutableSearchExecutor  # noqa: F401
from .serving import BatchReport, ServePipeline, ServeStats  # noqa: F401
from .sharded import SHARDED_VARIANTS, ShardedSearchExecutor  # noqa: F401
from .telemetry import (  # noqa: F401
    FlightRecorder,
    HopProfiler,
    MetricsRegistry,
    Telemetry,
    Tracer,
)

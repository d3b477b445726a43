from cloud_server_tpu.utils.failure import (  # noqa: F401
    CollectiveWatchdog,
    NaNGuard,
    PreemptionHandler,
    TrainingDiverged,
    Watchdog,
)
from cloud_server_tpu.utils.logging import (  # noqa: F401
    JsonLogger,
    MetricLogger,
    read_jsonl,
)
from cloud_server_tpu.utils.serving_metrics import (  # noqa: F401
    LATENCY_BUCKETS,
    FlightRecorder,
    MetricsRegistry,
    ServingMetrics,
    histogram_percentile,
    histogram_summary,
    merge_snapshots,
    render_prometheus,
)
from cloud_server_tpu.utils.metrics import (  # noqa: F401
    DEVICE_PEAKS,
    MetricAggregator,
    StepTimer,
    device_peaks,
    param_count,
    transformer_flops_per_token,
)
from cloud_server_tpu.utils.tracing import (  # noqa: F401
    annotate,
    capture_trace,
    start_profiler_server,
)

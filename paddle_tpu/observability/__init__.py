"""paddle_tpu.observability — framework-wide runtime telemetry.

A process-global metrics registry (counters / gauges / histograms with
labels, thread-safe snapshot/reset) plus a span tracer unified with
``paddle_tpu.profiler``'s host event recorder. Off by default behind
``FLAGS_observability``; see observability/README.md for the metric naming
scheme and the bench.py field mapping.

    import paddle_tpu
    paddle_tpu.observability.enable()
    ...train / run passes / collectives...
    print(paddle_tpu.observability.summary())
    paddle_tpu.observability.dump_jsonl("/tmp/metrics.jsonl")
"""

from . import (  # noqa: F401
    aggregate,
    anatomy,
    attribution,
    export,
    flight_recorder,
    goodput,
    health,
    instrument,
    memory,
    metrics,
    tracing,
    training,
    xplane,
)
from .aggregate import fleet_report, render_report  # noqa: F401
from .attribution import (  # noqa: F401
    HardwareSpec,
    attribute,
    hardware_for_device,
    site_report,
)
from .export import (  # noqa: F401
    MetricsExporter,
    get_exporter,
    start_exporter,
    stop_exporter,
)
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    get_flight_recorder,
    read_flight,
    record_event,
    start_flight_recorder,
    stop_flight_recorder,
)
from .goodput import GoodputMonitor  # noqa: F401
from .health import (  # noqa: F401
    EwmaDetector,
    HealthConfig,
    HealthMonitor,
    NonfiniteProvenance,
    param_group,
)
from .instrument import record_collective, record_compile  # noqa: F401
from .memory import (  # noqa: F401
    record_device_memory,
    record_executable,
    record_kv_cache,
    record_live_buffers,
)
from .metrics import (  # noqa: F401
    MetricsRegistry,
    counter,
    disable,
    dump_jsonl,
    enable,
    enabled,
    gauge,
    get_registry,
    hist_totals,
    histogram,
    reset,
    snapshot,
    summary,
)
from .tracing import (  # noqa: F401
    add_span_sink,
    clear_spans,
    remove_span_sink,
    set_max_spans,
    span,
    spans,
)
from .training import record_step  # noqa: F401

__all__ = [
    "MetricsRegistry", "enabled", "enable", "disable",
    "counter", "gauge", "histogram", "snapshot", "reset", "get_registry",
    "summary", "dump_jsonl", "hist_totals",
    "span", "spans", "clear_spans",
    "add_span_sink", "remove_span_sink", "set_max_spans",
    "record_collective", "record_compile", "record_step",
    "MetricsExporter", "start_exporter", "stop_exporter", "get_exporter",
    "FlightRecorder", "start_flight_recorder", "stop_flight_recorder",
    "get_flight_recorder", "read_flight", "record_event",
    "record_executable", "record_live_buffers", "record_device_memory",
    "record_kv_cache",
    "GoodputMonitor", "fleet_report", "render_report",
    "HealthMonitor", "HealthConfig", "EwmaDetector", "NonfiniteProvenance",
    "param_group",
    "HardwareSpec", "attribute", "hardware_for_device", "site_report",
]

"""Observability subsystem: tracing, recompile watchdog, unified
metrics registry, training-step profiler, step-program registry.

The measurement substrate under every perf claim in this repo (the
reference's PerformanceListener/StatsStorage pipeline, grown into the
tracing + compile/runtime-attribution subsystem TensorFlow
(arXiv:1605.08695) treats as first-class):

- ``tracing``        nested spans -> JSONL / Chrome trace (Perfetto)
- ``compile_watch``  every trace / lowering / compile of the process
                     by function, persistent-cache loads apart from
                     cold compiles; recompile-storm trip-wire
- ``registry``       process-wide counters/gauges/histograms with
                     Prometheus text exposition
- ``step_profile``   data-wait / dispatch / device decomposition +
                     MFU, riding the standard listener chain
- ``programs``       the step programs this process built, and for
                     each the table from a device op to the
                     ``jax.named_scope`` it was traced under

and (ISSUE 3) the layer that WATCHES the measurements and acts:

- ``health``           HealthMonitor: fused in-step finite check +
                       host sliding-window detectors, with
                       warn/raise/rollback policies
- ``flight_recorder``  bounded event ring -> self-contained
                       post-mortem bundle on anomaly/crash/dump()
- ``alerts``           declarative threshold rules over any registry
                       metric (for-duration + debounce), feeding
                       /healthz and the UI health panel
"""

from deeplearning4j_tpu.observability.alerts import (
    AlertManager, AlertRule,
)
from deeplearning4j_tpu.observability.compile_watch import (
    GlobalCompileStats, RecompileStormError, SteadyStateCompileError,
    install_global_watch,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    FlightRecorder,
)
from deeplearning4j_tpu.observability.health import (
    HealthMonitor, TrainingDivergedError, fused_health,
)
from deeplearning4j_tpu.observability.registry import (
    REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
)
from deeplearning4j_tpu.observability.slo import (
    SLO, BurnWindow, SLOMonitor,
)
from deeplearning4j_tpu.observability.step_profile import (
    ProfilerListener, detect_peak_flops, model_flops_utilization,
    peak_flops_for_kind,
)
from deeplearning4j_tpu.observability.tracing import (
    RequestContext, Sampler, Tracer, current_context, get_tracer,
    startup, trace,
)

__all__ = [
    "AlertManager", "AlertRule", "GlobalCompileStats",
    "FlightRecorder", "HealthMonitor", "RecompileStormError",
    "SteadyStateCompileError",
    "TrainingDivergedError", "fused_health", "install_global_watch",
    "REGISTRY", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "ProfilerListener", "detect_peak_flops",
    "model_flops_utilization", "peak_flops_for_kind", "Tracer",
    "get_tracer", "startup", "trace", "RequestContext", "Sampler",
    "current_context", "SLO", "BurnWindow", "SLOMonitor",
]

"""Observability for the elastic serving stack: structured event tracing
(Chrome trace-event / JSONL export), a Prometheus-style metrics registry,
``jax.profiler`` hooks, the process-wide compile and GC record, and the
live telemetry plane — ring-buffer flight recorder, ``/statusz`` status
server, anomaly watchdog with postmortem capture, and the cost-model
audit. See ``docs/observability.md``."""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.tracer import (CAT_ALLOC, CAT_COMPILE, CAT_ITER, CAT_REQUEST,
                              CAT_SCHED, CAT_SPEC, NULL_TRACER, NullTracer,
                              Tracer, make_tracer, request_tid,
                              validate_chrome_trace)
from repro.obs.ringtrace import DEFAULT_RING_CAPACITY, RingTracer
from repro.obs.statusz import StatusServer
from repro.obs.watchdog import WATCHDOG_RULES, Watchdog
from repro.obs.costaudit import CostModelAudit
from repro.obs import compiles, profiling

__all__ = [
    "CAT_ALLOC", "CAT_COMPILE", "CAT_ITER", "CAT_REQUEST", "CAT_SCHED",
    "CAT_SPEC", "CostModelAudit", "Counter", "DEFAULT_RING_CAPACITY",
    "Gauge", "Histogram", "MetricsRegistry", "NULL_TRACER", "NullTracer",
    "RingTracer", "StatusServer", "Tracer", "WATCHDOG_RULES", "Watchdog",
    "compiles", "make_tracer", "profiling", "request_tid",
    "validate_chrome_trace",
]

"""lodestar_tpu.observability — hot-path tracing + derived metrics.

Public surface:

  - ``trace_span(name, **attrs)`` — context manager AND decorator;
    near-zero when disabled (``LODESTAR_TPU_TRACE`` unset/0); with JAX
    loaded, an enabled span is also a ``jax.profiler`` annotation.
  - ``timed_span(name, (histogram, label))`` — a trace_span whose timing
    also feeds an always-on labelled histogram.
  - ``enabled()`` / ``configure(enabled=, capacity=)`` / ``get_tracer()``
  - ``current_id()`` — explicit parent linking across threads.
  - ``dump_chrome_trace()`` / ``write_chrome_trace(path)`` /
    ``trace_summary()`` — blocking sinks (never call in async bodies
    under network/chain/sync; tpulint enforces this).

``python -m lodestar_tpu.observability`` summarizes or dumps a trace
(from a file, a live node's GET /trace, or this process's ring).
"""

from .tracer import (  # noqa: F401
    SpanRecord,
    Tracer,
    configure,
    current_id,
    enabled,
    get_tracer,
    timed_span,
    trace_span,
)
from .sinks import (  # noqa: F401
    dump_chrome_trace,
    install_registry_sink,
    kernel_compile_snapshot,
    trace_summary,
    write_chrome_trace,
)
from .timeseries import MetricsSampler, TimeSeriesRing  # noqa: F401
from .slo import SloEngine, breach_snapshot  # noqa: F401
from .flight_recorder import (  # noqa: F401
    FlightRecorder,
    list_bundles,
    load_bundle,
)

# every process with tracing gets the /metrics derivation for free
install_registry_sink()

__all__ = [
    "SpanRecord",
    "Tracer",
    "configure",
    "current_id",
    "enabled",
    "get_tracer",
    "timed_span",
    "trace_span",
    "dump_chrome_trace",
    "install_registry_sink",
    "kernel_compile_snapshot",
    "trace_summary",
    "write_chrome_trace",
    "MetricsSampler",
    "TimeSeriesRing",
    "SloEngine",
    "breach_snapshot",
    "FlightRecorder",
    "list_bundles",
    "load_bundle",
]

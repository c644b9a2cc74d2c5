"""repro.obs — deterministic tracing + quantile metrics.

- :mod:`repro.obs.trace`: span/instant recorder stamped in sim time,
  Perfetto ``trace_event`` JSON export, module-level ``TRACE`` no-op
  singleton for siteless call points; ``host_span`` on the profiler's
  clock and ``HostClock``, the one wall-clock seam.
- :mod:`repro.obs.metrics`: counters and exact-quantile histograms
  behind a registry, and the ``MetricSet`` attribute facade.
- :mod:`repro.obs.cli`: the ``repro-trace`` console script
  (export / summarize / diff).
"""
from repro.obs.trace import (  # noqa: F401
    NO_SPAN,
    NULL,
    HostClock,
    NullRecorder,
    TraceRecorder,
    host_span,
    install,
    uninstall,
)
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Histogram,
    MetricSet,
    Registry,
)

"""repro.cluster.telemetry — live observability for the cluster.

Four pieces, all stdlib-only (this package must import without jax, like
the rest of the node-loader bootstrap path):

* :mod:`~repro.cluster.telemetry.registry` — the thread-safe event bus +
  metrics registry every host-side component publishes into, plus the
  JSONL trace writer for offline replay;
* :mod:`~repro.cluster.telemetry.spans` — per-process span recording
  (off by default), from a job's submit to the node's band program, on
  the epoch clock that the JAX profiler's trace shares;
* :mod:`~repro.cluster.telemetry.http` — the ``GET /metrics`` / ``/jobs``
  / ``/nodes`` / ``/events`` status endpoint (JSON + Prometheus text);
* :mod:`~repro.cluster.telemetry.dashboard` — the self-contained HTML
  dashboard served at ``GET /``.

See ARCHITECTURE.md "Observability" for how the host loader, membership
layer, node heartbeats, and service scheduler feed it.
"""

from repro.cluster.telemetry.http import TelemetryServer  # noqa: F401
from repro.cluster.telemetry.registry import (  # noqa: F401
    Histogram,
    Telemetry,
    TraceWriter,
    read_trace,
    total_counts,
)

__all__ = ["Histogram", "Telemetry", "TelemetryServer", "TraceWriter",
           "read_trace", "total_counts"]

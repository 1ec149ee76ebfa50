"""ddl_tpu.obs — end-to-end data-plane tracing over the Metrics seam.

Four pieces (ISSUE 15; the reference had no metrics at all, SURVEY
§5.5 — the rebuild's counter/gauge registry left exactly the blind
spots this layer closes):

- **Window lifecycle spans** (:mod:`~ddl_tpu.obs.spans`): a bounded,
  lock-cheap, zero-cost-when-disarmed :class:`SpanLog` records
  timestamped stage events keyed on each window's integrity-trailer
  identity ``(producer_idx, seq)`` at the pipeline's choke points,
  exportable as Chrome/Perfetto ``trace_event`` JSON with
  thread-per-stage lanes and cross-process flow stitching.
- **Histograms** (:meth:`Metrics.observe` /
  :meth:`Metrics.quantile`, ``ddl_tpu.observability``): fixed
  log-spaced bounded buckets — first-class p50/p99s for window
  latency and admission waits.
- **Cross-process aggregation** (:mod:`~ddl_tpu.obs.aggregate`):
  PROCESS workers ship periodic snapshot + span-delta ObsReports over
  the existing control channel, merged into the consumer registry
  under ``producer.<idx>.*``.
- **Flight recorder** (:mod:`~ddl_tpu.obs.recorder`): a fixed-size
  ring of recent span/metric events, dumped atomically at failure
  sites (integrity corruption, fault trips, preemption notices,
  watchdog failures) — ``python -m ddl_tpu.obs dump <artifact>``
  pretty-prints the post-mortem.

Reference: docs/OBSERVABILITY.md (name families, span model, bucket
layout, aggregation topology, flight-record format, a Perfetto
walkthrough).  An armed stream is byte-identical to a disarmed one
(``tests/test_obs.py::TestThreadE2E::test_arming_never_changes_bytes``);
the armed side's overhead is not measured on the chip.
"""

from __future__ import annotations

from ddl_tpu.obs.aggregate import ReportMerger, build_report, ship_every
from ddl_tpu.obs.recorder import (
    FlightRecorder,
    armed_recorder,
    flight_dump,
)
from ddl_tpu.obs.recorder import armed as flight_armed
from ddl_tpu.obs.spans import (
    STAGES,
    SpanLog,
    chrome_trace,
    tracing,
    write_chrome_trace,
)

__all__ = [
    "FlightRecorder",
    "ReportMerger",
    "STAGES",
    "SpanLog",
    "armed_recorder",
    "build_report",
    "chrome_trace",
    "flight_armed",
    "flight_dump",
    "ship_every",
    "tracing",
    "write_chrome_trace",
]

"""fishnet-tpu observability: one timeline, one metrics surface.

Two modules, both zero-dependency (pure stdlib — no JAX, no numpy at
module scope, same import constraint as utils/settings.py): they are
imported by conftest, the linter, and the engine host child before JAX
initializes, and must never drag device runtime into a process that only
wants to read a trace dump.

- `obs.trace`: thread-safe bounded ring-buffer recorder (span context
  managers, instant events, counter samples on `time.monotonic`),
  exported as Chrome trace-event JSON that loads in Perfetto or
  `chrome://tracing`. The engine host records its own ring and streams
  it to the supervisor over the frames protocol; ClockSync maps the
  child's monotonic clock onto the parent's so the merged file shows
  `queue.acquire` → `supervisor.dispatch` → host `search` spans with the
  scheduler's sessions, boundary phases and fetches under each segment.
- `obs.metrics`: counter/gauge/histogram registry absorbing the ad-hoc
  counters (SupervisorStats, SyncStats totals, LaneScheduler occupancy
  totals), rendered as Prometheus text over an opt-in stdlib-http
  endpoint (FISHNET_TPU_METRICS_PORT) and folded into the sqlite
  StatsRecorder time series.

Tracing is OFF by default: `trace.RECORDER` is None and every
instrumentation site costs one attribute load + one `is None` check —
no events, no allocations, no context managers. See docs/observability.md.
"""
from . import metrics, trace  # noqa: F401

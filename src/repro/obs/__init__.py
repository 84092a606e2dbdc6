"""Observability plane: deterministic tracing, metrics, plan-vs-actual drift.

The paper's promise is *predicted* behavior — the §4 equality split makes
every processor finish together, the LBP byte model says what every link
carries — and this package is how a live run is checked against those
predictions:

  trace.py    ``Tracer``: nested spans + instant events + counter tracks
              against an INJECTABLE clock (engine steps, controller ticks,
              ``ManualClock`` seconds — never the wall clock), with a
              ``NullTracer`` no-op default so hot loops pay one method call;
              ``ProfilerTracer`` puts the same hooks on ``jax.profiler``'s
              clock (the serving engine's default).
  export.py   Chrome-trace/Perfetto JSON exporter (byte-deterministic for
              deterministic runs).
  metrics.py  process-local registry of counters / gauges / fixed-bucket
              histograms — no wall clock in the data path, order-invariant
              histogram merge.
  drift.py    plan-vs-actual: observed finishes or shares scored against a
              ``PartitionPlan``'s predictions; the normalized drift gauge
              is the re-plan trigger signal (ROADMAP item 5).
  clock.py    the ONE sanctioned home of wall-clock reads
              (``time.time``/``time.monotonic`` are CI-grep-gated to this
              package).

Clock-injection policy: every runtime layer times its trace against the
clock it already owns — the serving engine's iteration clock, the fleet
controller's tick counter, a ``ManualClock`` in tests — so two identical
runs export byte-identical traces.  Wall-clock quantities (TTFT and
throughput seconds) stay in the metrics/report plane and are never gated
or traced.
"""

from .clock import monotonic, perf_counter, wall_time  # noqa: F401
from .drift import DriftMonitor, drift_fractions  # noqa: F401
from .export import to_chrome_json, write_chrome_trace  # noqa: F401
from .metrics import (Counter, Gauge, Histogram,  # noqa: F401
                      MetricsRegistry, throughput_summary)
from .trace import NullTracer, ProfilerTracer, Tracer  # noqa: F401

"""Unified host-side telemetry: spans, latency histograms, sentinels.

One event vocabulary — a jsonl stream of one-object-per-line records
tagged by ``"kind"`` — shared by the trainer, checkpointing, eval-in-loop
and the serving engine, and consumed by ``scripts/obs_report.py``:

  kind="span"          tracer.py     timed host-side phase (data_load,
                                     train_step, serving_tick, ...)
  kind="event"         tracer.py     point-in-time marker (divergence,
                                     slo_breach, ...)
  kind="trace_header"  tracer.py     wall-clock epoch of a stream's t=0
                                     (what lets export.py merge streams)
  kind="train"/"val"   utils/metrics MetricsLogger step records
  kind="serving_tick"  utils/metrics ServingMetrics per-tick records
                                     (+ goodput/MFU + live trace ids)
  kind="request"       utils/metrics per-request latency record
                                     (queue-wait, TTFT, ITL histogram,
                                     trace_id)

Request-flow tracing rides the same records: ``context.py`` mints one
trace id per request journey, the serving fabric stamps it everywhere,
``export.py`` merges N streams into one Perfetto-loadable trace with
flow arrows per request, and ``slo.py`` watches rolling-window p95
targets over the finished-request stream.

Everything here is strictly host-side: no device syncs, nothing traced
by jit — enabling telemetry cannot change what XLA compiles (pinned by
tests/test_obs.py trace-count tests).  docs/OBSERVABILITY.md has the
schema and the span names.
"""

from mamba_distributed_tpu.obs.context import mint_trace_id
from mamba_distributed_tpu.obs.export import (
    export_chrome_trace,
    split_pulled_stream,
    to_chrome_trace,
)
from mamba_distributed_tpu.obs.histogram import StreamingHistogram
from mamba_distributed_tpu.obs.slo import SLOMonitor, TickRegressionDetector
from mamba_distributed_tpu.obs.sentinel import (
    DivergenceError,
    DivergenceSentinel,
    FlightRecorder,
)
from mamba_distributed_tpu.obs.tracer import (
    NULL_TRACER,
    AnnotatedTracer,
    SpanTracer,
    annotated,
    append_jsonl,
    jsonable,
)
from mamba_distributed_tpu.obs.watchdog import CompileWatchdog

__all__ = [
    "AnnotatedTracer",
    "CompileWatchdog",
    "DivergenceError",
    "DivergenceSentinel",
    "FlightRecorder",
    "NULL_TRACER",
    "SLOMonitor",
    "SpanTracer",
    "StreamingHistogram",
    "TickRegressionDetector",
    "annotated",
    "append_jsonl",
    "export_chrome_trace",
    "jsonable",
    "mint_trace_id",
    "split_pulled_stream",
    "to_chrome_trace",
]

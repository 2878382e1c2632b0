"""Observability subsystem — structured tracing, metrics, watchdogs.

The reference repo's only window into a 16-process run was grepping raw
per-rank logs after the fact (SURVEY §5.3: `ps_server/log*.log`), and
until this package our reproduction was no better: the train loop, the
PS path, the launcher supervisor, and the serving engine each printed
in their own ad-hoc format.  This package gives every subsystem one
structured, near-zero-overhead vocabulary:

  trace     — JSONL span/event emitter (step, compile, checkpoint
              save/restore, PS push/pull; on the serving path one
              `serve_iteration` a turn of the engine thread, cut into
              named laps, with `serve_batch_form`, `serve_prefill_chunk`,
              `serve_decode`, `serve_close_window` and `clock_anchor`
              under it, each launch's span naming its program and its
              ordinal) with wall time, rank, and step attributes.
              Summarize with
              `python -m dtf_tpu.cli.trace_main <trace_dir>`.
  registry  — counters / gauges / histograms with percentile
              snapshots, exported in the existing BenchmarkMetric
              record format ({"name","value","unit"}) so the benchmark
              infrastructure keeps consuming one shape.
  watchdog  — anomaly detectors wired into the train loop: NaN/Inf
              loss (loud structured abort), step-time regression
              (rolling-median × factor), and heartbeat files the
              launcher supervisor consumes instead of scraping stdout.
  ledger    — always-on MFU/cost accounting: each jitted executable's
              XLA flop/byte counts (pulled at compile time from the
              AOT executable the caller then runs) joined with
              measured wall time into achieved-FLOP/s, MFU, and
              HBM-bandwidth-fraction gauges; summarized by
              `trace_main --ledger`.

Everything is pure Python and off-device: instrumentation runs on the
host at step boundaries only, and every entry point is a no-op when
tracing is not configured (bounded by tests/test_obs.py's <5% overhead
assertion on a smoke-train step).
"""

from dtf_tpu.obs import trace
from dtf_tpu.obs.ledger import Ledger
from dtf_tpu.obs.registry import (Counter, Gauge, Histogram,
                                  MetricsRegistry, default_registry)
from dtf_tpu.obs.watchdog import (Heartbeat, NanLossWatchdog,
                                  ReaderLagWatchdog, StepTimeWatchdog,
                                  TrainingAnomaly)

__all__ = [
    "trace",
    "Counter", "Gauge", "Histogram", "Ledger", "MetricsRegistry",
    "default_registry",
    "Heartbeat", "NanLossWatchdog", "ReaderLagWatchdog",
    "StepTimeWatchdog", "TrainingAnomaly",
]

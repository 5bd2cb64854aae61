"""Unified tracing + metrics pipeline (ISSUE 3 tentpole).

Three legs, one namespace:

- ``trace`` — ring-buffered structured spans with Chrome-trace/Perfetto
  export (``span("p2p/send", bytes=n)``, ``PT_TRACE_DIR``);
- ``stats`` (paddle_tpu.stats) — counters, gauges, timers, and
  log-bucketed histograms (p50/p90/p99) one process-wide registry;
- ``statsz`` — opt-in live HTTP endpoint serving the snapshot
  (``PT_STATSZ_PORT``), scrapeable across a multi-host job.

``init_from_env()`` runs at ``import paddle_tpu`` and activates only
what the env contract asks for — with neither var set, the whole
subsystem stays dormant (one dict lookup per process).
"""

import os

from paddle_tpu.observability import trace
from paddle_tpu.observability.trace import span, complete
from paddle_tpu.observability.statsz import (StatszServer, start_statsz,
                                             stop_statsz)
from paddle_tpu.observability.merge import (merge_trace_files,
                                            merge_rank_traces,
                                            stitch_trace_files,
                                            stitch_rank_traces,
                                            request_segments)
from paddle_tpu.observability import comm
from paddle_tpu.observability.comm import (exposed_time, step_overlap,
                                           record_step_overlap)
from paddle_tpu.observability import flight
from paddle_tpu.observability import runtime
from paddle_tpu.observability import devprof
from paddle_tpu.observability import numerics

__all__ = ["trace", "span", "complete",
           "StatszServer", "start_statsz", "stop_statsz",
           "merge_trace_files", "merge_rank_traces",
           "stitch_trace_files", "stitch_rank_traces",
           "request_segments", "init_from_env",
           "comm", "exposed_time", "step_overlap", "record_step_overlap",
           "flight", "runtime", "devprof", "numerics"]


def init_from_env():
    """Wire tracing (PT_TRACE_DIR / PT_TRACE_FILE) and the statsz
    server (PT_STATSZ_PORT) from the launch env contract. Idempotent;
    errors never break the importing process (observability must not
    take the job down)."""
    trace._init_from_env()
    port = os.environ.get("PT_STATSZ_PORT")
    if port:
        try:
            start_statsz(int(port))
        except (ValueError, OSError):
            pass  # bad/busy port: the job matters more than the endpoint


init_from_env()

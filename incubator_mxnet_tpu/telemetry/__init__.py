"""Runtime telemetry: structured metrics, funnel stage-tracing, span
tracing + flight recorder, SLO tracking, roofline analysis, and a
training-health monitor (see TELEMETRY.md).

Six connected parts:

- `registry`  — process-wide counters/gauges/histograms (lock-free
  thread-shard fast path), `report()`/`dump()`/`exposition()`, built-in
  step/compile/jit-cache/transfer series; ``MXNET_TELEMETRY_DUMP``
  periodic Prometheus-textfile snapshots;
- `stages`    — per-stage µs accounting inside the `apply_op` funnel
  behind the MXNET_TELEMETRY knob (dead branches when off);
- `tracing`   — Dapper-style span tracer (trace/correlation IDs, ambient
  context, per-thread rings) threaded through serve requests, estimator
  steps, dataloader fetches, kvstore syncs, and checkpoint I/O; flight
  recorder dumping the last spans on crash/injected fault; Chrome-trace
  export in epoch µs beside the profiler's rebased lanes (same off-path
  dead-branch discipline as `stages`); the always-on phase clock of the
  serving loop (`mx.serve.*` profiler spans, step and request records);
- `slo`       — declarative objectives over registry series with
  error-budget burn as ``mx_slo_*`` gauges and a loud `monitor.check()`
  hook;
- `roofline`  — post-process the profiler's XPlane device trace into
  per-phase bytes vs time vs peak-HBM-bandwidth tables;
- `monitor`   — reference-parity `Monitor` (per-tensor health stats,
  batched host sync), `install_nan_hook()` non-finite guard (eager +
  compiled via jax.debug.callback), per-rank aggregation at kvstore sync
  points, pluggable health checks, and the estimator `TelemetryHandler`;
- `compiles`  — per-program XLA compile ledger (cost/memory analysis,
  HLO fingerprints) with recompile forensics naming the offending
  argument (``mx_jit_recompiles_total{program=,cause=}``);
- `hbm`       — subsystem-attributed live-buffer census over
  ``jax.live_arrays()``, growth watchdog (``MXNET_MEMWATCH_INTERVAL``),
  and the RESOURCE_EXHAUSTED post-mortem (``MXNET_OOM_POSTMORTEM``);
- `fleet`     — the cross-rank plane: collective profiler over
  `parallel/dist.py` + `parallel/collectives.py` (``mx_collective_*``,
  barrier-arrival skew), `fleet_report()` per-rank/aggregate registry
  views with a straggler z-score, clock-offset estimation + stitched
  multi-rank timelines (``tools/trace_timeline.py --fleet``), and the
  crash-fanout flight recorder merged by ``tools/fleetwatch.py``;
- `kernels`   — per-HLO kernel census over the profiler's device trace,
  roofline placement per kernel (``bound_by`` with honest unknown-bytes
  coverage), compile-ledger join, and `diff_census` fusion forensics
  (``mx_kernel_fusion_delta``; rendered by ``tools/kernelscope.py``);
- `goodput`   — training goodput ledger attributing every wall second to
  compute / data_wait / checkpoint / reshard / drain / recovery / idle
  via `lease()` seams in the estimator, dataloader, checkpointer, and
  `ElasticController` (``mx_goodput_seconds_total{state=}``,
  ``mx_goodput_frac``; fleet-aggregated in `fleet_report()`);
- `timeseries` — opt-in ring-buffer history over every registry series
  (``MXNET_TS_INTERVAL``/``MXNET_TS_SAMPLES``) with windowed queries
  (`rate`/`delta`/`percentile_over_time`/`window_frac`) — the signal
  layer the burn-rate alerter and autoscale advisor read;
- `burnrate`  — SRE-style multi-window multi-burn-rate alerts over the
  SLO burn gauges (``mx_alert_firing{alert=}``, hysteresis so steady
  traces never flap; ``MXNET_BURN_WINDOWS``);
- `capacity`  — per-tenant/per-model cost ledger at the serving seams
  (tokens, prefill/decode device-seconds, KV page-seconds, queue-wait
  as ``mx_capacity_*``; rolled up in `fleet_report()`);
- `anatomy`   — per-request latency anatomy (request wall decomposed
  into queue_wait / preempted / prefill_wait / prefill_compute /
  handoff_migration / decode_compute / spec_overhead, sum-to-wall per
  request), per-replica role residency
  (``mx_replica_residency_seconds_total{replica=,role=,state=}``), and
  the tail-sampled request archive (``MXNET_ANATOMY_SAMPLE`` /
  ``MXNET_ANATOMY_RING``; rendered by ``tools/reqscope.py``).

Env knobs (registered in `util._ENV_KNOBS`): ``MXNET_TELEMETRY``
(``1`` = stage + span tracing on, ``raise`` = + NaN guard raising at the
first non-finite output, ``0``/unset = off — zero per-op cost),
``MXNET_TELEMETRY_INTERVAL`` (batches between estimator registry logs),
``MXNET_TELEMETRY_DUMP=<path>[:interval_s]`` (periodic exposition
snapshots for node-exporter textfile scraping).
"""
from __future__ import annotations

from . import locks  # noqa: F401  (first: tracked_lock feeds the rest)
from . import registry  # noqa: F401
from . import roofline  # noqa: F401
from . import stages  # noqa: F401
from . import tracing  # noqa: F401
from . import slo  # noqa: F401
from . import monitor  # noqa: F401
from . import compiles  # noqa: F401
from . import hbm  # noqa: F401
from . import fleet  # noqa: F401
from . import kernels  # noqa: F401
from . import goodput  # noqa: F401
from . import timeseries  # noqa: F401
from . import burnrate  # noqa: F401
from . import capacity  # noqa: F401
from . import anatomy  # noqa: F401
from .monitor import Monitor, install_nan_hook  # noqa: F401

# arm the host->device byte inlet (a counter inc per transfer — rare
# events, so always on once telemetry is imported)
from ..ndarray import ndarray as _nd_mod

_nd_mod._H2D_HOOK = registry.add_h2d_bytes

__all__ = ["registry", "stages", "tracing", "slo", "roofline", "monitor",
           "compiles", "hbm", "fleet", "kernels", "goodput", "locks",
           "timeseries", "burnrate", "capacity", "anatomy",
           "Monitor", "install_nan_hook"]

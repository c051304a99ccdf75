"""TPU-native continuous-batching inference serving (see SERVING.md).

The framework's decode story before this subsystem was a single-job
loop: one fixed batch, all requests starting and stopping together
(`bench_gpt_decode`). Real serving is the opposite — requests arrive and
finish at different times — and the known technique is continuous
(iteration-level) batching with paged KV-cache management (Orca,
OSDI '22; vLLM/PagedAttention, SOSP '23), adapted here to the TPU
constraint that XLA programs are fixed-shape: instead of dynamic
tensors, ONE compiled decode program stays alive and requests map their
token ranges onto pool pages through a static-shape page table.

Three connected parts:

- `engine`    — :class:`SlotDecoder`: the persistent paged device pool
  ``(L, n_pages, H, page_tokens, d)``, the host-side
  :class:`PageAllocator` (refcounts, loud :class:`PagePoolExhausted`)
  and :class:`PrefixCache` (shared system prompts prefilled once), and
  the two compiled program families against the pool (page-aligned
  chunked prefill, batched gather-by-page-table decode), both with
  donated buffers — zero steady-state recompiles and no per-step
  allocation. Optional int8 KV storage
  (``MXNET_SERVE_KV_DTYPE=int8``) halves resident KV bytes per slot;
- `eva`       — :class:`EvaSlotDecoder`: the same pool and allocator for
  the EvaByte family, a slot's pages of two kinds (the exact rows of its
  current window, one summary row per finished chunk of every window
  before) and the roll between them;
- `scheduler` — :class:`Scheduler`: bounded admission queue (FIFO or
  remaining-chunk SJF), loud :class:`QueueFull` backpressure,
  per-request deadlines (:class:`DeadlineExceeded`, retryable under
  `fault.retry.classify_exception`), and the ``step()`` loop that
  interleaves prefill CHUNKS of waiting requests with decode of running
  slots, retiring slots on EOS/length mid-flight;
- `api`       — :class:`ServeEngine`: thread-safe blocking
  ``generate``, streaming ``submit``/``iter_tokens``, batch
  ``generate_many``, background driver thread, graceful
  ``shutdown(drain=True)``;
- `tenancy` + `gateway` — the multi-tenant front door:
  :class:`ModelRegistry` (co-resident models sharing one HBM page
  budget) behind :class:`Gateway` — priority-tiered admission (higher
  tiers preempt lower-tier running slots, preempted work resumes warm
  off its cached KV pages), per-tenant token-rate quotas and weighted
  deficit-round-robin fairness (`TokenBucket`, `WDRRQueue`), driven
  against recorded traces by `tools/loadgen.py`;
- `sharded` + `router` — pod-scale: :class:`ServeLayout` partition
  rules place a :class:`ShardedSlotDecoder`'s params and per-layer KV
  pools onto a device mesh (heads-sharded attention pools, Megatron
  fsdp×tp matmuls, every single-chip invariant preserved), and
  ``ModelRegistry.add(..., replicas=N, mesh=...)`` fronts N replica
  engines behind :class:`ReplicaRouter` least-loaded + prefix-affinity
  dispatch with drain-free `Gateway.hot_swap` weight rolls
  (SERVING.md §pod-scale);
- `disagg`    — disaggregated prefill/decode serving (SERVING.md
  §disaggregation): ``ModelRegistry.add(..., prefill_replicas=,
  decode_replicas=)`` splits a pod into compute-bound prefill replicas
  and bandwidth-bound decode replicas; a finished prefill's KV pages
  migrate as a content-addressed `PrefixCache` fill (refcounts handed
  off, ``mx_serve_page_migration_{pages,bytes}_total`` accounted) and
  the request is adopted mid-decode on the far side — decode replicas
  never compile a prefill program (compile-ledger gated), with
  rollback to co-located serving when the handoff faults
  (``page_migration`` seam) or the decode side is page-exhausted;
- `elastic`   — the closed loop over the capacity observatory:
  :class:`ReplicaSetController` (armed by ``MXNET_ELASTIC_SERVE``)
  consumes `AutoscaleAdvisor` recommendations and resizes the LIVE
  replica set — scale-up spawns, warms (both program families, zero
  cold compiles on the request path) and publishes a new replica on a
  rebalanced page budget; scale-down drains and retires; a replica
  killed by the ``replica_crash`` chaos seam is replaced with its
  in-flight work re-queued (zero failed requests); a fault mid-spawn
  (``replica_spawn`` seam) rolls back to exactly N replicas
  (SERVING.md §elastic replicas, RESILIENCE.md §8).

Observability and chaos ride the existing subsystems: the registry
carries ``mx_serve_ttft_seconds``, ``mx_serve_tokens_total``,
``mx_serve_queue_depth``, ``mx_serve_slot_occupancy``,
``mx_serve_page_occupancy``, ``mx_serve_prefix_hits_total``,
``mx_serve_prefill_chunks_total``, ``mx_serve_evictions_total``
(``reason="preempted"`` included), the gateway's ``model``/``tenant``/
``priority``-labeled views of TTFT and tokens, and
``mx_gateway_queue_depth{priority=}``; `MXNET_FAULT_INJECT` has the
``serve_step`` and ``gateway_step`` seams. Env knobs:
``MXNET_SERVE_MAX_QUEUE``, ``MXNET_SERVE_POLICY``,
``MXNET_SERVE_DEADLINE_S``, ``MXNET_SERVE_PAGE_TOKENS``,
``MXNET_SERVE_PREFILL_CHUNK``, ``MXNET_SERVE_KV_DTYPE``,
``MXNET_SERVE_PRIORITY_TIERS``, ``MXNET_SERVE_TENANT_QUOTA``,
``MXNET_GATEWAY_MAX_QUEUE``, ``MXNET_GATEWAY_QUANTUM``,
``MXNET_GATEWAY_PREEMPT``, ``MXNET_SERVE_MESH``,
``MXNET_SERVE_REPLICAS``, ``MXNET_SERVE_AFFINITY``.

Typical use::

    import incubator_mxnet_tpu as mx

    engine = mx.serve.ServeEngine(model, max_slots=8).start()
    h = engine.submit(prompt_ids, max_new_tokens=128)
    for tok in engine.iter_tokens(h):
        ...
    engine.shutdown(drain=True)
"""
from __future__ import annotations

from . import api  # noqa: F401
from . import disagg  # noqa: F401
from . import elastic  # noqa: F401
from . import engine  # noqa: F401
from . import eva  # noqa: F401
from . import gateway  # noqa: F401
from . import mla  # noqa: F401
from . import router  # noqa: F401
from . import scheduler  # noqa: F401
from . import sharded  # noqa: F401
from . import ssm  # noqa: F401
from . import tenancy  # noqa: F401
from .api import ServeEngine  # noqa: F401
from .disagg import MigrationAborted  # noqa: F401
from .elastic import ReplicaScaleError, ReplicaSetController  # noqa: F401
from .engine import (PageAllocator, PagePoolExhausted,  # noqa: F401
                     PrefixCache, SlotDecoder)
from .eva import EvaSlotDecoder  # noqa: F401
from .mla import MLASlotDecoder  # noqa: F401
from .ssm import HybridSlotDecoder  # noqa: F401
from .gateway import Gateway, GatewayRequest, ModelRegistry  # noqa: F401
from .router import ReplicaRouter, replica_meshes  # noqa: F401
from .scheduler import (DeadlineExceeded, EngineClosed,  # noqa: F401
                        QueueFull, Request, Scheduler)
from .sharded import (ServeLayout, ShardedSlotDecoder,  # noqa: F401
                      serve_mesh)
from .tenancy import Tenant, TokenBucket, WDRRQueue  # noqa: F401

__all__ = ["ServeEngine", "SlotDecoder", "EvaSlotDecoder", "MLASlotDecoder",
           "HybridSlotDecoder", "Scheduler",
           "Request",
           "PageAllocator", "PrefixCache", "PagePoolExhausted",
           "QueueFull", "DeadlineExceeded", "EngineClosed",
           "Gateway", "GatewayRequest", "ModelRegistry",
           "ServeLayout", "ShardedSlotDecoder", "ReplicaRouter",
           "serve_mesh", "replica_meshes",
           "ReplicaSetController", "ReplicaScaleError",
           "MigrationAborted",
           "Tenant", "TokenBucket", "WDRRQueue",
           "api", "disagg", "elastic", "engine", "eva", "gateway", "mla", "router",
           "scheduler", "sharded", "ssm", "tenancy"]

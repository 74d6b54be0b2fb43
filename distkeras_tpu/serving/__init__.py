"""Online serving: a continuous-batching decode engine over the KV-cache
generation stack (:mod:`distkeras_tpu.inference.generate`).

The reference's inference surface is batch-transform only
(``distkeras/predictors.py``: map a fixed model over a DataFrame); this
package closes the ROADMAP's "serve heavy traffic" gap with an online
request path:

- :class:`ServingEngine` — fixed-slot continuous batching: one compiled
  decode step for the lifetime of the server, requests admitted into free
  slots mid-decode (no retrace, no drain), optionally with **chunked
  prefill** (``prefill_chunk``: long prompts admit one bounded chunk per
  decode tick);
- :class:`KVBlockPool` — the engine's one KV cache: decode slots
  allocate fixed-size KV blocks from ONE pool through per-slot block
  tables, and the same blocks, keyed by a radix trie over prompt
  prefixes (ref-counted, LRU-evicted), are the prefix cache — a hit is
  zero-copy shared blocks. Sized by ``ServingEngine(kv_pool_mb=...)``
  (or a whole context per slot when no budget is given); a budgeted
  pool may be oversubscribed (preempt-and-requeue, typed ``kv_oom``
  rejects past capacity), and long-context requests chain blocks up to
  the trained context;
- :class:`Scheduler` / :class:`Request` — priority-FIFO admission with
  max-depth backpressure, per-request deadlines, and bounded
  cache-aware reordering within a priority class;
- :class:`ServingServer` / :class:`ServingClient` — asyncio TCP front end
  with newline-delimited-JSON streaming token output;
- :class:`ServingMetrics` — TTFT / inter-token latency / occupancy
  percentiles through :class:`distkeras_tpu.tracing.MetricStream`;
- :mod:`distkeras_tpu.serving.cluster` — multi-replica serving:
  :class:`ServingCluster` (= :class:`Router` front port +
  :class:`ReplicaSupervisor` restarts) with prefix-cache-affine routing,
  zero-streamed retry on replica death, and zero-downtime rolling weight
  reloads;
- :mod:`distkeras_tpu.serving.kv_transfer` — KV block migration: a
  prompt's pool blocks serialized (bitwise, provenance-stamped) and
  adopted into a peer replica's pool, the primitive behind
  **disaggregated prefill/decode fleets** (``run.py cluster --roles
  prefill=N,decode=M``), cross-replica prefix sharing, and
  drain-by-migration rolling reloads (typed
  :class:`KVTransferError` rejects; every failure falls back to
  monolithic serving).
"""

from distkeras_tpu.serving.scheduler import (
    EngineStopped,
    PoolExhausted,
    QueueFullError,
    Request,
    RequestCancelled,
    RequestTimeout,
    Scheduler,
    ServingError,
    TenantOverQuota,
    TenantQuota,
)
from distkeras_tpu.serving.kv_transfer import KVTransferError
from distkeras_tpu.serving.metrics import ServingMetrics
from distkeras_tpu.serving.slo import (
    Objective,
    SLOEngine,
    default_objectives,
)
from distkeras_tpu.serving.prefix_cache import KVBlockPool
from distkeras_tpu.serving.engine import ServingEngine
from distkeras_tpu.serving.server import ServingServer
from distkeras_tpu.serving.client import ServingClient
from distkeras_tpu.serving.cluster import (
    LocalReplica,
    ProcessReplica,
    ReplicaSupervisor,
    Router,
    ServingCluster,
)

__all__ = [
    "ServingEngine",
    "ServingCluster",
    "Router",
    "ReplicaSupervisor",
    "LocalReplica",
    "ProcessReplica",
    "KVBlockPool",
    "PoolExhausted",
    "Scheduler",
    "Request",
    "ServingServer",
    "ServingClient",
    "ServingMetrics",
    "ServingError",
    "QueueFullError",
    "RequestTimeout",
    "RequestCancelled",
    "EngineStopped",
    "TenantOverQuota",
    "TenantQuota",
    "KVTransferError",
    "SLOEngine",
    "Objective",
    "default_objectives",
]

"""mxnet_tpu.serving — in-process dynamic-batching inference service.

The online half of the framework (ROADMAP north star: "serves heavy
traffic from millions of users"), built on two substrates this repo
already has: the process-wide executor program cache (one compiled
program per graph x batch-bucket, so dynamic batching amortizes
compilation exactly the way BucketingModule does for training) and the
runtime telemetry registry (latency histograms, rejection counters,
queue gauges — scrape ``/metrics`` or snapshot in-process).

Pieces (each its own module, composable without :class:`Server`):

- :class:`ModelRegistry` / :class:`ServedModel` — checkpoints loaded
  into bound predict executors, one per batch-size bucket
  (``registry.py``);
- :class:`AdmissionController` — bounded queue, per-request deadlines,
  typed backpressure (``admission.py``);
- :class:`DynamicBatcher` — pad/concat to power-of-two buckets, split
  results per request, crash-proof dispatch thread (``batcher.py``);
- :class:`Server` — futures API (``submit``/``submit_async``),
  ``warmup()`` with zero-recompile verification, optional stdlib HTTP
  endpoint, graceful drain (``server.py``);
- :class:`FleetServer` / :class:`ReplicaGroup` / :class:`Router` — the
  fleet tier: N replicas behind the shared admission queue, weighted
  least-loaded dispatch, per-replica health with quarantine-and-drain
  (``router.py``, docs/serving.md §fleet);
- :class:`ContinuousBatcher` — slot-based continuous batching for
  stateful/recurrent decode: fixed slot count, per-slot state (a
  pytree of carries) carried on device, streams join/leave without
  retracing (``continuous.py``);
- :class:`KVBlockPool` / :class:`PagedTransformerDecoder` — the
  paged-KV tier for autoregressive transformer decode: device-resident
  page pool with slot -> page-table indirection, prefix-cache reuse
  with copy-on-write, memprof-accounted footprint (``kv_cache.py``,
  ``decode.py``, docs/serving.md §paged-KV);
- typed rejections (``errors.py``), instrument names (``metrics.py``).

See docs/serving.md for the architecture and the bucket/warmup/
rejection contracts; ``tests/test_serving.py`` is the executable
spec.
"""
from __future__ import annotations

from .admission import (AdmissionController, Request, default_deadline_ms,
                        default_queue_depth)
from .batcher import DynamicBatcher
from .continuous import (ContinuousBatcher, DecodeStream, SlotScheduler,
                         default_slot_count)
from .decode import PagedDecodeStream, PagedTransformerDecoder
from .errors import (BadRequest, DeadlineExceeded, ModelNotFound,
                     NoHealthyReplica, Overloaded, RequestTooLarge,
                     ServerClosed, ServingError)
from .kv_cache import (KVBlockPool, default_page_tokens,
                       default_pool_pages, page_chain_hash)
from .registry import ModelRegistry, ServedModel, bucket_for, bucket_sizes
from .router import FleetServer, Replica, ReplicaGroup, Router, \
    default_replicas
from .server import Server

__all__ = [
    "AdmissionController", "BadRequest", "ContinuousBatcher",
    "DeadlineExceeded", "DecodeStream", "DynamicBatcher", "FleetServer",
    "KVBlockPool", "ModelNotFound", "ModelRegistry", "NoHealthyReplica",
    "Overloaded", "PagedDecodeStream", "PagedTransformerDecoder",
    "Replica", "ReplicaGroup", "Request", "RequestTooLarge", "Router",
    "ServedModel", "Server", "ServerClosed", "ServingError",
    "SlotScheduler", "bucket_for", "bucket_sizes", "default_deadline_ms",
    "default_page_tokens", "default_pool_pages", "default_queue_depth",
    "default_replicas", "default_slot_count", "page_chain_hash",
]

"""High-throughput native input pipeline (ref: src/io's layered iterator
stack — IIterator<DataBatch>, ImageRecordIter2's threaded decode,
dmlc::ThreadedIter prefetch — rebuilt for a TPU host).

The legacy ``DataIter`` protocol is single-threaded pull; at chip-rate
consumption (PR 5: 3,045 img/s resnet50 train) it becomes the step-time
ceiling the ``data_wait`` telemetry measures.  This subsystem turns it
into a real pipeline:

- **multi-worker prefetch executor** (`executor.PrefetchExecutor`):
  thread pool by default, spawn-process pool for GIL-bound decode, with
  a **bounded reorder buffer** so the batch sequence is bitwise-
  deterministic for a fixed seed whatever the worker count;
- **sharded record sources** (`stages.RecordFileSource` over
  ``MXIndexedRecordIO``): one random-access reader handle per worker,
  balanced ``num_parts`` sharding that covers every record exactly once;
- **composable stages** (source -> decode/augment -> batch -> prefetch,
  mirroring iter_prefetcher.h's layering): decode/augment runs off the
  driving thread, seeded per record (`sharding.record_seed`);
- **double-buffered device transfer** (`device.DeviceTransfer` + the
  adapter's one-batch lookahead): the H2D ``device_put`` of batch N is
  issued while step N-1 computes, preserving the fit-loop overlap
  contract;
- **DataIter adapter** (`adapter.PipelineDataIter`): ``Module.fit``,
  ``BucketingModule`` and the scoring loops consume the pipeline
  unchanged (``fit`` even accepts the Pipeline directly).

Everything is host-side: the pipeline adds ZERO program retraces.
Knobs: ``MXNET_TPU_IO_WORKERS``,
``MXNET_TPU_IO_PREFETCH_DEPTH``, ``MXNET_TPU_IO_DOUBLE_BUFFER``
(docs/env_vars.md); guide: docs/io_pipeline.md.
"""
from .adapter import PipelineDataIter
from .device import DeviceTransfer, double_buffer_enabled
from .executor import (PipelineClosed, PrefetchExecutor, ReorderBuffer,
                       default_num_workers, default_prefetch_depth)
from .pipeline import Pipeline
from .sharding import (BatchTask, epoch_order, epoch_plan, epoch_seed,
                       record_seed, shard_records)
from .stages import (HostBatch, ImageRecordDecoder, ListSource,
                     NDArrayRecordDecoder, RecordFileSource,
                     assemble_batch, decode_task)

__all__ = [
    "Pipeline", "PipelineDataIter", "PrefetchExecutor", "ReorderBuffer",
    "PipelineClosed", "RecordFileSource", "ListSource",
    "ImageRecordDecoder", "NDArrayRecordDecoder", "HostBatch",
    "BatchTask", "DeviceTransfer", "assemble_batch", "decode_task",
    "epoch_order", "epoch_plan", "epoch_seed", "record_seed",
    "shard_records", "default_num_workers", "default_prefetch_depth",
    "double_buffer_enabled",
]

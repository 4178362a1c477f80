"""Streaming scan pipeline: overlap DB paging, file I/O, device dispatch,
and commit across job steps.

PR 2 proved the kernel; BENCH_r05 proved the kernel alone does not move
``scan_e2e_files_per_sec`` — every step of a batched job ran strictly
sequentially (SELECT → gather → hash → transaction), so the device's
overlap inside one hash call died at each step boundary. "GPUs as
Storage System Accelerators" (arxiv 1202.3669) and SEDD (arxiv 2501.01046)
both find that accelerator storage pipelines only win when I/O, transfer and
compute overlap *end-to-end*; this package is that layer.

A batched job opts in by returning a :class:`PipelineSpec` from
``StatefulJob.pipeline_spec()``. The spec names three stage callables that
the :class:`PipelineExecutor` runs on dedicated threads connected by bounded
queues (depth ``SD_PIPELINE_DEPTH``, default 2):

- **prefetcher** — ``pipeline_page``: pages the next step's rows and gathers
  sample messages (file I/O) while the current batch is hashing. Reads only;
  the ``pipeline-ordering`` sdlint pass rejects DB writes here. With
  ``SD_SCAN_SHARDS`` > 1 and a spec that provides ``split``/``shard``/
  ``merge`` callables, this stage fans each cursor page across parallel
  gather shard workers and an ordered ticket merger (the
  ``IngestLanes.submit`` shape) re-serializes them, so the dispatcher still
  sees exactly the sequential page stream.
- **dispatcher** — ``pipeline_process``: device/CPU compute. Bounded queues
  keep it fed so ≥2 hash batches are enqueued against jax's async dispatch
  (the sampled row pipeline's internal double-buffering supplies the
  in-flight depth per call).
- **committer** — ``pipeline_commit``: runs on the job's own thread, in
  strict batch order, and is the ONLY stage allowed to write the DB. Commit
  of batch N overlaps hashing of batch N+1 and paging of batch N+2.

Ordering invariants (see docs/architecture/scan-pipeline.md):

1. Commits are strictly ordered by batch sequence; the checkpoint cursor in
   ``data`` is only advanced by the committer, so a pause/crash resumes at
   the last *committed* batch — byte-identical to the sequential path.
2. CRDT ops are emitted inside commit, in the same per-row order as the
   sequential path, so the sync op-log is byte-identical too.
3. Pause/cancel/shutdown drain cleanly: speculative pages and in-flight
   hashes are discarded, never committed out of order.
"""

from .executor import (PipelineExecutor, pipeline_depth, pipeline_enabled,
                       scan_shards)
from .spec import PipelineSpec

__all__ = ["PipelineExecutor", "PipelineSpec", "pipeline_depth",
           "pipeline_enabled", "scan_shards"]

"""Elastic training: preemption-safe checkpoint/resume + fault injection.

The reference framework's ps-lite layer treats worker death as a normal
event (heartbeats, ``is_recovery`` re-joins, dead-node listing —
SURVEY.md §5.3, dmlc-core/tracker).  On a TPU pod the analogue is
checkpoint-based: preemption is the COMMON case at fleet scale, so the
framework owns three pieces:

- ``checkpoint.Checkpointer`` — atomic, sha256-manifested, last-K full
  state snapshots (params, optimizer state, data-iterator position,
  step counter, flight-recorder lineage) on a step schedule
  (``MXNET_TPU_CKPT_STEPS``), on health-monitor anomaly (black box
  first, then the snapshot), and on SIGTERM with a bounded-drain
  deadline;
- ``resume.resume`` / ``resume.resume_fit`` — restore into a possibly
  *re-factorized* mesh (surviving-worker count != original) and
  warm-boot compiled programs from the shared
  ``MXNET_TPU_PROGRAM_CACHE_DIR`` volume;
- ``chaos`` — declarative fault plans (kill-at-step,
  checkpoint-corrupt, write-stall) that prove resumed runs match
  uninterrupted ones (``tests/test_elastic.py``).

The epoch-granular legacy surface (``latest_checkpoint``,
``fit_elastic`` — resume-from-latest ``prefix-%04d.params``) lives on in
``legacy.py`` unchanged.  See docs/elastic.md.
"""
from __future__ import annotations

from .legacy import (dead_nodes, fit_elastic, latest_checkpoint,
                     resume_epoch)
from .checkpoint import (Checkpointer, PreemptedError, Snapshot,
                         SnapshotError)
from .resume import ResumeReport, resume, resume_fit
from . import chaos

__all__ = [
    # legacy epoch-granular surface
    "dead_nodes", "latest_checkpoint", "resume_epoch", "fit_elastic",
    # step-granular preemption-safe surface
    "Checkpointer", "Snapshot", "SnapshotError", "PreemptedError",
    "ResumeReport", "resume", "resume_fit", "chaos",
]

"""Step-granular preemption-safe checkpoints.

One snapshot is one directory ``<ckpt_dir>/snap-<step>/`` holding the
FULL training state:

- ``params.ndarray`` — arg + aux params (``Module.save_params`` format);
- ``optimizer.states`` — optimizer state via
  ``Module.save_optimizer_states``;
- ``manifest.json`` — step/epoch/batch counters, the data-iterator
  position (the io_pipeline determinism root: a pure ``(seed, epoch,
  position)`` tuple reproduces the batch stream on resume), bound
  data/label shapes (so ``resume`` can bind without the iterator), the
  device count of the writing mesh, flight-recorder lineage, and a
  sha256 + byte count per artifact.

Write protocol (the ``_build_rec_index`` contract, directory form):
artifacts land in a pid+counter-suffixed temp directory, the manifest
is written LAST, and one ``os.rename`` commits the snapshot — a reader
either sees a complete manifested directory or nothing.  Artifact
writes retry under capped exponential backoff; a snapshot that still
fails to verify at read time (truncated file, flipped bytes, missing
manifest) is skipped with a warning in favor of the previous one.

Triggers (``Checkpointer.attach`` + the fit loop's per-step hook):

- **schedule** — every ``MXNET_TPU_CKPT_STEPS`` completed steps;
- **anomaly** — a health-monitor rule fired; ordering is black box
  first: the monitor writes its flight dump, THEN the checkpoint (for
  ``raise`` actions the snapshot is written from ``fit``'s unwind,
  after ``TrainingDivergedError`` carried the dump path);
- **preempt** — SIGTERM/SIGINT: the handler only sets a flag; the next
  step boundary drains the in-flight step and snapshots within the
  bounded drain deadline, then raises :class:`PreemptedError` so the
  launcher restarts the worker.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import signal
import threading

from .. import threads as _threads
import time

from ..base import MXNetError
from ..log import module_logger as _module_logger
from ..observability import flight_recorder as _flight
from ..observability import telemetry as _telemetry

DIR_ENV = "MXNET_TPU_CKPT_DIR"
STEPS_ENV = "MXNET_TPU_CKPT_STEPS"
KEEP_ENV = "MXNET_TPU_CKPT_KEEP"

SNAP_PREFIX = "snap-"
MANIFEST_NAME = "manifest.json"
PARAMS_FILE = "params.ndarray"
STATES_FILE = "optimizer.states"

DEFAULT_KEEP = 3
DEFAULT_DRAIN_S = 30.0
WRITE_ATTEMPTS = 4
BACKOFF_BASE_S = 0.1
BACKOFF_CAP_S = 2.0

_log = _module_logger(__name__)
_tmp_counter = [0]
_tmp_lock = _threads.package_lock("checkpoint._tmp_lock")


def _int_env(name, default):
    raw = os.environ.get(name, "").strip()
    if not raw:
        return default
    try:
        return int(raw)
    except ValueError:
        _log.warning("ignoring malformed %s=%r (want an integer); "
                     "using %s", name, raw, default)
        return default


class SnapshotError(MXNetError):
    """A snapshot could not be written or no usable one could be read."""


class PreemptedError(MXNetError):
    """Training was preempted (SIGTERM/SIGINT): the final snapshot is on
    disk (``.snapshot_path``, None when the drain deadline expired
    before a step boundary) and the launcher should restart the worker,
    which resumes via :func:`mxnet_tpu.elastic.resume`."""

    def __init__(self, message, step=None, snapshot_path=None):
        super().__init__(message)
        self.step = step
        self.snapshot_path = snapshot_path


def _sha256_file(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _desc_list(descs):
    if not descs:
        return None
    import numpy as np
    return [{"name": d.name, "shape": list(d.shape),
             "dtype": str(np.dtype(getattr(d, "dtype", "float32"))),
             "layout": getattr(d, "layout", None)} for d in descs]


class Snapshot:
    """Read-side handle over one manifested snapshot directory."""

    def __init__(self, directory, manifest):
        self.directory = directory
        self.manifest = manifest

    @classmethod
    def open(cls, directory):
        path = os.path.join(directory, MANIFEST_NAME)
        try:
            with open(path) as f:
                manifest = json.load(f)
        except (OSError, ValueError) as exc:
            raise SnapshotError("unreadable snapshot manifest %s (%s)"
                                % (path, exc)) from exc
        if manifest.get("kind") != "mxnet_tpu_snapshot":
            raise SnapshotError("%s is not a snapshot manifest" % path)
        return cls(directory, manifest)

    @property
    def step(self):
        return int(self.manifest.get("step", -1))

    @property
    def epoch(self):
        return int(self.manifest.get("epoch", 0))

    @property
    def reason(self):
        return self.manifest.get("reason", "?")

    @property
    def n_dev(self):
        return self.manifest.get("n_dev")

    @property
    def data_position(self):
        return self.manifest.get("data_position") or {}

    def artifact(self, name):
        return os.path.join(self.directory, name)

    def verify(self):
        """Problems with this snapshot's artifacts (empty list = every
        manifested file present, right size, right sha256)."""
        problems = []
        for name, meta in (self.manifest.get("files") or {}).items():
            path = self.artifact(name)
            if not os.path.exists(path):
                problems.append("%s: missing" % name)
                continue
            size = os.path.getsize(path)
            if size != meta.get("bytes"):
                problems.append("%s: %d bytes, manifest says %s"
                                % (name, size, meta.get("bytes")))
                continue
            if _sha256_file(path) != meta.get("sha256"):
                problems.append("%s: sha256 mismatch" % name)
        return problems

    def load_params(self):
        """``(arg_params, aux_params)`` NDArray dicts from the params
        artifact (``save_params``'s ``arg:``/``aux:`` key format)."""
        from ..ndarray import load
        split = {"arg": {}, "aux": {}}
        for key, value in load(self.artifact(PARAMS_FILE)).items():
            kind, _, name = key.partition(":")
            if kind not in split or not name:
                raise SnapshotError("%s holds a non-param key %r"
                                    % (self.artifact(PARAMS_FILE), key))
            split[kind][name] = value
        return split["arg"], split["aux"]

    def describe(self):
        return {"step": self.step, "epoch": self.epoch,
                "reason": self.reason, "path": self.directory,
                "n_dev": self.n_dev}


class Checkpointer:
    """Writes the snapshots and drives the three triggers.

    ``attach(module)`` installs this checkpointer on the module: the
    fit loop calls :meth:`on_step` after every completed step (post
    update, post health judgment), and the health monitor's anomaly
    callback marks a pending anomaly snapshot.  Chaos hooks
    (``elastic/chaos.py``) ride the public hook lists."""

    def __init__(self, directory=None, every_steps=None, keep=None,
                 drain_deadline_s=DEFAULT_DRAIN_S, logger=None):
        directory = directory or os.environ.get(DIR_ENV)
        if not directory:
            raise SnapshotError(
                "Checkpointer needs a directory (argument or %s)"
                % DIR_ENV)
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.every_steps = _int_env(STEPS_ENV, 0) if every_steps is None \
            else int(every_steps)
        self.keep = max(1, _int_env(KEEP_ENV, DEFAULT_KEEP)
                        if keep is None else int(keep))
        self.drain_deadline_s = float(drain_deadline_s)
        self.logger = logger or _log
        self.step = 0
        self.last_path = None
        # chaos / test hooks: pre_write_hooks(path) run before every
        # artifact write attempt (a raising hook exercises the retry
        # path, a sleeping one the drain deadline); post_save_hooks
        # (snapshot) after a committed snapshot; step_observers(step,
        # epoch, batch) before the trigger logic each step.
        self.pre_write_hooks = []
        self.post_save_hooks = []
        self.step_observers = []
        self._anomaly_pending = None
        self._preempt_at = None
        self._preempt_signum = None
        self._preempt_noted = False
        self._prev_handlers = {}
        # resume offset: fit restarts nbatch at 0 after resume_fit's
        # fast-forward, so positions reported for the RESUME epoch are
        # short by the skipped batches — save() re-adds them, keeping
        # a second preemption's replay exact (resume() sets this)
        self._offset_epoch = None
        self._offset_skip = 0

    # -- wiring --------------------------------------------------------------

    def attach(self, module):
        """Install on ``module`` (the fit loop's per-step hook) and on
        its health monitor when one already exists; a monitor created
        later registers the callback itself
        (``BaseModule._ensure_health_monitor``)."""
        # an elastic training process is a fleet member too: with
        # MXNET_TPU_TS_INTERVAL_S set it ships its series into the
        # shared trace-root dir alongside the serving replicas (no-op
        # when the env is unset)
        from ..observability import timeseries as _timeseries
        _timeseries.ensure_sampler()
        module._elastic_ckpt = self
        mon = getattr(module, "_health_mon", None)
        if mon is not None and self.note_anomaly not in mon.callbacks:
            mon.add_callback(self.note_anomaly)
        return self

    def note_anomaly(self, record):
        """Health-monitor callback: mark an anomaly snapshot pending.
        The monitor's own flight dump (for ``dump``/``raise`` actions)
        happens after the callbacks and BEFORE the next step boundary
        writes the snapshot — black box first."""
        if self._anomaly_pending is None:
            self._anomaly_pending = dict(record)

    def install_signal_handlers(self, signals=(signal.SIGTERM,
                                               signal.SIGINT)):
        """SIGTERM/SIGINT set the preempt flag; the next step boundary
        snapshots and raises :class:`PreemptedError`.  The handler
        itself only sets state — no I/O (a snapshot taken mid-dispatch
        would capture half-updated state) and no locks (it runs ON the
        interrupted main thread, which may already hold the
        non-reentrant flight-recorder or logging lock; taking either
        here would self-deadlock the worker).  The flight record and
        log line are emitted at the next step boundary."""

        def _handler(signum, frame):
            self._preempt_at = time.monotonic()
            self._preempt_signum = signum

        installed = []
        for sig in signals:
            try:
                self._prev_handlers[sig] = signal.signal(sig, _handler)
                installed.append(sig)
            except ValueError:
                # not the main thread: the host process owns signals
                self.logger.warning(
                    "cannot install the preemption handler for signal "
                    "%s off the main thread; call "
                    "Checkpointer.preempt() from the process's own "
                    "handler instead", sig)
        return installed

    def remove_signal_handlers(self):
        for sig, prev in self._prev_handlers.items():
            try:
                signal.signal(sig, prev)
            except ValueError:
                pass
        self._prev_handlers = {}

    def preempt(self):
        """Programmatic preemption (for hosts that own their signal
        handlers): same effect as receiving SIGTERM."""
        self._preempt_at = time.monotonic()
        self._preempt_signum = None

    def note_resume_position(self, epoch, skip_batches):
        """Called by ``resume()``: batch indices reported for ``epoch``
        are offsets into the REMAINDER of that epoch (the fit loop's
        nbatch restarts at 0 after the fast-forward) — ``save`` adds
        ``skip_batches`` back so the recorded data position stays
        absolute and a second resume replays exactly."""
        self._offset_epoch = int(epoch)
        self._offset_skip = int(skip_batches)

    # -- the per-step trigger ------------------------------------------------

    def on_step(self, module, epoch=0, batch=None):
        """Called by the fit loop after each completed step (update
        applied, health judged).  Applies the trigger logic; raises
        :class:`PreemptedError` after a preemption snapshot."""
        self.step += 1
        for obs in list(self.step_observers):
            obs(self.step, epoch, batch)
        if self._preempt_at is not None:
            if not self._preempt_noted:
                # deferred from the signal handler (which must not
                # take the recorder/logging locks): note the signal
                # now, on the fit thread, before the drain snapshot
                self._preempt_noted = True
                _flight.note_elastic({
                    "kind": "preempt_signal",
                    "signal": None if self._preempt_signum is None
                    else int(self._preempt_signum),
                    "step": self.step})
                self.logger.warning(
                    "preemption signal %s received: drained the "
                    "in-flight step at step %d, snapshot within %.1fs",
                    self._preempt_signum, self.step,
                    self.drain_deadline_s)
            budget = self.drain_deadline_s \
                - (time.monotonic() - self._preempt_at)
            path = None
            if budget > 0:
                path = self._save_guarded(module, epoch, batch,
                                          "preempt", deadline_s=budget)
            else:
                self.logger.error(
                    "drain deadline (%.1fs) expired before a step "
                    "boundary; exiting WITHOUT a preemption snapshot "
                    "(last snapshot: %s)", self.drain_deadline_s,
                    self.last_path)
            raise PreemptedError(
                "training preempted (signal %s) at step %d; snapshot: %s"
                % (self._preempt_signum, self.step, path),
                step=self.step, snapshot_path=path)
        if self._anomaly_pending is not None:
            rec, self._anomaly_pending = self._anomaly_pending, None
            # the monitor's flight dump (when its action dumps) is
            # already on disk: black box first, then the checkpoint
            self._save_guarded(module, epoch, batch,
                               "anomaly:%s" % rec.get("rule", "?"))
        elif self.every_steps > 0 and self.step % self.every_steps == 0:
            # guarded like the other triggers: a checkpoint-volume blip
            # outlasting the write retries must cost a snapshot, not
            # the healthy training run it exists to protect
            self._save_guarded(module, epoch, batch, "schedule")

    def on_diverged(self, module, epoch=0, batch=None):
        """``fit``'s unwind hook for ``TrainingDivergedError``: the
        raising rule already wrote the flight dump (black box first);
        leave a final snapshot behind, never masking the error.
        ``epoch``/``batch`` are the diverged step's position (its
        update IS in the saved params — the health vector is captured
        post-update), so a resume continues at the next batch."""
        self._anomaly_pending = None
        # the diverged step completed its update but unwound before
        # on_step could count it: count it here so the snapshot's step
        # matches the updates it contains and resumed schedules align
        self.step += 1
        self._save_guarded(module, epoch, batch, "diverged")

    def _save_guarded(self, module, epoch, batch, reason,
                      deadline_s=None):
        try:
            return self.save(module, epoch=epoch, batch=batch,
                             reason=reason, deadline_s=deadline_s)
        except Exception:
            self.logger.exception("%s snapshot at step %d failed; "
                                  "continuing with the previous one "
                                  "(%s)", reason, self.step,
                                  self.last_path)
            return None

    # -- writing -------------------------------------------------------------

    def _write_artifact(self, path, writer, deadline=None):
        """Run ``writer(path)`` with capped-exponential-backoff retries
        (transient filesystem errors on a shared checkpoint volume are
        normal).  ``deadline`` is an ABSOLUTE ``time.monotonic()``
        timestamp shared by every artifact of one snapshot — a fresh
        per-artifact budget would let a preemption drain consume a
        multiple of the grace period."""
        for attempt in range(WRITE_ATTEMPTS):
            try:
                for hook in list(self.pre_write_hooks):
                    hook(path)
                writer(path)
                return
            except (OSError, IOError) as exc:
                delay = min(BACKOFF_CAP_S, BACKOFF_BASE_S * (2 ** attempt))
                if attempt == WRITE_ATTEMPTS - 1 or (
                        deadline is not None
                        and time.monotonic() + delay > deadline):
                    raise SnapshotError(
                        "writing %s failed after %d attempt(s): %s"
                        % (path, attempt + 1, exc)) from exc
                self.logger.warning(
                    "snapshot write %s failed (%s); retry %d/%d in "
                    "%.2fs", path, exc, attempt + 1,
                    WRITE_ATTEMPTS - 1, delay)
                time.sleep(delay)

    def save(self, module, epoch=0, batch=None, reason="manual",
             deadline_s=None):
        """Write one full-state snapshot for the current step counter
        and commit it atomically.  Returns the snapshot directory."""
        if not (module.binded and module.params_initialized):
            raise SnapshotError("cannot snapshot an unbound module")
        if batch is not None and int(epoch) == self._offset_epoch:
            # positions in the resume epoch arrive relative to the
            # fast-forward point: restore the absolute batch index
            batch = int(batch) + self._offset_skip
        step = self.step
        final_dir = os.path.join(self.directory,
                                 "%s%010d" % (SNAP_PREFIX, step))
        with _tmp_lock:
            _tmp_counter[0] += 1
            tmp_dir = os.path.join(
                self.directory, ".tmp-%d-%d" % (os.getpid(),
                                                _tmp_counter[0]))
        os.makedirs(tmp_dir)
        t0 = time.monotonic()
        deadline = None if deadline_s is None else t0 + float(deadline_s)
        try:
            files = {}
            self._write_artifact(os.path.join(tmp_dir, PARAMS_FILE),
                                 module.save_params, deadline)
            if module.optimizer_initialized:
                self._write_artifact(
                    os.path.join(tmp_dir, STATES_FILE),
                    module.save_optimizer_states, deadline)
            for name in os.listdir(tmp_dir):
                path = os.path.join(tmp_dir, name)
                files[name] = {"sha256": _sha256_file(path),
                               "bytes": os.path.getsize(path)}
            recorder = _flight.get_recorder()
            manifest = {
                "kind": "mxnet_tpu_snapshot",
                "version": 1,
                "step": step,
                "epoch": int(epoch),
                "batch": None if batch is None else int(batch),
                "reason": reason,
                "created": time.time(),
                "data_position": {
                    "epoch": int(epoch),
                    "batch": None if batch is None else int(batch),
                    "consumed_batches": None if batch is None
                    else int(batch) + 1},
                "data_shapes": _desc_list(
                    getattr(module, "_data_shapes", None)),
                "label_shapes": _desc_list(
                    getattr(module, "_label_shapes", None)),
                "n_dev": len(getattr(module, "_context", None) or []) or None,
                "lineage": {
                    "flight_last_dump": recorder.last_dump_path,
                    "anomalies": recorder.anomaly_count(),
                    "last_recorded_step": recorder.last_step()},
                "files": files,
            }
            # manifest last: its presence is the commit marker inside
            # the directory; the rename below is the global one
            mpath = os.path.join(tmp_dir, MANIFEST_NAME)
            with open(mpath + ".tmp", "w") as f:
                json.dump(manifest, f, indent=1, sort_keys=True)
            os.replace(mpath + ".tmp", mpath)
            if os.path.exists(final_dir):
                # re-reaching a step after resuming past a corrupt or
                # stale snapshot: the fresh write replaces it
                shutil.rmtree(final_dir)
            os.rename(tmp_dir, final_dir)
        except Exception:
            shutil.rmtree(tmp_dir, ignore_errors=True)
            raise
        self.last_path = final_dir
        wall_ms = (time.monotonic() - t0) * 1e3
        total = sum(m["bytes"] for m in files.values())
        _telemetry.counter(
            "elastic.checkpoints",
            help="committed elastic snapshots").inc()
        _telemetry.histogram(
            "elastic.checkpoint_ms",
            help="wall time of one snapshot write").observe(wall_ms)
        _flight.note_elastic({"kind": "checkpoint", "step": step,
                              "epoch": int(epoch), "reason": reason,
                              "path": final_dir, "bytes": int(total),
                              "wall_ms": round(wall_ms, 2)})
        self.logger.info("elastic snapshot step %d (%s) -> %s "
                         "(%d bytes, %.1f ms)", step, reason, final_dir,
                         total, wall_ms)
        snap = Snapshot.open(final_dir)
        for hook in list(self.post_save_hooks):
            hook(snap)
        self._retain()
        return final_dir

    def _retain(self):
        """Drop the oldest snapshots beyond ``keep`` (after a
        successful write, so a failing write never shrinks history)."""
        snaps = self.snapshots(include_broken=True)
        for directory, _ in snaps[:-self.keep]:
            shutil.rmtree(directory, ignore_errors=True)
            self.logger.info("elastic retention: dropped %s", directory)

    # -- reading -------------------------------------------------------------

    def snapshots(self, include_broken=False):
        """``[(directory, Snapshot|None), ...]`` oldest first.  Broken
        directories (no parsable manifest) are excluded unless
        ``include_broken`` (retention counts them so a corrupt pile
        cannot pin disk forever)."""
        out = []
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return out
        for name in names:
            if not name.startswith(SNAP_PREFIX):
                continue
            directory = os.path.join(self.directory, name)
            try:
                snap = Snapshot.open(directory)
            except SnapshotError:
                snap = None
                if not include_broken:
                    continue
            out.append((directory, snap))
        return out

    def latest(self, verify=True):
        """Newest usable :class:`Snapshot` (or None).  With ``verify``
        (default) each candidate's manifest sha256s are checked; a
        corrupt/partial snapshot is skipped with a warning in favor of
        the previous one — the fault-injection contract."""
        for directory, snap in reversed(self.snapshots()):
            if snap is None:
                continue
            if verify:
                problems = snap.verify()
                if problems:
                    self.logger.warning(
                        "skipping corrupt snapshot %s: %s", directory,
                        "; ".join(problems))
                    _flight.note_elastic({
                        "kind": "checkpoint_rejected",
                        "step": snap.step, "path": directory,
                        "problems": problems})
                    _telemetry.counter(
                        "elastic.corrupt_snapshots",
                        help="snapshots rejected at manifest "
                             "verify").inc()
                    continue
            return snap
        return None

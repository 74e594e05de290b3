"""Resume from the newest usable snapshot, possibly on a different mesh.

``resume(module, directory)`` restores params + optimizer state into
an unbound module and reports what happened, including the warm-boot
evidence: with ``MXNET_TPU_PROGRAM_CACHE_DIR``
on a shared volume a replacement worker's bind restores its compiled
programs from disk — ``expect_warm=True`` asserts zero backend compiles
via the memprof build totals instead of hoping.

``resume_fit`` is the whole loop: resume, re-attach the checkpointer,
fast-forward the data iterator to the snapshot's ``(epoch, batch)``
position (pure replay — the io_pipeline batch stream is a deterministic
function of ``(seed, epoch, position)``), and continue ``fit`` to
``num_epoch``.  A run resumed this way is step-for-step the
uninterrupted run: bitwise-equal final params at the original
factorization, allclose across a re-factorization
(``tests/test_elastic.py``).
"""
from __future__ import annotations

import os

from ..base import MXNetError
from ..io import DataDesc, DataIter
from ..log import module_logger as _module_logger
from ..observability import flight_recorder as _flight
from ..observability import memprof as _memprof
from .checkpoint import (Checkpointer, Snapshot, SnapshotError,
                         STATES_FILE)

_log = _module_logger(__name__)


class ResumeReport:
    """What ``resume`` did: the snapshot it chose, where training picks
    up (``begin_epoch`` + ``skip_batches`` into that epoch), whether
    the mesh re-factorized, and the warm-boot counters."""

    def __init__(self, snapshot, checkpointer, begin_epoch, skip_batches,
                 refactorized, n_dev_from, n_dev_to, warm):
        self.snapshot = snapshot
        self.checkpointer = checkpointer
        self.step = snapshot.step
        self.begin_epoch = begin_epoch
        self.skip_batches = skip_batches
        self.refactorized = refactorized
        self.n_dev_from = n_dev_from
        self.n_dev_to = n_dev_to
        self.warm = warm

    def describe(self):
        return {"step": self.step, "begin_epoch": self.begin_epoch,
                "skip_batches": self.skip_batches,
                "refactorized": self.refactorized,
                "n_dev_from": self.n_dev_from,
                "n_dev_to": self.n_dev_to,
                "warm": dict(self.warm),
                "snapshot": self.snapshot.describe()}


def _descs(records):
    if not records:
        return None
    return [DataDesc(r["name"], tuple(r["shape"]),
                     dtype=r.get("dtype", "float32"),
                     layout=r.get("layout")) for r in records]


def resume(module, directory=None, checkpointer=None, kvstore="local",
           optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
           expect_warm=False, logger=None):
    """Restore ``module`` from the newest verified snapshot.

    The module may be completely fresh (same symbol): bind shapes come
    from the manifest, params from ``params.ndarray``, optimizer state
    (momentum, f32 masters) from ``optimizer.states``.
    Returns a :class:`ResumeReport`; raises :class:`SnapshotError` when
    no usable snapshot exists."""
    from .. import executor_cache
    logger = logger or _log
    ckpt = checkpointer if checkpointer is not None \
        else Checkpointer(directory=directory)
    snap = ckpt.latest(verify=True)
    if snap is None:
        raise SnapshotError("no usable snapshot under %r" % ckpt.directory)

    totals0 = _memprof.build_totals()
    with executor_cache.watch_traces() as watch:
        if not module.binded:
            data_shapes = _descs(snap.manifest.get("data_shapes"))
            if not data_shapes:
                raise SnapshotError(
                    "snapshot %s records no data shapes; bind the "
                    "module before resume()" % snap.directory)
            module.bind(data_shapes=data_shapes,
                        label_shapes=_descs(
                            snap.manifest.get("label_shapes")),
                        for_training=True)
        arg_params, aux_params = snap.load_params()
        module.set_params(arg_params, aux_params)
        if not module.optimizer_initialized:
            module.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                                  optimizer_params=optimizer_params)
        states = snap.artifact(STATES_FILE)
        if os.path.exists(states):
            module.load_optimizer_states(states)
        else:
            logger.warning("snapshot %s has no optimizer states; "
                           "momentum restarts from zero", snap.directory)
    totals1 = _memprof.build_totals()
    warm = {k: totals1[k] - totals0[k] for k in totals1}
    warm["traces"] = watch.total()
    if expect_warm and (warm["built"] or warm["backend_compiles"]):
        raise MXNetError(
            "elastic warm-resume verification failed: restoring from "
            "%s built %d program(s) with %d backend compile(s) — a "
            "replacement worker on a populated %s volume must restore "
            "everything from disk" % (snap.directory, warm["built"],
                                      warm["backend_compiles"],
                                      "MXNET_TPU_PROGRAM_CACHE_DIR"))

    n_dev_to = len(getattr(module, "_context", None) or []) or 1
    n_dev_from = snap.n_dev
    refactorized = n_dev_from is not None and n_dev_from != n_dev_to

    if refactorized:
        logger.warning(
            "resuming into a re-factorized mesh: %s -> %s device(s), %s "
            "optimizer state", n_dev_from, n_dev_to,
            "with" if os.path.exists(states) else "without")

    position = snap.data_position
    consumed = position.get("consumed_batches") or 0
    begin_epoch = int(position.get("epoch") or 0)
    ckpt.step = snap.step
    # snapshots written during the resumed partial epoch see nbatch
    # restart at 0 — teach the checkpointer the offset so a SECOND
    # preemption's snapshot still records the absolute data position
    ckpt.note_resume_position(begin_epoch, int(consumed))
    report = ResumeReport(snap, ckpt, begin_epoch, int(consumed),
                          refactorized, n_dev_from, n_dev_to, warm)
    _flight.note_elastic({
        "kind": "resume", "from_step": snap.step,
        "snapshot": snap.directory, "begin_epoch": begin_epoch,
        "skip_batches": int(consumed), "refactorized": refactorized,
        "n_dev_from": n_dev_from, "n_dev_to": n_dev_to,
        "warm": dict(warm)})
    logger.info(
        "elastic resume from step %d (%s): epoch %d skip %d, "
        "%d device(s)%s; warm boot: %d restored / %d built / %d "
        "backend compile(s)", snap.step, snap.directory, begin_epoch,
        consumed, n_dev_to,
        " [re-factorized from %s]" % n_dev_from if refactorized else "",
        warm.get("restored", 0), warm.get("built", 0),
        warm.get("backend_compiles", 0))
    return report


class _SkipFirstEpochIter(DataIter):
    """Fast-forward wrapper: silently consumes the first ``skip``
    batches of the FIRST epoch (the batches the snapshot already
    trained on), then passes through — later epochs (after ``reset``)
    run full.  Pure replay keeps the resumed batch stream identical to
    the uninterrupted run's."""

    def __init__(self, base, skip):
        super().__init__(getattr(base, "batch_size", 0))
        self._base = base
        self._pending = int(skip)

    @property
    def provide_data(self):
        return self._base.provide_data

    @property
    def provide_label(self):
        return self._base.provide_label

    def reset(self):
        self._pending = 0
        self._base.reset()

    def next(self):
        while self._pending > 0:
            self._pending -= 1
            try:
                self._base.next()
            except StopIteration:
                # the snapshot landed exactly on (or past) the epoch
                # boundary: this epoch contributes nothing
                self._pending = 0
                raise
        return self._base.next()

    def close(self):
        close = getattr(self._base, "close", None)
        if close is not None:
            close()


def resume_fit(module, train_data, num_epoch, directory=None,
               checkpointer=None, eval_data=None, kvstore="local",
               optimizer="sgd",
               optimizer_params=(("learning_rate", 0.01),),
               expect_warm=False, **fit_kwargs):
    """``resume`` + continue ``fit`` to ``num_epoch``: restores state,
    re-attaches the checkpointer (step counter synced to the snapshot),
    fast-forwards ``train_data`` past the consumed batches of the
    resume epoch, and trains.  Returns the :class:`ResumeReport`."""
    report = resume(module, directory=directory,
                    checkpointer=checkpointer, kvstore=kvstore,
                    optimizer=optimizer, optimizer_params=optimizer_params,
                    expect_warm=expect_warm)
    # a resumed (often respawned) worker rejoins the fleet health
    # plane: the inherited MXNET_TPU_REQTRACE_CTX root routes its
    # shipped series into the same dir as the parent's (no-op when
    # MXNET_TPU_TS_INTERVAL_S is unset)
    from ..observability import timeseries as _timeseries
    _timeseries.ensure_sampler()
    report.checkpointer.attach(module)
    it = _SkipFirstEpochIter(train_data, report.skip_batches) \
        if report.skip_batches else train_data
    import warnings
    with warnings.catch_warnings():
        # fit's init_params/init_optimizer correctly no-op on the
        # restored module; their "already initialized" warnings are
        # the expected resume path, not user error
        warnings.filterwarnings("ignore",
                                message="Parameters already initialized")
        module.fit(it, eval_data=eval_data,
                   begin_epoch=report.begin_epoch, num_epoch=num_epoch,
                   kvstore=kvstore, optimizer=optimizer,
                   optimizer_params=optimizer_params, **fit_kwargs)
    return report

"""Deterministic fault injection for the elastic subsystem.

A fault plan is declarative JSON — reviewable, replayable, env-shippable
(``MXNET_TPU_CHAOS_PLAN``) — so the same plan drives a unit test and a
victim subprocess::

    [{"kind": "kill_at_step", "step": 22},
     {"kind": "corrupt_checkpoint", "at_step": 20},
     {"kind": "write_stall", "seconds": 0.2, "count": 2}]

Fault kinds:

- ``kill_at_step`` — the worker dies the instant step N completes
  (``mode="exit"``: ``os._exit`` with ``exit_code``, default 57 — the
  subprocess form a preemption actually takes; ``mode="raise"``:
  :class:`WorkerKilled`, the in-process test form).
- ``corrupt_checkpoint`` — after the first committed snapshot at/after
  ``at_step``, flip bytes in one artifact WITHOUT touching the
  manifest: exactly the partial/corrupt write the manifest sha256
  verify exists to catch (resume must fall back to the previous
  snapshot).
- ``write_stall`` — the first ``count`` artifact writes sleep
  ``seconds`` before proceeding (exercises the backoff/deadline paths
  of the checkpoint writer).

``ChaosMonkey(plan).arm(checkpointer)`` installs the hooks; every fault
that fires is recorded in ``monkey.fired`` and the flight recorder's
``elastic`` ring.
"""
from __future__ import annotations

import json
import os

from ..base import MXNetError
from ..log import module_logger as _module_logger
from ..observability import flight_recorder as _flight
from .checkpoint import MANIFEST_NAME, PARAMS_FILE

PLAN_ENV = "MXNET_TPU_CHAOS_PLAN"
KINDS = ("kill_at_step", "corrupt_checkpoint", "write_stall")
DEFAULT_KILL_EXIT = 57

_log = _module_logger(__name__)


class WorkerKilled(MXNetError):
    """The in-process form of a ``kill_at_step`` fault."""

    def __init__(self, message, step=None):
        super().__init__(message)
        self.step = step


def _require(fault, key, types):
    if not isinstance(fault.get(key), types):
        raise MXNetError("chaos fault %r needs %r (%s)"
                         % (fault.get("kind"), key, types))


class FaultPlan:
    """Validated, normalized list of fault dicts."""

    def __init__(self, faults):
        normalized = []
        for fault in faults or []:
            if not isinstance(fault, dict):
                raise MXNetError("chaos fault must be a dict, got %r"
                                 % (fault,))
            kind = fault.get("kind")
            if kind not in KINDS:
                raise MXNetError("unknown chaos fault kind %r (known: %s)"
                                 % (kind, ", ".join(KINDS)))
            fault = dict(fault)
            if kind == "kill_at_step":
                _require(fault, "step", int)
                fault.setdefault("mode", "exit")
                if fault["mode"] not in ("exit", "raise"):
                    raise MXNetError("kill_at_step mode must be "
                                     "'exit' or 'raise'")
                fault.setdefault("exit_code", DEFAULT_KILL_EXIT)
            elif kind == "corrupt_checkpoint":
                fault.setdefault("at_step", 0)
                _require(fault, "at_step", int)
                fault.setdefault("artifact", PARAMS_FILE)
            else:  # write_stall
                _require(fault, "seconds", (int, float))
                fault.setdefault("count", 1)
            normalized.append(fault)
        self.faults = normalized

    @classmethod
    def from_json(cls, text):
        try:
            doc = json.loads(text)
        except ValueError as exc:
            raise MXNetError("unparsable chaos plan JSON: %s"
                             % exc) from exc
        if isinstance(doc, dict):
            doc = doc.get("faults", [doc])
        return cls(doc)

    @classmethod
    def from_env(cls):
        """The plan from ``MXNET_TPU_CHAOS_PLAN`` (None when unset) —
        how a harness ships a plan into its victim subprocess."""
        raw = os.environ.get(PLAN_ENV, "").strip()
        return cls.from_json(raw) if raw else None

    def describe(self):
        return [dict(f) for f in self.faults]

    def dryrun(self):
        """Human-readable validation report without arming anything —
        what would fire, and when."""
        lines = ["chaos plan: %d fault(s)" % len(self.faults)]
        for fault in self.faults:
            kind = fault["kind"]
            if kind == "kill_at_step":
                lines.append("  kill worker at step %d (%s)"
                             % (fault["step"], fault["mode"]))
            elif kind == "corrupt_checkpoint":
                lines.append("  corrupt %s of the first snapshot at/"
                             "after step %d" % (fault["artifact"],
                                                fault["at_step"]))
            else:
                lines.append("  stall the first %d artifact write(s) "
                             "by %.2fs" % (fault["count"],
                                           fault["seconds"]))
        return "\n".join(lines)


def corrupt_snapshot(snapshot_dir, artifact=PARAMS_FILE, nbytes=16):
    """Flip ``nbytes`` bytes at the middle of one snapshot artifact,
    leaving the manifest untouched — the canonical injected corruption.
    Returns the path."""
    path = os.path.join(snapshot_dir, artifact)
    if artifact == MANIFEST_NAME:
        raise MXNetError("corrupt an artifact, not the manifest — a "
                         "missing/garbled manifest is a different "
                         "(already-covered) failure class")
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(max(0, size // 2 - nbytes // 2))
        chunk = f.read(nbytes)
        f.seek(max(0, size // 2 - nbytes // 2))
        f.write(bytes(b ^ 0xFF for b in chunk))
    _log.warning("chaos: corrupted %d byte(s) of %s", len(chunk), path)
    return path


class ChaosMonkey:
    """Arms a :class:`FaultPlan` onto a ``Checkpointer``'s hook lists."""

    def __init__(self, plan, logger=None):
        self.plan = plan
        self.logger = logger or _log
        self.fired = []

    def _note(self, record):
        self.fired.append(record)
        _flight.note_elastic(dict(record, kind="chaos:" + record["kind"]))
        self.logger.warning("chaos fault fired: %s", record)

    def arm(self, checkpointer):
        for fault in self.plan.faults:
            kind = fault["kind"]
            if kind == "kill_at_step":
                checkpointer.step_observers.append(
                    self._kill_hook(fault))
            elif kind == "corrupt_checkpoint":
                checkpointer.post_save_hooks.append(
                    self._corrupt_hook(fault))
            else:
                checkpointer.pre_write_hooks.append(
                    self._stall_hook(fault))
        return self

    def _kill_hook(self, fault):
        def hook(step, epoch, batch):
            if step != fault["step"]:
                return
            self._note({"kind": "kill_at_step", "step": step,
                        "mode": fault["mode"]})
            if fault["mode"] == "raise":
                raise WorkerKilled("chaos kill at step %d" % step,
                                   step=step)
            # the subprocess form of a preemption: no unwinding, no
            # atexit — the process is simply gone
            os._exit(fault["exit_code"])
        return hook

    def _corrupt_hook(self, fault):
        state = {"done": False}

        def hook(snapshot):
            if state["done"] or snapshot.step < fault["at_step"]:
                return
            state["done"] = True
            corrupt_snapshot(snapshot.directory, fault["artifact"])
            self._note({"kind": "corrupt_checkpoint",
                        "step": snapshot.step,
                        "artifact": fault["artifact"]})
        return hook

    def _stall_hook(self, fault):
        state = {"left": int(fault["count"])}

        def hook(path):
            if state["left"] <= 0:
                return
            state["left"] -= 1
            self._note({"kind": "write_stall", "path": path,
                        "seconds": fault["seconds"]})
            import time
            time.sleep(float(fault["seconds"]))
        return hook

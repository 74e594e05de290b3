"""Language-model training cells with Mamba-2 state-space layers: the run IS
``drivers/fit_lm.run`` (the ring, the one ``fit`` call, the window, the
comparison with the plain reference), as ``fit_lm_window``'s is, and this
driver adds what that one cannot hand its readers:

- ``required_flops`` from ``shapes_ssm`` (the ``ssd`` nodes' recurrence,
  ``4 * N * P`` a token and head forward, beside ``shapes_window``'s count);
- ``kernel_work``: the operations and bytes that the state-space kernels'
  calls in the window require, for ``readers/kernel_compute_roofline.py``
  (patterns ``ssd_scan_fwd`` and ``ssd_scan_bwd``);
- a note of the program's lowerings of the recurrence by path
  (``ops.ssm.lowered_kernel`` / ``ops.ssm.lowered_xla``), so that a run
  that fell back to the ``lax.scan`` says so in its own output."""
from __future__ import annotations

import importlib

import jax.numpy as jnp

from .. import shapes_ssm
from . import fit_lm

LOWERINGS = ("ops.ssm.lowered_kernel", "ops.ssm.lowered_xla")


def _lowerings():
    from mxnet_tpu.observability import telemetry
    snap = telemetry.snapshot()
    return {c: float(snap[c]["value"]) if c in snap else 0.0
            for c in LOWERINGS}


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    out = fit_lm.run(loaded, args, devices, spans, tracer, clock, t_start,
                     fault=fault, check_it=check_it)
    obs = out.get("obs")
    if obs is None:
        return out
    cfg, mix = loaded["config"], loaded["traffic"]
    sym = importlib.import_module(
        "benchmark.builders." + cfg["builder"]).symbol(cfg)
    shape = (int(mix["batch"]), int(mix["seq_len"]))
    in_shapes = {"data": shape, "softmax_label": shape}
    steps = obs["steps"]
    obs["required_flops"] = steps * shapes_ssm.train_flops(
        sym, fit_lm.model_of(cfg), **in_shapes)
    itemsize = jnp.dtype(cfg["precision"]["compute"]).itemsize
    obs["kernel_work"] = {
        pattern: {k: steps * v
                  for k, v in work(sym, itemsize, **in_shapes).items()}
        for pattern, work in (
            ("ssd_scan_fwd", shapes_ssm.ssd_scan_forward_work),
            ("ssd_scan_bwd", shapes_ssm.ssd_scan_backward_work))}
    out["notes"].append("lowerings of the state-space recurrence: %s" % ", ".join(
        "%s %d" % (c, v) for c, v in _lowerings().items()))
    return out

"""Language-model training cells whose attention is windowed: the run IS
``drivers/fit_lm.run`` (the ring, the one ``fit`` call, the window, the
comparison with the plain reference), and this driver adds what that one
cannot hand its readers:

- ``required_flops`` from ``shapes_window`` (an attention node counts the
  pairs its mask lets through, not every earlier key);
- the two ``module.attn.*`` counters at the window's two edges, read where
  ``fit_lm`` reads its own: it opens and closes the window's span exactly
  there, so the spans object handed to it reads them on the way;
- ``kernel_work``: the operations and bytes of the flash forward's calls in
  the window, for ``readers/kernel_compute_roofline.py``."""
from __future__ import annotations

import importlib

import jax.numpy as jnp

from .. import harness, shapes_window
from . import fit_lm

ATTN_COUNTERS = ("module.attn.pairs_computed", "module.attn.pairs_visible")


def _attn_counters():
    from mxnet_tpu.observability import telemetry
    snap = telemetry.snapshot()
    return {c: float(snap[c]["value"]) for c in ATTN_COUNTERS if c in snap}


class _EdgeSpans(harness.Spans):
    """The run's spans, reading the attention counters as the window's span
    opens and closes."""

    def __init__(self, spans):
        self.inner, self.on = spans, spans.on
        self.opened = self.closed = None

    def __call__(self, name):
        return self.inner(name)

    def open_window(self):
        self.opened = _attn_counters()
        self.inner.open_window()

    def close_window(self):
        self.inner.close_window()
        self.closed = _attn_counters()


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    edges = _EdgeSpans(spans)
    out = fit_lm.run(loaded, args, devices, edges, tracer, clock, t_start,
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
    obs["required_flops"] = steps * shapes_window.train_flops(
        sym, fit_lm.model_of(cfg), **in_shapes)
    obs["counters"].update(
        {c: v - (edges.opened or {}).get(c, 0.0)
         for c, v in (edges.closed or {}).items()})
    work = shapes_window.flash_forward_work(
        sym, jnp.dtype(cfg["precision"]["compute"]).itemsize, **in_shapes)
    obs["kernel_work"] = {"flash_attn_fwd": {k: steps * v
                                             for k, v in work.items()}}
    return out

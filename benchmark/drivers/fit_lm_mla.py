"""Language-model training cells whose attention has two widths (latent
attention): the run IS ``drivers/fit_lm.run``, as ``fit_lm_window``'s is, and
this driver adds what that one cannot hand its readers:

- ``required_flops`` from ``shapes_mla`` (a pair costs ``2 * d_qk + 2 *
  d_v``, not ``4 * head_dim``);
- the two ``module.attn.*`` counters at the window's two edges, read by
  ``fit_lm_window``'s own spans object;
- ``kernel_work``: the operations and bytes that the flash forward's and
  the flash backward's calls in the window require, for
  ``readers/kernel_compute_roofline.py`` (patterns ``flash_attn_fwd`` and
  ``flash_attn_bwd``, which both backward kernels match)."""
from __future__ import annotations

import importlib

import jax.numpy as jnp

from .. import shapes_mla
from . import fit_lm, fit_lm_window


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    edges = fit_lm_window._EdgeSpans(spans)
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
    obs["required_flops"] = steps * shapes_mla.train_flops(
        sym, fit_lm.model_of(cfg), **in_shapes)
    obs["counters"].update(
        {c: v - (edges.opened or {}).get(c, 0.0)
         for c, v in (edges.closed or {}).items()})
    itemsize = jnp.dtype(cfg["precision"]["compute"]).itemsize
    obs["kernel_work"] = {
        pattern: {k: steps * v
                  for k, v in work(sym, itemsize, **in_shapes).items()}
        for pattern, work in (
            ("flash_attn_fwd", shapes_mla.flash_forward_work),
            ("flash_attn_bwd", shapes_mla.flash_backward_work))}
    return out

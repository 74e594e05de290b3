"""Language-model training cells under a BLOCK-DIFFUSION objective:
``Module.fit`` through the fused step on ``[batch, 2 L]`` inputs, the noisy
copy of each clean sequence of ``seq_len`` tokens beside it
(``mxnet_tpu.models.sdar.noise``), with each position's loss weight as the
label, fed by ``NDArrayIter`` over a ring of host batches.

The run is ``drivers/fit_lm.run``'s, step for step: ONE ``fit`` call, whose
first ``check_steps`` steps the plain reference follows and whose step
``warmup_steps`` opens the window; the comparison is ``fit_lm.check``.  What
differs, and why this is a driver of its own (``fit_lm.run`` makes its ring
and its input shapes inside itself):

- the ring: ``ring_batches`` batches of clean ids uniform over the
  vocabulary slice less its last row (the mask token), each noised from the
  seed with the mix's ``block_length`` and ``t_min``; the inputs are ``2 *
  seq_len`` positions long; ``noise_gap`` compares the ring with the
  reference's own noising of the same ids (limit 0);
- the counters read at the window's edges: the expert and recomputation
  ones of ``fit_lm``, the two ``module.attn.*`` and the two ``module.bd.*``;
- ``required_flops`` and ``kernel_work`` from ``shapes_bd`` (the mask's
  visible pairs), for ``fit_step_mfu`` and the two flash kernels' roofline
  shares."""
from __future__ import annotations

import gc
import importlib
import os

import numpy as np

from .. import harness, shapes_bd, traffic as traffic_mod
from ..window import StepWindow, quantile
from . import fit_lm
from .fit import TRACKED, _component_ms, _contexts, _state_leaf

COUNTERS = fit_lm.COUNTERS + (
    "module.attn.pairs_computed", "module.attn.pairs_visible",
    "module.bd.masked_positions", "module.bd.noisy_positions")


def bd_ring(mix, cfg, seed, noise=None):
    """(inputs, weights), each float32 [ring*batch, 2 * seq_len]: clean ids
    uniform over the slice's first ``vocab_size - 1`` rows, noised with the
    last row as the mask by ``noise`` (the program's ``models.sdar.noise``
    unless given), both from the seed."""
    if noise is None:
        from mxnet_tpu.models.sdar import noise
    if int(mix["block_length"]) != int(cfg["block_length"]):
        raise ValueError("the mix noises blocks of %s, the model's mask "
                         "has blocks of %s" % (mix["block_length"],
                                               cfg["block_length"]))
    rows = int(mix["ring_batches"]) * int(mix["batch"])
    mask_id = int(cfg["vocab_size"]) - 1
    ids = traffic_mod.host_rng(seed, 13).integers(
        0, mask_id, (rows, int(mix["seq_len"])))
    return noise(ids, traffic_mod.host_rng(seed, 17), int(mix["block_length"]),
                 mask_id, float(mix["t_min"]))


def noise_gap(mix, cfg, seed, ring_x, ring_y, reference):
    """The entries in which the ring differs from what the reference's own
    noising (``reference.noise``) makes of the same ids and seed: 0 unless
    the program noises otherwise than the objective says."""
    want_x, want_y = bd_ring(mix, cfg, seed, reference.noise)
    return float(np.count_nonzero(ring_x != want_x)
                 + np.count_nonzero(ring_y != want_y))


def _counters():
    from mxnet_tpu.observability import telemetry
    snap = telemetry.snapshot()
    return {c: float(snap[c]["value"]) for c in COUNTERS if c in snap}


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache

    cfg, mix = loaded["config"], loaded["traffic"]
    model, opt = fit_lm.model_of(cfg), cfg["optimizer"]
    batch, seq = int(mix["batch"]), 2 * int(mix["seq_len"])
    n_check, n_warm = int(mix["check_steps"]), int(mix["warmup_steps"])
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
        else args.seconds
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    sym = builder.symbol(cfg)
    in_shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}

    ring_x, ring_y = bd_ring(mix, model, args.seed)
    arg_shapes, _, _ = sym.infer_shape(**in_shapes)
    arg_spec = {n: tuple(s) for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in in_shapes}
    names = sorted(arg_spec)
    make_w0 = lambda: fit_lm.make_weights(args.seed, arg_spec, cfg["init"])

    class Ring(mx.io.DataIter):
        """Cycles the ``NDArrayIter`` over the ring until told to stop."""

        def __init__(self):
            super().__init__(batch)
            self.inner = mx.io.NDArrayIter(ring_x, ring_y, batch_size=batch)
            self.stop = False

        provide_data = property(lambda self: self.inner.provide_data)
        provide_label = property(lambda self: self.inner.provide_label)

        def reset(self):
            pass

        def next(self):
            if self.stop:
                raise StopIteration
            with spans("input:next"):
                try:
                    return self.inner.next()
                except StopIteration:
                    self.inner.reset()
                    return self.inner.next()

    ring = Ring()
    metric = mx.metric.create(mix["eval_metric"])
    if spans.on:
        plain_update = metric.update

        def update(labels, preds):
            with spans("metric:update"):
                plain_update(labels, preds)
        metric.update = update

    mod = mx.mod.Module(sym, context=_contexts(devices))
    window = StepWindow(seconds)
    s = {"step": 0, "losses": [], "open": None, "close": None, "m1": None,
         "dN": None, "w0": None}
    beta1 = float(opt["beta1"])

    @jax.jit
    def grad_norms(moments):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            m.astype(jnp.float32) / (1.0 - beta1)))) for m in moments])

    @jax.jit
    def change_norm(w, start):
        return jnp.sqrt(jnp.sum(jnp.square(
            w.astype(jnp.float32) - start.astype(jnp.float32))))

    def by_name(values):
        """The fused step's per-parameter list in ``names`` order."""
        got = dict(zip(mod._fused_step.param_names, values))
        return [got[n] for n in names]

    def edge():
        return {"components": _component_ms(), "counters": _counters(),
                "traces": executor_cache.trace_counts(),
                "clock": clock.mark()}

    def on_batch_end(param):
        if window.closed:
            return
        if window.is_open:
            if window.step_end(harness.now()):
                ring.stop = True
                spans.close_window()
                s["close"] = edge()
            return
        with spans("fit:batch_end"):
            s["step"] += 1
            n = s["step"]
            fused = mod._fused_step
            if n <= n_check:
                s["losses"].append(float(np.mean(np.asarray(
                    mod.get_outputs()[0].asnumpy(), np.float64))))
                if n == 1:
                    s["m1"] = np.asarray(grad_norms(by_name(
                        [_state_leaf(st) for st in fused.states])),
                        np.float64)
                if n == n_check:
                    s["dN"] = np.asarray(
                        [change_norm(w, jnp.asarray(start)) for w, start
                         in zip(by_name(fused._masters), s["w0"])],
                        np.float64)
                    s["w0"] = None      # the start is not held any longer
            if n == n_warm - 2:
                tracer.start()
            if n == n_warm:
                jax.block_until_ready(list(fused._masters))
                s["open"] = edge()
                spans.open_window()
                window.open(harness.now())

    # bound and initialised here, so that the float32 start can be let go
    # before the first step; ``fit`` finds the module ready and trains it
    mod.bind(data_shapes=ring.provide_data, label_shapes=ring.provide_label,
             for_training=True)
    w0 = make_w0()
    mod.init_params(arg_params={n: mx.nd.NDArray(a) for n, a in w0.items()},
                    aux_params={})
    # kept ON THE HOST till the last check step, in the storage type
    small = jnp.bfloat16 if cfg["init"]["round_bf16"] else jnp.float32
    s["w0"] = [np.asarray(w0[n].astype(small)) for n in names]
    del w0
    mod.fit(ring, num_epoch=1, eval_metric=metric, kvstore=mix["kvstore"],
            optimizer=opt["name"],
            optimizer_params={"learning_rate": opt["learning_rate"],
                              "beta1": opt["beta1"], "beta2": opt["beta2"],
                              "epsilon": opt["epsilon"], "wd": opt["wd"],
                              "multi_precision": opt["multi_precision"]},
            batch_end_callback=on_batch_end)
    if s["close"] is None:
        raise RuntimeError("fit ended before the window closed")
    fused = mod._fused_step
    if fused is None or not fused.ran:
        raise RuntimeError("Module.fit did not train through the fused step")

    reduced = tracer.stop_and_reduce()
    memory_peak = harness.memory_peak_bytes(
        devices, loaded["cell"]["name"],
        lambda: fused._step_jit.lower(*fused._last_abstract).compile())
    if os.environ.get("BENCH_STEP_LOG"):    # diagnosis: every step's end
        np.save(os.environ["BENCH_STEP_LOG"],
                np.concatenate(([window.t_open], window.ends[:window.n])))
    rate = window.rate(batch)
    setup_s = window.t_open - t_start
    steps = window.n
    finite = bool(np.all(np.isfinite(
        np.asarray(mod.get_outputs()[0].asnumpy(), np.float64))))

    delta = lambda key, c: s["close"][key].get(c, 0.0) \
        - s["open"][key].get(c, 0.0)
    itemsize = jnp.dtype(cfg["precision"]["compute"]).itemsize
    obs = {
        "cell": loaded["cell"], "chips": len(devices),
        "device_kind": devices[0].device_kind, "trace": reduced,
        "step_seconds": window.step_seconds(), "steps": steps,
        "components_ms": {c: delta("components", c) for c in TRACKED},
        "counters": {c: delta("counters", c) for c in s["close"]["counters"]},
        "retraces_in_window": sum(
            v - s["open"]["traces"].get(k, 0)
            for k, v in s["close"]["traces"].items()),
        "compile": {"compile_s": s["open"]["clock"][0],
                    "cache_hits": s["open"]["clock"][1],
                    "cache_misses": s["open"]["clock"][2]},
        "required_flops": steps * shapes_bd.train_flops(sym, model,
                                                        **in_shapes),
        "kernel_bytes": {},
        "kernel_work": {
            pattern: {k: steps * v
                      for k, v in work(sym, itemsize, **in_shapes).items()}
            for pattern, work in (
                ("flash_attn_fwd", shapes_bd.flash_forward_work),
                ("flash_attn_bwd", shapes_bd.flash_backward_work))},
    }

    mine = {"grad_norms": s["m1"], "update_norms": s["dN"]}
    losses = list(s["losses"])
    del mod, fused, ring, metric, s
    gc.collect()
    end = {"train_samples_per_s": rate, "setup_s": setup_s}
    if not check_it:        # a probe of size alone
        return {"end_to_end": end, "numbers": {}, "notes": [],
                "memory_peak": memory_peak}
    t_ref, c_ref = harness.now(), clock.mark()
    numbers, notes, refs = fit_lm.check(cfg, mix, devices, make_w0, ring_x,
                                        ring_y, mine, losses, names)
    c_end = clock.mark()
    notes.append("the reference and the comparison took %.1f s (%.1f s of it "
                 "obtaining executables: %d cache hits, %d misses)"
                 % (harness.now() - t_ref, c_end[0] - c_ref[0],
                    c_end[1] - c_ref[1], c_end[2] - c_ref[2]))
    numbers["noise_gap"] = noise_gap(
        mix, model, args.seed, ring_x, ring_y,
        importlib.import_module("benchmark.references." + cfg["reference"]))
    if not finite:
        numbers["loss_gap"] = float("inf")
    return {"end_to_end": end, "obs": obs, "attempted": steps,
            "failed": 0 if finite else steps, "numbers": numbers,
            "notes": notes, "memory_peak": memory_peak, "reduced": reduced,
            "refs": refs, "inputs": (make_w0, ring_x, ring_y),
            "tails": ["steps in window: %d, step p50 %.3f ms, max %.3f ms"
                      % (steps, 1e3 * quantile(obs["step_seconds"], 0.5),
                         1e3 * float(np.max(obs["step_seconds"])))]}

"""Language-model training cells: ``Module.fit`` through the fused step on
``[batch, seq]`` token ids, every position a next-token target, fed by
``NDArrayIter`` over a ring of host batches.

The shape of a run is ``drivers/fit.py``'s: ONE ``fit`` call, whose first
``check_steps`` steps are the ones the plain reference follows and whose
step ``warmup_steps`` opens the window.  What differs is what a model of
0.6 B parameters allows: the symbol's output is the loss (one number a
sequence, 8 bytes a step through ``mx.metric.Loss``), and nothing the size
of the parameters is kept for the comparison — after step one the norms of
Adam's first moment (``m1 = (1 - beta1) g``, so the first gradient as the
optimizer got it), after the last check step the norms of the parameters'
change against the start kept on the host, leaf by leaf.  The reference
(``references/qwen3_next.py``) takes the same steps after the window, with
the module freed, from weights made again from the seed."""
from __future__ import annotations

import gc
import importlib
import json
import os

import numpy as np

from .. import compare, harness, shapes_lm, traffic as traffic_mod, weights
from ..window import StepWindow, quantile
from .fit import TRACKED, _component_ms, _contexts, _state_leaf

_STEPS = {}     # the reference's jitted step, traced once a process

MODEL_KEYS_LEFT_OUT = ("name", "source", "builder", "reference", "reduced",
                       "published", "deployment", "precision", "optimizer",
                       "init", "assumed")
COUNTERS = ("module.moe.selections_held", "module.moe.selections_total",
            "module.moe.expert_load_max", "module.moe.expert_load_mean",
            "module.recompute.blocks")


def model_of(cfg):
    """The published config's keys, as the builder and the reference take
    them."""
    return {k: v for k, v in cfg.items() if k not in MODEL_KEYS_LEFT_OUT}


def lm_ring(mix, vocab_size, seed):
    """(tokens, next tokens), each [ring*batch, seq] float32 holding whole
    ids uniform over the vocabulary: ``ring_batches`` distinct batches."""
    rows = int(mix["ring_batches"]) * int(mix["batch"])
    ids = traffic_mod.host_rng(seed, 13).integers(
        0, vocab_size, (rows, int(mix["seq_len"]) + 1))
    return (np.ascontiguousarray(ids[:, :-1], np.float32),
            np.ascontiguousarray(ids[:, 1:], np.float32))


def make_weights(seed, spec, init):
    """{name: float32 array} for ``spec``: ``weights.make_weights`` for the
    kinds it knows, and ``log_uniform:lo:hi`` (the log of a uniform draw,
    Gated DeltaNet's ``A_log``) made here, rounded alike."""
    import jax
    import jax.numpy as jnp
    import zlib
    logs = {}
    for name in spec:
        kind = weights._kind(name, init["rules"])
        if kind.startswith("log_uniform:"):
            lo, hi = (float(v) for v in kind.split(":")[1:])
            key = jax.random.fold_in(weights.seed_key(seed, 2),
                                     zlib.crc32(name.encode()) & 0x7FFFFFFF)
            w = jnp.log(jax.random.uniform(
                key, spec[name], jnp.float32,
                minval=max(lo, 1e-4), maxval=hi))
            if init["round_bf16"]:
                w = jax.lax.reduce_precision(w, exponent_bits=8,
                                             mantissa_bits=7)
            logs[name] = w
    out = weights.make_weights(
        seed, {n: s for n, s in spec.items() if n not in logs},
        init["rules"], init["round_bf16"])
    out.update(logs)
    return out


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
    model, opt = model_of(cfg), cfg["optimizer"]
    batch, seq = int(mix["batch"]), int(mix["seq_len"])
    n_check, n_warm = int(mix["check_steps"]), int(mix["warmup_steps"])
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
        else args.seconds
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    sym = builder.symbol(cfg)
    in_shapes = {"data": (batch, seq), "softmax_label": (batch, seq)}

    ring_x, ring_y = lm_ring(mix, model["vocab_size"], args.seed)
    arg_shapes, _, _ = sym.infer_shape(**in_shapes)
    arg_spec = {n: tuple(s) for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in in_shapes}
    names = sorted(arg_spec)
    make_w0 = lambda: make_weights(args.seed, arg_spec, cfg["init"])

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
            now = harness.now()
            if window.step_end(now):
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
    # kept ON THE HOST till the last check step, in the storage type: the
    # values are bfloat16's already, so this start is exact at half the
    # bytes, and the chip has none to spare for it
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
        "required_flops": steps * shapes_lm.train_flops(sym, model,
                                                        **in_shapes),
        "kernel_bytes": {},
    }

    mine = {"grad_norms": s["m1"], "update_norms": s["dN"]}
    losses = list(s["losses"])
    del mod, fused, ring, metric, s
    gc.collect()
    end = {"train_samples_per_s": rate, "setup_s": setup_s}
    if not check_it:        # the calibration tool's probes of size alone
        return {"end_to_end": end, "numbers": {}, "notes": [],
                "memory_peak": memory_peak}
    t_ref, c_ref = harness.now(), clock.mark()
    numbers, notes, refs = check(cfg, mix, devices, make_w0, ring_x, ring_y,
                                 mine, losses, names)
    c_end = clock.mark()
    notes.append("the reference and the comparison took %.1f s (%.1f s of it "
                 "obtaining executables: %d cache hits, %d misses)"
                 % (harness.now() - t_ref, c_end[0] - c_ref[0],
                    c_end[1] - c_ref[1], c_end[2] - c_ref[2]))
    if not finite:
        numbers["loss_gap"] = float("inf")
    return {"end_to_end": end, "obs": obs, "attempted": steps,
            "failed": 0 if finite else steps, "numbers": numbers,
            "notes": notes, "memory_peak": memory_peak, "reduced": reduced,
            "refs": refs, "inputs": (make_w0, ring_x, ring_y),
            "tails": ["steps in window: %d, step p50 %.3f ms, max %.3f ms"
                      % (steps, 1e3 * quantile(obs["step_seconds"], 0.5),
                         1e3 * float(np.max(obs["step_seconds"])))]}


def reference_norms(cfg, mix, devices, make_w0, ring_x, ring_y, names,
                    hooks=None, fault=None, steps=None):
    """(losses, per-leaf norms of the first gradient, per-leaf norms of the
    parameters' change) of the plain reference over the first steps on the
    same rows, or of a control (``hooks``) or a planted fault put in its
    place.  Parameters, both moments and one gradient in float32 are all
    the chip holds: the step gives back norms, never a gradient, it is
    donated its state, and the start is made twice (``make_w0()``), once to
    step from and once to measure the change against."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    model, opt = model_of(cfg), cfg["optimizer"]
    batch = int(mix["batch"])
    steps = steps or int(mix["check_steps"])
    key = (cfg["reference"], json.dumps([model, opt], sort_keys=True), hooks,
           fault, tuple(names), devices[0].id)
    if key not in _STEPS:
        def step(p, m, v, t, x, y):
            loss, g, p, m, v = ref.adam_step(p, m, v, t, x, y, model, opt,
                                             hooks, fault)
            norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(g[n])))
                               for n in names])
            return loss, norms, p, m, v
        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1, 2))
    step = _STEPS[key]
    dev = devices[0]
    with jax.default_matmul_precision("highest"):
        w0 = make_w0()
        params = {n: jax.device_put(w0[n], dev)
                  for n in ref.param_shapes(model)}
        del w0
        mean = jax.tree_util.tree_map(jnp.zeros_like, params)
        var = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, g1 = [], None
        for k in range(steps):
            lo = (k % int(mix["ring_batches"])) * batch
            loss, norms, params, mean, var = step(
                params, mean, var, jnp.float32(k + 1),
                jax.device_put(ring_x[lo:lo + batch], dev),
                jax.device_put(ring_y[lo:lo + batch], dev))
            losses.append(float(loss))
            if k == 0:
                g1 = np.asarray(norms, np.float64)
        del mean, var
        d = compare.leaf_norms(
            jax.jit(lambda a, b: {n: a[n] - b[n] for n in names},
                    donate_argnums=(0,))(params, make_w0()), names)
    return losses, g1, d


def check(cfg, mix, devices, make_w0, ring_x, ring_y, mine, losses, names):
    """Compare what the timed object produced in its first steps with the
    plain reference run over the same rows."""
    g_norm, d_norm = mine["grad_norms"], mine["update_norms"]
    ref_losses, ref_g, ref_d = reference_norms(
        cfg, mix, devices, make_w0, ring_x, ring_y, names)
    numbers, where = compare.training_numbers(
        losses, ref_losses, g_norm, ref_g, d_norm, ref_d)
    notes = ["losses program %s reference %s" % (
        ["%.6f" % v for v in losses], ["%.6f" % v for v in ref_losses]),
        "worst leaves (program/reference norm): gradient %s | update %s | "
        "%d leaves nought to rounding left out of the update" % (
            compare.worst_leaves(names, g_norm, ref_g),
            compare.worst_leaves(
                names, d_norm, ref_d,
                ref_g >= compare.DEAD_LEAF * np.median(ref_g)),
            where["dead_leaves"])]
    if os.environ.get("BENCH_ALL_LEAVES"):      # diagnosis: not the worst four
        notes.append("every leaf's gradient (program/reference norm): "
                     + compare.worst_leaves(names, g_norm, ref_g,
                                            top=len(names)))
    return numbers, notes, {"names": names, "losses": ref_losses,
                            "grad_norms": ref_g, "update_norms": ref_d}

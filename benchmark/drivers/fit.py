"""Training cells: ``Module.fit`` through the fused step, fed by
``NDArrayIter`` over a ring of host batches.

ONE ``fit`` call carries the whole run.  Its first steps are the set-up: the
first ``check_steps`` are the ones the plain reference follows (loss each
step, the optimizer's state after step one, the parameters after the last),
the rest warm the loop to steady state.  At the end of step
``warmup_steps`` the harness waits for the device, opens the window, and
from then on its batch-end callback does one clock read and one array store
a step.  The window closes at the end of the first step that ends at or
after ``--seconds``; the iterator then runs dry and ``fit`` returns.

``fault`` is the drivers' common hook for the tests; a training step is
broken by patching ``FusedTrainStep.run`` instead, so it is unused here."""
from __future__ import annotations

import gc
import importlib
import json
import os

import numpy as np

from .. import compare, harness, shapes, traffic as traffic_mod, weights
from ..window import StepWindow, quantile

_STEPS = {}     # the reference's jitted step, traced once a process

TRACKED = ("data_wait", "fwd_bwd_dispatch", "update", "metric", "sync")


def _contexts(devices):
    import mxnet_tpu as mx
    kind = mx.tpu if devices[0].platform == "tpu" else mx.cpu
    return [kind(i) for i in range(len(devices))]


def _component_ms():
    from mxnet_tpu.observability import telemetry
    snap = telemetry.snapshot()
    return {c: float(snap.get("module.step.%s_ms" % c, {}).get("sum", 0.0))
            for c in TRACKED}


def _state_leaf(state):
    import jax
    return jax.tree_util.tree_leaves(state)[0]


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache

    cfg, mix = loaded["config"], loaded["traffic"]
    model, opt = cfg["model"], cfg["optimizer"]
    batch, n_check = int(mix["batch"]), int(mix["check_steps"])
    n_warm = int(mix["warmup_steps"])
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
        else args.seconds
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    sym = builder.symbol(cfg)
    image = tuple(model["image_shape"])
    in_shapes = {"data": (batch,) + image, "softmax_label": (batch,)}

    # inputs and weights from the seed; the ring is touched (made) before
    # the window, so no step pages in fresh memory
    ring_x, ring_y = traffic_mod.fit_ring(mix, image, model["num_classes"],
                                          args.seed)
    arg_shapes, _, aux_shapes = sym.infer_shape(**in_shapes)
    arg_spec = {n: s for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in in_shapes}
    aux_spec = dict(zip(sym.list_auxiliary_states(), aux_shapes))
    init = cfg["init"]
    w0 = weights.make_weights(args.seed, arg_spec, init["rules"],
                              init["round_bf16"])
    aux0 = weights.make_weights(args.seed, aux_spec, init["rules"], False)
    to_nd = lambda tree: {n: mx.nd.NDArray(a) for n, a in tree.items()}

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
    s = {"step": 0, "losses": [], "snap": {}, "open": None, "close": None}

    def masters_and_momentum():
        fused = mod._fused_step
        names = list(fused.param_names)
        return (names, list(fused._masters),
                [_state_leaf(st) for st in fused.states])

    def on_batch_end(param):
        if window.closed:
            return
        if window.is_open:
            now = harness.now()
            if window.step_end(now):
                ring.stop = True
                spans.close_window()
                s["close"] = {"components": _component_ms(),
                              "traces": executor_cache.trace_counts(),
                              "clock": clock.mark()}
            return
        with spans("fit:batch_end"):
            s["step"] += 1
            n = s["step"]
            if n <= n_check:
                probs = np.asarray(mod.get_outputs()[0].asnumpy(),
                                   np.float64)
                lo = ((n - 1) % int(mix["ring_batches"])) * batch
                labels = ring_y[lo:lo + batch].astype(np.int64)
                s["losses"].append(float(-np.mean(np.log(np.maximum(
                    probs[np.arange(batch), labels], 1e-300)))))
                names, masters, moms = masters_and_momentum()
                if n == 1:
                    s["snap"]["names"] = names
                    s["snap"]["w1"] = [jnp.copy(a) for a in masters]
                    s["snap"]["m1"] = [jnp.copy(a) for a in moms]
                if n == n_check:
                    s["snap"]["wN"] = [jnp.copy(a) for a in masters]
            if n == n_warm - 2:
                tracer.start()
            if n == n_warm:
                jax.block_until_ready(masters_and_momentum()[1])
                s["open"] = {"components": _component_ms(),
                             "traces": executor_cache.trace_counts(),
                             "clock": clock.mark()}
                spans.open_window()
                window.open(harness.now())

    mod.fit(ring, num_epoch=1, eval_metric=metric, kvstore=mix["kvstore"],
            optimizer=opt["name"],
            optimizer_params={"learning_rate": opt["learning_rate"],
                              "momentum": opt["momentum"], "wd": opt["wd"],
                              "multi_precision": opt["multi_precision"]},
            arg_params=to_nd(w0), aux_params=to_nd(aux0),
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

    from mxnet_tpu.ops import pallas_kernels
    with pallas_kernels.trace_scope(
            platform=devices[0].platform, partitioned=len(devices) > 1):
        kernels_on = dict(pallas_kernels.kernel_signature())
    itemsize = 2 if cfg["precision"]["compute"] in ("bfloat16", "float16") \
        else 4
    obs = {
        "cell": loaded["cell"], "chips": len(devices),
        "device_kind": devices[0].device_kind, "trace": reduced,
        "step_seconds": window.step_seconds(), "steps": steps,
        "components_ms": {c: s["close"]["components"][c]
                          - s["open"]["components"][c] for c in TRACKED},
        "retraces_in_window": sum(
            v - s["open"]["traces"].get(k, 0)
            for k, v in s["close"]["traces"].items()),
        "compile": {"compile_s": s["open"]["clock"][0],
                    "cache_hits": s["open"]["clock"][1],
                    "cache_misses": s["open"]["clock"][2]},
        "required_flops": steps * shapes.symbol_train_flops(sym, **in_shapes),
        "kernel_bytes": {"custom-call": steps * shapes.bn_pool_kernel_bytes(
            sym, itemsize, pallas_kernels.bn_sums_eligible,
            **in_shapes)} if any(v != "off" for v in kernels_on.values())
        else {},
    }

    # -- the program's readings, then its state is freed ---------------------
    dev0 = devices[0]
    names = s["snap"]["names"]
    mine = {k: {n: jax.device_put(a, dev0) for n, a in zip(names, s["snap"][k])}
            for k in ("w1", "m1", "wN")}
    losses = list(s["losses"])
    del mod, fused, ring, metric, s
    gc.collect()
    if not check_it:        # the calibration tool's probes of size alone
        return {"end_to_end": {"train_samples_per_s": rate,
                               "setup_s": setup_s},
                "numbers": {}, "notes": [], "memory_peak": memory_peak}
    t_ref, c_ref = harness.now(), clock.mark()
    numbers, notes, refs = check(cfg, mix, devices, w0, ring_x, ring_y, mine,
                                 losses)
    c_end = clock.mark()
    notes.append("the reference and the comparison took %.1f s (%.1f s of it "
                 "obtaining executables: %d cache hits, %d misses)"
                 % (harness.now() - t_ref, c_end[0] - c_ref[0],
                    c_end[1] - c_ref[1], c_end[2] - c_ref[2]))
    if not finite:
        numbers["loss_gap"] = float("inf")
    return {"end_to_end": {"train_samples_per_s": rate, "setup_s": setup_s},
            "obs": obs, "attempted": steps, "failed": 0 if finite else steps,
            "numbers": numbers, "notes": notes, "memory_peak": memory_peak,
            "reduced": reduced, "refs": refs,
            "inputs": (w0, ring_x, ring_y),
            "tails": ["steps in window: %d, step p50 %.3f ms, max %.3f ms"
                      % (steps, 1e3 * quantile(obs["step_seconds"], 0.5),
                         1e3 * float(np.max(obs["step_seconds"])))]}


def reference_steps(cfg, mix, devices, w0, ring_x, ring_y, hooks=None,
                    rows=None, steps=None):
    """The plain reference through the first steps on the same rows:
    (losses, first gradient, parameters after the last step)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    model = dict(cfg["model"])
    opt = cfg["optimizer"]
    batch = int(mix["batch"])
    steps = steps or int(mix["check_steps"])
    if len(devices) > 1:
        mesh = Mesh(np.array(devices), ("dp",))
        rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("dp"))
    else:
        rep = split = jax.sharding.SingleDeviceSharding(devices[0])
    key = (cfg["reference"], json.dumps([model, opt], sort_keys=True),
           hooks, rows, tuple(d.id for d in devices))
    if key not in _STEPS:
        _STEPS[key] = jax.jit(
            lambda p, m, x, y: ref.sgd_step(p, m, x, y, model, opt, hooks,
                                            rows), donate_argnums=(1,))
    step = _STEPS[key]
    params = jax.device_put({n: w0[n] for n in ref.param_shapes(
        dict(model, image_shape=tuple(model["image_shape"])))}, rep)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, g1 = [], None
    for k in range(steps):
        lo = (k % int(mix["ring_batches"])) * batch
        x = jax.device_put(ring_x[lo:lo + batch], split)
        y = jax.device_put(ring_y[lo:lo + batch], split)
        loss, grads, params, mom = step(params, mom, x, y)
        losses.append(float(loss))
        if k == 0:
            g1 = grads
        del grads
    return losses, g1, params


def program_norms(cfg, w0, mine, names):
    """Norms of the first gradient as the optimizer got it, worked out from
    its state after one step (``mom1 = -lr (g + wd w0)``), and of the
    parameters' change over the check steps."""
    import jax
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    opt = cfg["optimizer"]
    lr = float(opt["learning_rate"])

    @jax.jit
    def derive(w0, m1, wn):
        g = {n: -m1[n] / lr - ref.weight_decay_of(n, opt["wd"]) * w0[n]
             for n in names}
        return g, {n: wn[n] - w0[n] for n in names}

    g, d = derive({n: w0[n] for n in names}, mine["m1"], mine["wN"])
    return compare.leaf_norms(g, names), compare.leaf_norms(d, names)


def check(cfg, mix, devices, w0, ring_x, ring_y, mine, losses):
    """Compare what the timed object produced in its first steps with the
    plain reference run over the same rows."""
    import jax
    names = sorted(mine["w1"])
    g_norm, d_norm = program_norms(cfg, w0, mine, names)
    mine.clear()
    ref_losses, ref_g, ref_d = reference_norms(
        cfg, mix, devices, w0, ring_x, ring_y, names)
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
    return numbers, notes, {"names": names, "losses": ref_losses,
                            "grad_norms": ref_g, "update_norms": ref_d}


def reference_norms(cfg, mix, devices, w0, ring_x, ring_y, names, hooks=None,
                    rows=None):
    """(losses, per-leaf norms of the first gradient, per-leaf norms of the
    parameters' change) of the reference, or of a control or a planted fault
    put in its place."""
    import jax
    losses, g1, wn = reference_steps(cfg, mix, devices, w0, ring_x, ring_y,
                                     hooks, rows)
    g = compare.leaf_norms(g1, names)
    d = compare.leaf_norms(
        jax.jit(lambda a, b: {n: a[n] - b[n] for n in names})(
            wn, {n: w0[n] for n in names}), names)
    return losses, g, d

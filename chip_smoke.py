#!/usr/bin/env python3
"""chip_smoke.py -- the quickest proof that the system still starts on a TPU.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the models the repo serves:

  train   ResNet-50 (bf16, batch 32, SGD+momentum, f32 masters) through
          ``Module.fit`` -> ``FusedTrainStep`` on ``mx.tpu(0)``;
  serve   the trained weights through ``serving.Server`` (bucketed
          predictors, warmup, mixed-size requests), compared with a
          ``mx.cpu()`` ``Predictor`` on the same params;
  decode  ``TransformerLM`` at GPT-2-small widths through
          ``PagedTransformerDecoder`` over a ``KVBlockPool`` (prefix hit,
          copy-on-write, donated pools), compared with the model's own
          full forward on the chip;
  train_dp  the train phase again over four chips (``kvstore='tpu_ici'``,
          batch 128) when the host has four; reported as not run otherwise.

Weights are random from a seed; depth is never cut here.  It refuses to run
unless the default JAX backend is a TPU, exits non-zero when any phase
raises, and prints as its last line of standard output one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}`` and no other key
(the phase reports go on the lines before it).  Timings are OBSERVATIONS of
this one run (each ends in ``block_until_ready``), not benchmark metrics.

The phases are importable functions taking a size: ``tests/
test_chip_smoke.py`` calls them at ``TOY`` size on the CPU; ``python
chip_smoke.py`` always runs ``FULL``.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

FULL = {
    "train": dict(num_layers=50, image_shape="3,224,224", num_classes=1000,
                  batch=32, steps=60, dtype="bfloat16", lr=0.05),
    "serve": dict(max_batch_size=32, rows=(1, 5, 32, 17, 8, 3)),
    "decode": dict(vocab=50257, embed=768, heads=12, ffn=3072, layers=12,
                   seq=1024, page=16, pages=64, slots=4, new_tokens=8),
}
# the same phases at a size the CPU finishes in seconds (tier-1)
TOY = {
    "train": dict(num_layers=8, image_shape="3,16,16", num_classes=10,
                  batch=8, steps=6, dtype="bfloat16", lr=0.05),
    "serve": dict(max_batch_size=4, rows=(1, 3, 4, 2)),
    "decode": dict(vocab=64, embed=32, heads=2, ffn=128, layers=1, seq=64,
                   page=8, pages=24, slots=3, new_tokens=4),
}

# tests/test_consistency_sweep.py's cpu-vs-tpu tolerances: MXU-backed f32
# ops, and the bf16 lane
MXU_TOL = 2e-2
BF16_TOL = 6e-2


class CompileClock:
    """Seconds JAX spent obtaining executables (trace + lower + backend
    compile or persistent-cache load), and persistent-cache hits/misses,
    read off ``jax.monitoring`` -- one listener for the whole run."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return (self.seconds, self.hits, self.misses)

    def since(self, mark):
        return {"compile_s": round(self.seconds - mark[0], 2),
                "cache_hits": self.hits - mark[1],
                "cache_misses": self.misses - mark[2]}


def _platforms(arrays):
    return {d.platform for a in arrays for d in a.devices()}


def _median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2]


def rows_agree(got, want, tol, what):
    """Compare rows of logits (or log-probabilities) with a reference the
    way a deep network's error behaves: every entry within ``tol`` of the
    row's own range, and the same argmax wherever the reference's top-2
    margin exceeds twice the row's error.  An elementwise allclose on
    softmax PROBABILITIES is the wrong yardstick both ways: vacuous when
    the softmax saturates, and failing at 0.15 for a bf16 ResNet-50 whose
    logits agree to 1% (both observed on the chip, PR 21).  Returns
    (worst error / range, rows whose argmax was checked, rows too close
    to call)."""
    import numpy as np
    worst, checked, ambiguous = 0.0, 0, 0
    for g, w in zip(got, want):
        span = max(float(np.max(w) - np.min(w)), 1.0)
        err = float(np.max(np.abs(g - w)))
        assert err <= tol * span, \
            "%s off the reference by %.4g on a row spanning %.4g " \
            "(tolerance %g of the span)" % (what, err, span, tol)
        worst = max(worst, err / span)
        top2 = np.partition(w, -2)[-2:]
        if top2[1] - top2[0] > 2 * err:
            assert int(np.argmax(g)) == int(np.argmax(w)), \
                "%s picks a different argmax than the reference" % what
            checked += 1
        else:
            ambiguous += 1
    return worst, checked, ambiguous


def device_report():
    """What JAX found, as JAX reports it."""
    import importlib.metadata as md

    import jax
    import jaxlib
    dev = jax.devices()[0]
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()),
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__, "libtpu": libtpu}}


def result_line(device, ok=True):
    """The last line of standard output: one JSON object with exactly the
    keys ``ok`` and ``device`` (``platform``, ``kind``, ``count``) -- the
    driver parses it and takes no other key.  Everything else the run
    observed goes on the lines before it."""
    return json.dumps({"ok": bool(ok),
                       "device": {"platform": str(device["platform"]),
                                  "kind": str(device["kind"]),
                                  "count": int(device["count"])}})


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(size, contexts, kvstore="local", clock=None):
    """``Module.fit`` on a repeated synthetic batch.  Returns the report
    plus what the serve phase reuses: the symbol, its dtype, the trained
    params and the batch."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.ops import pallas_kernels

    cfg = size["train"]
    platform = jax.default_backend()
    batch, steps, classes = cfg["batch"], cfg["steps"], cfg["num_classes"]
    sym = models.resnet.get_symbol(
        num_classes=classes, num_layers=cfg["num_layers"],
        image_shape=cfg["image_shape"], dtype=cfg["dtype"])
    shape = tuple(int(d) for d in cfg["image_shape"].split(","))
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, (batch,) + shape).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.float32)
    it = mx.io.NDArrayIter(np.tile(x, (steps, 1, 1, 1)), np.tile(y, steps),
                           batch_size=batch, shuffle=False)

    mx.random.seed(0)
    mod = mx.mod.Module(sym, context=contexts)
    losses, step_s = [], []
    t_last = [time.perf_counter()]

    def on_batch(param):
        # the step's real end: its updated parameters exist on the device
        exe = mod._exec_group.execs[0]
        jax.block_until_ready(
            [exe.arg_dict[n]._h.array for n in mod._param_names])
        now = time.perf_counter()
        step_s.append(now - t_last[0])
        t_last[0] = now
        losses.append(float(param.eval_metric.get()[1]))
        param.eval_metric.reset()

    mark = clock.mark() if clock else None
    t_last[0] = time.perf_counter()
    mod.fit(it, num_epoch=1, eval_metric="ce", kvstore=kvstore,
            optimizer="sgd",
            optimizer_params={"learning_rate": cfg["lr"], "momentum": 0.9,
                              "wd": 1e-4, "multi_precision": True},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            batch_end_callback=on_batch)

    fused = mod._fused_step
    assert fused is not None and fused.ran, \
        "Module.fit did not train through the fused step"
    assert len(losses) == steps, (len(losses), steps)
    assert all(math.isfinite(v) for v in losses), losses
    ln_k = math.log(classes)
    assert 0.5 * ln_k < losses[0] < 2.0 * ln_k, \
        "first loss %.3f is not near ln(%d)=%.3f" % (losses[0], classes, ln_k)
    assert min(losses[-3:]) < losses[0] - 0.1 * ln_k, \
        "loss did not fall: %s" % (losses,)
    held = [a._h.array for exe in mod._exec_group.execs
            for a in exe.arg_dict.values()] + list(fused._masters)
    assert _platforms(held) == {platform}, _platforms(held)
    if cfg["dtype"] != "float32":
        assert any(fused.mixed), "multi_precision kept no f32 masters"

    # the modes the step's trace resolved: XLA partitions the dp step by
    # itself, where the Mosaic kernels resolve off (docs/kernels.md)
    with pallas_kernels.trace_scope(platform=platform,
                                    partitioned=len(contexts) > 1):
        kernels = dict(pallas_kernels.kernel_signature())
    report = {
        "fused_step_ran": True,
        "kernels": kernels,
        "loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4),
        "first_step_s": round(step_s[0], 2),
        "steady_step_ms": round(_median(step_s[2:]) * 1e3, 2),
        "param_platforms": sorted(_platforms(held)),
    }
    if clock:
        report.update(clock.since(mark))

    if len(contexts) > 1:
        from mxnet_tpu.module.fused_step import collective_counts
        execs = mod._exec_group.execs
        shard_devs = [next(iter(e.outputs[0]._h.array.devices()))
                      for e in execs]
        assert len(set(shard_devs)) == len(contexts), shard_devs
        assert {d.platform for d in shard_devs} == {platform}, shard_devs
        rows = [int(e.outputs[0].shape[0]) for e in execs]
        assert rows == [batch // len(contexts)] * len(contexts), rows
        counts = collective_counts(fused.compiled_hlo())
        assert counts.get("all-reduce", 0) >= 1, counts
        report["shard_devices"] = [str(d) for d in shard_devs]
        report["collectives"] = counts

    arg_params, aux_params = mod.get_params()
    return report, {"symbol": sym, "dtype": cfg["dtype"], "batch": x,
                    "arg_params": arg_params, "aux_params": aux_params}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(size, trained, clock=None):
    """The trained model behind ``serving.Server``: warmup, mixed-size
    requests, agreement with a cpu ``Predictor``, zero retraces."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.predict import Predictor

    cfg = size["serve"]
    platform = jax.default_backend()
    sym, x = trained["symbol"], trained["batch"]
    arg_params, aux_params = trained["arg_params"], trained["aux_params"]
    tol = MXU_TOL if trained["dtype"] == "float32" else BF16_TOL
    feat = tuple(x.shape[1:])
    mark = clock.mark() if clock else None
    server = serving.Server(max_batch_size=cfg["max_batch_size"])
    try:
        model = server.add_model("resnet", sym, arg_params, aux_params,
                                 input_shapes={"data": feat})
        warm = server.warmup()
        report = {"buckets": list(model.buckets)}
        if clock:
            report.update(clock.since(mark))

        # the cpu reference: same params, bound once at the largest bucket
        params = {"arg:%s" % k: v for k, v in arg_params.items()}
        params.update({"aux:%s" % k: v for k, v in aux_params.items()})
        top = max(cfg["rows"])
        ref = Predictor(sym.tojson(), params, {"data": (top,) + feat},
                        ctx=mx.cpu())

        def reference(rows):
            padded = np.zeros((top,) + feat, np.float32)
            padded[:len(rows)] = rows
            ref.forward(data=padded)
            return ref.get_output(0).asnumpy()[:len(rows)]

        reference(x[:1])    # the reference's own trace stays out of the window
        tiny = np.finfo(np.float32).tiny
        worst, worst_p, checked, ambiguous = 0.0, 0.0, 0, 0
        req_s, top_p = [], []
        with executor_cache.watch_traces() as watch:
            for i, n in enumerate(cfg["rows"]):
                rows = np.take(x, np.arange(i, i + n) % len(x), axis=0)
                t0 = time.perf_counter()
                out = server.submit("resnet", {"data": rows}, timeout=600)
                req_s.append(time.perf_counter() - t0)   # result is host-side
                got = np.asarray(out[0])
                want = reference(rows)
                assert got.shape == want.shape == (n, want.shape[1]), \
                    (got.shape, want.shape)
                assert np.all(np.isfinite(got)) and np.allclose(
                    got.sum(axis=1), 1.0, atol=1e-3), "not probabilities"
                # log-probabilities are the logits up to a per-row constant
                w, c, a = rows_agree(np.log(np.maximum(got, tiny)),
                                     np.log(np.maximum(want, tiny)), tol,
                                     "served log-probabilities")
                worst, checked, ambiguous = \
                    max(worst, w), checked + c, ambiguous + a
                worst_p = max(worst_p, float(np.max(np.abs(got - want))))
                top_p.extend(np.max(got, axis=1))
        assert watch.total() == 0, \
            "serving retraced after warmup: %s" % (watch.delta(),)
        # a saturated (one-hot) softmax would leave nothing to compare but
        # the argmax; a 20-step fit leaves BatchNorm's moving statistics
        # that far off, which is why the train phase runs 60
        assert _median(top_p) < 0.999, \
            "served probabilities are saturated: median top-1 %.6f" \
            % _median(top_p)
        exes = [model.predictor_for(b)._exe for b in model.buckets]
        held = [a._h.array for exe in exes
                for a in list(exe.arg_dict.values()) + list(exe.outputs)]
        assert _platforms(held) == {platform}, _platforms(held)
        report.update({
            "warmup_traces": warm["resnet"]["traces_first_pass"],
            "requests": len(cfg["rows"]), "retraces_after_warmup": 0,
            "max_rel_logprob_diff_vs_cpu": round(worst, 5), "tolerance": tol,
            "argmax_checked": checked, "argmax_ambiguous": ambiguous,
            "max_abs_prob_diff_vs_cpu": round(worst_p, 5),
            "median_top_prob": round(float(_median(top_p)), 4),
            "median_request_ms": round(_median(req_s) * 1e3, 2),
            "output_platforms": sorted(_platforms(held)),
        })
        return report
    finally:
        server.close()


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def decode_phase(size, clock=None):
    """Greedy decode through the paged-KV decoder, checked against the
    model's own full forward on the same device (teacher-forced on the
    decoder's tokens, so one near-tie cannot cascade)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache
    from mxnet_tpu.gluon.model_zoo import transformer_lm
    from mxnet_tpu.serving import KVBlockPool, PagedTransformerDecoder

    cfg = size["decode"]
    platform = jax.default_backend()
    ctx = mx.context.accelerator()
    vocab, seq, page = cfg["vocab"], cfg["seq"], cfg["page"]
    new = cfg["new_tokens"]

    mx.random.seed(0)
    lm = transformer_lm(vocab, embed_dim=cfg["embed"],
                        num_heads=cfg["heads"], num_layers=cfg["layers"],
                        seq_len=seq, ffn_dim=cfg["ffn"])
    lm.initialize(mx.initializer.Normal(0.02), ctx=ctx)
    lm.hybridize()
    mark = clock.mark() if clock else None
    lm(mx.nd.zeros((1, seq), ctx=ctx))      # materializes deferred shapes
    rng = np.random.RandomState(0)
    # the zoo initializes the position table to zeros; give positions work
    lm.pos.set_data(mx.nd.array(
        rng.normal(0, 0.02, (seq, cfg["embed"])).astype(np.float32),
        ctx=ctx))

    pool = KVBlockPool(cfg["layers"], cfg["heads"],
                       cfg["embed"] // cfg["heads"],
                       num_pages=cfg["pages"], page_size=page,
                       name="smoke.kv")
    dec = PagedTransformerDecoder(lm.decode_param_arrays(), lm.config,
                                  slot_count=cfg["slots"], pool=pool,
                                  name="smoke")
    step_s = []
    try:
        dec.warmup()
        report = {}
        if clock:
            report.update(clock.since(mark))

        def drain():
            while dec.pending():
                t0 = time.perf_counter()
                dec.step()      # ends in a host fetch of tokens + logits
                step_s.append(time.perf_counter() - t0)

        shared = rng.randint(0, vocab, size=2 * page)
        forked = np.concatenate([shared[:page],
                                 rng.randint(0, vocab, size=3)])
        with executor_cache.watch_traces() as watch:
            # mixed lengths decoding together, one of them the prompt the
            # next two share a prefix with
            first = [dec.submit(p, max_new_tokens=new) for p in
                     (shared, rng.randint(0, vocab, size=5),
                      rng.randint(0, vocab, size=page + 3))]
            drain()
            clones0 = pool.stats()["cow_clones"]
            again = dec.submit(shared, max_new_tokens=new)   # full hit: COW
            fork = dec.submit(forked, max_new_tokens=new)    # one-page hit
            drain()
        assert watch.total() == 0, \
            "decode retraced after warmup: %s" % (watch.delta(),)
        assert again.prefix_pages == 2 and fork.prefix_pages == 1, \
            (again.prefix_pages, fork.prefix_pages)
        assert pool.stats()["cow_clones"] == clones0 + 1, pool.stats()
        assert _platforms([pool.k_pool, pool.v_pool]) == {platform}
        streams = first + [again, fork]
        assert again.outputs()[0] == first[0].outputs()[0], \
            "prefix-cached stream decoded different tokens"
    finally:
        dec.close()

    # reference: one full causal forward per stream over prompt + its own
    # generated tokens (teacher-forced, so one near-tie cannot cascade);
    # row n-1+j must reproduce generated token j
    checked = ambiguous = 0
    worst = 0.0
    for stream in streams:
        toks, logits = stream.outputs()
        assert len(toks) == new, (len(toks), new)
        n = len(stream.prompt)
        full = np.zeros((1, seq), np.float32)
        full[0, :n + new] = list(stream.prompt) + toks
        ref = lm(mx.nd.array(full, ctx=ctx)).asnumpy()[0, n - 1:n - 1 + new]
        assert toks == [int(t) for t in np.argmax(logits, axis=1)]
        w, c, a = rows_agree(logits, ref, MXU_TOL, "decode logits")
        worst, checked, ambiguous = max(worst, w), checked + c, ambiguous + a
    assert checked > 0, "no decoded token could be checked"
    report.update({
        "streams": len(streams), "tokens_checked": checked,
        "tokens_ambiguous": ambiguous, "retraces_after_warmup": 0,
        "prefix_pages": [again.prefix_pages, fork.prefix_pages],
        "cow_clones": 1, "max_rel_logit_diff": round(worst, 5),
        "tolerance": MXU_TOL, "steps": len(step_s),
        "median_step_ms": round(_median(step_s) * 1e3, 2),
        "pool_platforms": [platform],
    })
    return report


# ---------------------------------------------------------------------------

def main():
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        print("chip_smoke: JAX_PLATFORMS=cpu -- this script proves the "
              "chip path and refuses to run on the CPU", file=sys.stderr)
        return 2
    import jax
    if jax.default_backend() != "tpu":
        print("chip_smoke: the default JAX backend is %r, not a TPU; "
              "nothing was run" % jax.default_backend(), file=sys.stderr)
        return 2

    import mxnet_tpu as mx

    device = device_report()
    print("device:", json.dumps(device), flush=True)
    print("jax compilation cache:", jax.config.jax_compilation_cache_dir,
          flush=True)
    clock = CompileClock()
    phases = {}

    def run(name, fn, *args, **kw):
        t0 = time.perf_counter()
        out = fn(*args, clock=clock, **kw)
        report = out[0] if isinstance(out, tuple) else out
        report["wall_s"] = round(time.perf_counter() - t0, 1)
        phases[name] = report
        print("phase %s (observations of this run, not benchmark metrics): "
              "%s" % (name, json.dumps(report)), flush=True)
        return out

    _, trained = run("train", train_phase, FULL, [mx.tpu(0)])
    run("serve", serve_phase, FULL, trained)
    del trained
    run("decode", decode_phase, FULL)
    if len(jax.local_devices()) >= 4:
        dp = dict(FULL, train=dict(FULL["train"], batch=128))
        run("train_dp4", train_phase, dp, [mx.tpu(i) for i in range(4)],
            kvstore="tpu_ici")
    else:
        phases["train_dp4"] = "not run: %d local device(s), needs 4" \
            % len(jax.local_devices())
        print("phase train_dp4:", phases["train_dp4"], flush=True)

    print("summary (observations of this run, not benchmark metrics):",
          json.dumps({"versions": device["versions"], "phases": phases}),
          flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

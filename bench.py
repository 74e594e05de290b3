"""Driver benchmark: ResNet-50 batch-32 on one chip — training AND inference.

The north-star metric (BASELINE.json) is *training* images/sec, so that is
the primary JSON field; inference throughput (the reference's
benchmark_score.py, P100 713.17 img/s, docs/faq/perf.md:138-148) rides
along, with achieved TFLOP/s and MFU derived from XLA's compiled cost
analysis of the framework's own programs.

Measurement methodology (the device-side ceiling: what the chip does
when the host is out of the loop):
- N iterations run INSIDE one jitted lax.fori_loop; every iteration is
  data-dependent on the previous one (training chains on updated params,
  inference perturbs the input with tanh(mean(logits))*1e-12), so no
  execution can be elided, deduplicated, or overlapped out of the window;
- the window ends with a real host fetch of a scalar accumulator that
  transitively depends on every iteration;
- throughput is the MARGINAL rate between a small and a large window,
  cancelling the fixed dispatch+fetch latency of a call;
- per-iteration FLOPs come from XLA cost analysis of the single-step
  compiled program.

The main mode is a device measurement and refuses to run anywhere else:
no TPU backend, or a device kind missing from PEAK_TFLOPS, is an error.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""
from __future__ import annotations

import json
import time

import numpy as np


def _load_traceview():
    """Import tools/traceview.py by path (the smokes assert on its
    summaries and exit codes without needing it on sys.path)."""
    import importlib.util
    import os
    tv_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tools", "traceview.py")
    spec = importlib.util.spec_from_file_location("_bench_traceview",
                                                  tv_path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


BASELINE_TRAIN_IMG_S = 181.53  # ResNet-50 training, batch 32, P100 (BASELINE.md)
BASELINE_INFER_IMG_S = 713.17  # ResNet-50 inference, batch 32, P100
BATCH = 32
N_SMALL = 5
N_LARGE = 25
REPS = 5

# bf16 matmul peak by device kind (public spec sheets).  A device kind
# that is not here is an error in main(), never a null MFU.
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v5": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def _cost_of(compiled):
    """(flops, bytes_accessed) from an AOT-compiled computation's cost
    analysis.  bytes_accessed is XLA's estimate of HBM traffic for one
    execution — the numerator of the roofline fraction."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0, 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not ca:
        return 0.0, 0.0
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)))


def _flops_of(compiled):
    return _cost_of(compiled)[0]


def _bench_hbm(jax):
    """Measured achievable HBM bandwidth: a STREAM-style triad
    (y = a*y + x) over 512 MiB f32 arrays inside one chained fori_loop —
    3 array passes (read x, read y, write y) per iteration, loop-carried
    so XLA:TPU executes every pass (measured 760 GB/s on v5e, 93% of
    the 819 GB/s spec).  Returns bytes/sec.

    CPU caveat: XLA:CPU blocks elementwise recurrences ACROSS loop
    iterations, so a cpu smoke run over-reports — the number is only
    meaningful on the chip (cpu runs of bench.py are smoke-only
    already)."""
    import jax.numpy as jnp
    n = 128 * 1024 * 1024  # 128M f32 = 512 MiB per array

    @jax.jit
    def loop(k, x, y):
        def body(i, carry):
            x, y = carry
            return (x, y * jnp.float32(0.999) + x)
        x, y = jax.lax.fori_loop(0, k, body, (x, y))
        return jnp.sum(y)

    @jax.jit
    def make():
        i = jnp.arange(n, dtype=jnp.float32)
        return i % 997.0 * 1e-3, i % 991.0 * 1e-3

    x, y = make()

    def run(k, x, y):
        return float(loop(k, x, y))  # host fetch

    sec_per_iter = _timed_windows(run, x, y)
    return 3.0 * n * 4 / sec_per_iter


def _timed_windows(loop_fn, *args, reps=None):
    """Marginal seconds/iteration between a small and an ADAPTIVELY
    SIZED large window; median of paired marginals across reps.
    loop_fn must end in a host fetch.

    Estimator forensics from rounds 4-5, recorded so the choice is not
    re-litigated: the fixed per-call cost C (dispatch + fetch) jitters
    between calls.  (a) min-of-paired-diffs (r04) is biased FAST —
    a contention spike landing on a pair's small window deflates that
    pair's difference, and the min picks exactly the most deflated pair
    (observed: f32 inference "99% MFU"); (b) difference-of-per-window-
    minima is garbage whenever (N_large-N_small)*iter is comparable to
    C's jitter (observed: 4 TB/s "HBM bandwidth", 5x the spec).  So:
    size the large window such that the marginal COMPUTE is ~1s — an
    order of magnitude above C jitter — and take the median of paired
    marginals, which cancels the slowly-varying part of C pairwise and
    is robust to spikes in either direction."""
    if reps is None:
        reps = REPS
    loop_fn(2, *args)  # warm (compile + caches)

    def pair(n_lo, n_hi):
        t0 = time.perf_counter()
        loop_fn(n_lo, *args)
        t1 = time.perf_counter()
        loop_fn(n_hi, *args)
        t2 = time.perf_counter()
        return ((t2 - t1) - (t1 - t0)) / (n_hi - n_lo)

    # scale probe -> window size targeting ~1s of marginal compute
    rough = max(pair(N_SMALL, N_LARGE), 1e-5)
    n_large = N_SMALL + max(N_LARGE - N_SMALL,
                            min(int(1.0 / rough), 2000))
    for attempt in range(3):
        estimates = sorted(e for e in
                           (pair(N_SMALL, n_large) for _ in range(reps))
                           if e > 0)
        if estimates:
            return estimates[len(estimates) // 2]
        # pathological host noise; re-measure rather than emit a
        # negative/infinite rate in the JSON of record
    raise RuntimeError("non-positive marginal sec/iter after retries")


def _build_resnet_exe(mx, ctx, rng, grad_req):
    from mxnet_tpu.models import resnet
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    exe = sym.simple_bind(ctx, grad_req=grad_req,
                          data=(BATCH, 3, 224, 224),
                          softmax_label=(BATCH,))
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = rng.uniform(0, 1, arr.shape).astype(np.float32)
        elif name == "softmax_label":
            arr[:] = rng.randint(0, 1000, arr.shape).astype(np.float32)
        else:
            arr[:] = rng.normal(0, 0.01, arr.shape).astype(np.float32)
    return exe


def _bench_inference(mx, jax, ctx, rng, compute_dtype=None):
    """compute_dtype=bfloat16: params and data stored/computed half-width —
    the framework's native TPU inference mode."""
    import jax.numpy as jnp
    exe = _build_resnet_exe(mx, ctx, rng, grad_req="null")
    prog = exe._prog
    arg_names, aux_names = prog.arg_names, prog.aux_names

    def maybe_cast(name, a):
        if compute_dtype is not None and a.dtype == jnp.float32 \
                and name != "softmax_label":
            return a.astype(compute_dtype)
        return a

    arg_vals = tuple(maybe_cast(n, exe.arg_dict[n]._h.array)
                     for n in arg_names)
    aux_vals = tuple(exe.aux_dict[n]._h.array for n in aux_names)
    flops = _flops_of(
        exe._fwd_jit.lower(arg_vals, aux_vals, (), False).compile())

    @jax.jit
    def loop(n, arg_vals, aux_vals):
        amap0 = dict(zip(arg_names, arg_vals))
        aux_map = dict(zip(aux_names, aux_vals))

        def body(i, carry):
            data, acc = carry
            amap = dict(amap0)
            amap["data"] = data
            outs, _ = prog.evaluate(amap, aux_map, (), False)
            m = jnp.mean(outs[0].astype(jnp.float32))
            # chain: next input depends (negligibly) on this output (the
            # factor is a runtime value, so XLA cannot fold the dependence)
            return (data * (1.0 + jnp.tanh(m) * 1e-12).astype(data.dtype),
                    acc + m)

        _, acc = jax.lax.fori_loop(0, n, body,
                                   (amap0["data"], jnp.float32(0.0)))
        return acc

    def run(n, arg_vals, aux_vals):
        return float(loop(n, arg_vals, aux_vals))  # host fetch

    sec_per_iter = _timed_windows(run, arg_vals, aux_vals)
    return BATCH / sec_per_iter, flops / sec_per_iter


def build_resnet_train_loop(mx, jax, ctx, rng, lr=0.01, momentum=0.9,
                            compute_dtype=None):
    """The fused ResNet-50 SGD-momentum training loop used by BOTH the
    throughput bench below and tools/roofline_probe.py (one
    construction to keep in sync).  Returns
    (loop, params0, mom0, aux0, flops, step_bytes) where loop(n, ...)
    runs n chained steps on-device and returns a scalar accumulator.

    compute_dtype=bfloat16 is the mixed-precision mode the framework's
    FusedTrainStep runs under optimizer multi_precision: f32 master
    weights and momentum, half-width cast inside the step, f32
    gradients through the cast's vjp (ref semantics:
    optimizer.py:446-476 mp_sgd_mom_update)."""
    import jax.numpy as jnp
    exe = _build_resnet_exe(mx, ctx, rng, grad_req="write")
    prog = exe._prog
    arg_names, aux_names = prog.arg_names, prog.aux_names
    param_names = [n for n in arg_names
                   if n not in ("data", "softmax_label")]
    param_set = set(param_names)
    other_names = [n for n in arg_names if n not in param_set]
    other_vals = tuple(exe.arg_dict[n]._h.array for n in other_names)
    if compute_dtype is not None:
        other_vals = tuple(
            v.astype(compute_dtype)
            if n == "data" and v.dtype == jnp.float32 else v
            for n, v in zip(other_names, other_vals))
    params0 = tuple(exe.arg_dict[n]._h.array for n in param_names)
    aux0 = tuple(exe.aux_dict[n]._h.array for n in aux_names)

    def sgd_step(params, mom, aux):
        amap = dict(zip(other_names, other_vals))
        aux_map = dict(zip(aux_names, aux))

        def f(pvals):
            m = dict(amap)
            if compute_dtype is not None:
                pvals = [p.astype(compute_dtype) for p in pvals]
            m.update(zip(param_names, pvals))
            outs, new_aux = prog.evaluate(m, aux_map, (), True)
            return outs, tuple(new_aux[n] for n in aux_names)

        (outs, new_aux), vjp_fn = jax.vjp(f, params)
        heads = [jnp.ones_like(o) for o in outs]
        zeros_aux = tuple(jnp.zeros_like(a) for a in new_aux)
        (grads,) = vjp_fn((heads, zeros_aux))
        new_params, new_mom = [], []
        for w, g, m in zip(params, grads, mom):
            m2 = momentum * m - lr * g.astype(w.dtype)
            new_params.append(w + m2)
            new_mom.append(m2)
        return tuple(new_params), tuple(new_mom), new_aux, outs

    # per-step flops + HBM bytes from the compiled single step
    mom0 = tuple(jnp.zeros_like(p) for p in params0)
    flops, step_bytes = _cost_of(
        jax.jit(sgd_step).lower(params0, mom0, aux0).compile())

    @jax.jit
    def loop(n, params, mom, aux):
        def body(i, carry):
            params, mom, aux, acc = carry
            params, mom, aux, outs = sgd_step(params, mom, aux)
            return (params, mom, aux,
                    acc + jnp.mean(outs[0].astype(jnp.float32)))

        _, _, _, acc = jax.lax.fori_loop(
            0, n, body, (params, mom, aux, jnp.float32(0.0)))
        return acc

    return loop, params0, mom0, aux0, flops, step_bytes


def _bench_training(mx, jax, ctx, rng, lr=0.01, momentum=0.9,
                    compute_dtype=None):
    loop, params0, mom0, aux0, flops, step_bytes = \
        build_resnet_train_loop(mx, jax, ctx, rng, lr, momentum,
                                compute_dtype)

    def run(n, params, mom, aux):
        return float(loop(n, params, mom, aux))  # host fetch

    sec_per_iter = _timed_windows(run, params0, mom0, aux0)
    return BATCH / sec_per_iter, flops / sec_per_iter, sec_per_iter, \
        step_bytes


def _bench_lstm(mx, jax, ctx, rng, batch=32, seq=35, hidden=200,
                embed=200, layers=2, vocab=10000):
    """BASELINE.json config 4: the LSTM language model of
    examples/rnn/lstm_bucketing.py (fused RNN cells — cudnn_rnn-inl.h's
    capability), one full SGD training step per iteration, chained.
    Returns (tokens/sec, flops/sec)."""
    import jax.numpy as jnp
    stack = mx.rnn.FusedRNNCell(hidden, num_layers=layers, mode="lstm")
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    net = mx.sym.Embedding(data=data, input_dim=vocab, output_dim=embed,
                           name="embed")
    outputs, _ = stack.unroll(seq, inputs=net, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(data=pred, num_hidden=vocab, name="pred")
    flat = mx.sym.Reshape(label, shape=(-1,))
    sym = mx.sym.SoftmaxOutput(data=pred, label=flat, name="softmax")

    exe = sym.simple_bind(ctx, grad_req="write", data=(batch, seq),
                          softmax_label=(batch, seq))
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = rng.randint(0, vocab, arr.shape).astype(np.float32)
        elif name == "softmax_label":
            arr[:] = rng.randint(0, vocab, arr.shape).astype(np.float32)
        else:
            arr[:] = rng.normal(0, 0.02, arr.shape).astype(np.float32)
    prog = exe._prog
    arg_names, aux_names = prog.arg_names, prog.aux_names
    param_names = [n for n in arg_names
                   if n not in ("data", "softmax_label")]
    other_names = [n for n in arg_names if n not in set(param_names)]
    other_vals = tuple(exe.arg_dict[n]._h.array for n in other_names)
    params0 = tuple(exe.arg_dict[n]._h.array for n in param_names)
    aux0 = tuple(exe.aux_dict[n]._h.array for n in aux_names)
    # fixed PRNG keys for the graph's rng nodes (dropout etc.): loop-
    # invariant is fine for a throughput measurement
    rng_keys = tuple(jax.random.PRNGKey(i)
                     for i in range(len(prog.rng_nodes)))
    lr = 0.01

    def sgd_step(params, aux):
        amap = dict(zip(other_names, other_vals))
        aux_map = dict(zip(aux_names, aux))

        def f(pvals):
            m = dict(amap)
            m.update(zip(param_names, pvals))
            outs, new_aux = prog.evaluate(m, aux_map, rng_keys, True)
            return outs, tuple(new_aux[n] for n in aux_names)

        (outs, new_aux), vjp_fn = jax.vjp(f, params)
        heads = [jnp.ones_like(o) for o in outs]
        zeros_aux = tuple(jnp.zeros_like(a) for a in new_aux)
        (grads,) = vjp_fn((heads, zeros_aux))
        new_params = tuple(w - lr / (batch * seq) * g
                           for w, g in zip(params, grads))
        return new_params, new_aux, outs

    flops, _ = _cost_of(jax.jit(sgd_step).lower(params0, aux0).compile())

    @jax.jit
    def loop(n, params, aux):
        def body(i, carry):
            params, aux, acc = carry
            params, aux, outs = sgd_step(params, aux)
            return (params, aux,
                    acc + jnp.mean(outs[0].astype(jnp.float32)))

        _, _, acc = jax.lax.fori_loop(0, n, body,
                                      (params, aux, jnp.float32(0.0)))
        return acc

    def run(n, params, aux):
        return float(loop(n, params, aux))

    sec_per_iter = _timed_windows(run, params0, aux0)
    return batch * seq / sec_per_iter, flops / sec_per_iter


def main():
    import jax
    import mxnet_tpu as mx

    if not mx.on_tpu():
        raise SystemExit(
            "bench.py measures the chip: the default JAX backend is %r, "
            "not a TPU.  A CPU run has no throughput or MFU to report "
            "(the --*-smoke modes are the CPU correctness checks)."
            % jax.default_backend())
    ctx = mx.tpu()
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_TFLOPS:
        raise SystemExit(
            "bench.py has no peak-FLOP/s entry for device kind %r; add it "
            "to PEAK_TFLOPS with its source before reporting an MFU" % kind)
    peak = PEAK_TFLOPS[kind]
    rng = np.random.RandomState(0)

    import jax.numpy as jnp
    cdt = jnp.bfloat16  # the framework's native TPU precision mode
    infer_img_s, infer_flops_s = _bench_inference(mx, jax, ctx, rng,
                                                  compute_dtype=cdt)
    (train_img_s, train_flops_s, train_sec_iter,
     train_bytes) = _bench_training(mx, jax, ctx, rng, compute_dtype=cdt)
    infer32_img_s, infer32_flops_s = _bench_inference(mx, jax, ctx, rng)
    train32_img_s, train32_flops_s, _, _ = _bench_training(mx, jax, ctx,
                                                           rng)
    hbm_bps = _bench_hbm(jax)
    lstm_tok_s, lstm_flops_s = _bench_lstm(mx, jax, ctx, rng)
    # roofline evidence: XLA's bytes-accessed is an UPPER bound on real
    # HBM traffic (it counts operand bytes at HLO boundaries, ignoring
    # fusion reuse — measured ~2.5x the physical traffic on this step),
    # so the fraction is reported as a bound, not a proof by itself; the
    # MFU number is the primary evidence.
    roofline_sec = train_bytes / hbm_bps if hbm_bps else 0.0
    roofline_fraction = roofline_sec / train_sec_iter \
        if train_sec_iter else None

    def tf(x):
        return round(x / 1e12, 2) if x else None

    def mfu(x):
        return round(x / 1e12 / peak, 4) if x else None

    # primary = bf16 mixed-precision TRAINING (f32 masters) — the
    # framework's recommended TPU mode, the analog of the reference's fp16
    # multi_precision training; f32 numbers ride along for the strict
    # baseline-precision comparison
    print(json.dumps({
        "metric": "resnet50_train_batch32",
        "value": round(train_img_s, 2),
        "unit": "images/sec",
        "vs_baseline": round(train_img_s / BASELINE_TRAIN_IMG_S, 3),
        "precision": "bf16_mixed(f32_master)",
        "train_tflops": tf(train_flops_s),
        "train_mfu": mfu(train_flops_s),
        "train_f32_img_s": round(train32_img_s, 2),
        "train_f32_mfu": mfu(train32_flops_s),
        "inference_img_s": round(infer_img_s, 2),
        "inference_vs_baseline": round(infer_img_s / BASELINE_INFER_IMG_S, 3),
        "inference_tflops": tf(infer_flops_s),
        "inference_mfu": mfu(infer_flops_s),
        "inference_f32_img_s": round(infer32_img_s, 2),
        "inference_f32_mfu": mfu(infer32_flops_s),
        "device_kind": kind,
        "peak_tflops_bf16": peak,
        # roofline evidence for the train-MFU ceiling (round-4 verdict 3);
        # bytes are XLA's cost-analysis UPPER bound on HBM traffic, so
        # fraction >1 means the bound is loose, not that the step beat
        # the memory system
        "hbm_gbps_measured": round(hbm_bps / 1e9, 1),
        "train_bytes_per_step_xla_bound": int(train_bytes),
        "roofline_fraction_upper_bound": round(roofline_fraction, 3)
        if roofline_fraction is not None else None,
        # BASELINE config 4: LSTM LM (batch 32, seq 35, 2x200 fused LSTM,
        # vocab 10k), full SGD step
        "lstm_tokens_s": round(lstm_tok_s, 1),
        "lstm_tflops": tf(lstm_flops_s),
        "lstm_mfu": mfu(lstm_flops_s),
    }))


def smoke():
    """Tiny-shape CI mode (`make bench-smoke`): exercises the executor
    program cache on its three hot client paths — repeated fused
    train-step dispatch, batch-shape alternation (module rebinds), and
    an executor bind→reshape→bind cycle — then prints the trace/cache
    counters.  A recompile regression (a path that stops hitting the
    cache) shows up as a trace-counter jump and fails the assertions,
    without needing the chip-scale model of the main bench."""
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache

    # pin the cache knobs to their defaults: the asserts below measure
    # the CODE, and a leftover MXNET_TPU_EXEC_CACHE=0 in the caller's
    # environment would read as a recompile regression
    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)

    ctx = mx.cpu()
    rng = np.random.RandomState(0)
    executor_cache.clear()
    executor_cache.reset_stats()

    def mlp():
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                    name="fc1")
        net = mx.sym.Activation(net, act_type="relu", name="relu1")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def batch(bs):
        from mxnet_tpu.io import DataBatch, DataDesc
        return DataBatch(
            data=[mx.nd.array(rng.rand(bs, 8).astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, 4, (bs,))
                               .astype(np.float32))],
            provide_data=[DataDesc("data", (bs, 8))],
            provide_label=[DataDesc("softmax_label", (bs,))])

    t0 = time.perf_counter()
    # 1) general-path training steps: one fused program, dispatched N times
    mod = mx.mod.Module(mlp(), context=ctx)
    mod.bind([("data", (8, 8))], [("softmax_label", (8,))])
    mod.init_params(mx.initializer.Xavier())
    steps = 12
    for _ in range(steps):
        mod.forward_backward(batch(8))
    # 2) batch-shape alternation: every switch rebinds; revisits must hit
    for bs in (4, 8, 4, 8):
        mod.forward_backward(batch(bs))
    # 3) executor bind -> reshape -> bind over the same symbol
    exe = mlp().simple_bind(ctx, grad_req="write",
                            data=(8, 8), softmax_label=(8,))
    exe.forward(is_train=False)
    exe2 = exe.reshape(partial_shaping=True, data=(4, 8),
                       softmax_label=(4,))
    exe2.forward(is_train=False)
    exe3 = exe2.reshape(partial_shaping=True, allow_up_sizing=True,
                        data=(8, 8), softmax_label=(8,))
    exe3.forward(is_train=False)
    wall = time.perf_counter() - t0

    stats = executor_cache.stats()
    print(json.dumps({
        "metric": "bench_smoke",
        "unit": "cache_counters",
        "train_steps": steps + 4,
        "wall_sec": round(wall, 2),
        "exec_cache": stats,
    }))
    # recompile-regression guards: exactly one fused trace per unique
    # batch shape, one fwd trace per reshape signature, and the
    # revisited signatures all came from the cache
    assert stats["traces_fwd_bwd"] == 2, stats
    assert stats["traces_fwd"] == 2, stats
    assert stats["hits"] >= 3, stats

    _smoke_observability(mx, ctx, rng, mlp)


def _smoke_observability(mx, ctx, rng, mlp):
    """Observability smoke: run the SAME 3-step fit twice — telemetry +
    profiler off, then on — and assert the exec-cache trace counters are
    identical (instrumentation adds zero recompiles).  The instrumented
    pass dumps a Chrome trace and a telemetry snapshot to /tmp for
    `python tools/traceview.py` / eyeballs."""
    import os
    from mxnet_tpu import executor_cache, profiler
    from mxnet_tpu.observability import telemetry

    trace_path = "/tmp/mxnet_tpu_smoke_trace.json"
    telem_path = "/tmp/mxnet_tpu_smoke_telemetry.json"

    def fit_once():
        # drop the entries smoke() warmed (not just the stats): each
        # pass must TRACE afresh, so an instrumentation regression that
        # perturbs tracing shows up as a counter difference instead of
        # being masked by cache hits
        executor_cache.clear()
        executor_cache.reset_stats()
        from mxnet_tpu.io import NDArrayIter
        it = NDArrayIter(rng.rand(24, 8).astype(np.float32),
                         rng.randint(0, 4, (24,)).astype(np.float32),
                         batch_size=8)
        mod = mx.mod.Module(mlp(), context=ctx)
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
        s = executor_cache.stats()
        return {k: s[k] for k in ("traces_fwd", "traces_fwd_bwd",
                                  "traces_fused_step")}

    prev_env = os.environ.get("MXNET_TPU_TELEMETRY")
    os.environ["MXNET_TPU_TELEMETRY"] = "0"
    off = fit_once()
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    telemetry.reset()
    profiler.profiler_set_config(mode="symbolic", filename=trace_path)
    profiler.profiler_set_state("run")
    on = fit_once()
    profiler.profiler_set_state("stop")  # dumps the trace
    with open(telem_path, "w") as f:
        f.write(telemetry.to_json_lines())
    if prev_env is None:
        os.environ.pop("MXNET_TPU_TELEMETRY", None)
    else:
        os.environ["MXNET_TPU_TELEMETRY"] = prev_env

    traceview = _load_traceview()
    breakdown = traceview.step_breakdown(
        traceview.load_trace(trace_path).get("traceEvents", []))
    print(json.dumps({
        "metric": "bench_smoke_observability",
        "trace": trace_path,
        "telemetry": telem_path,
        "trace_counters_off": off,
        "trace_counters_on": on,
        "step_coverage": round(breakdown["coverage"], 4)
        if breakdown else None,
        "starvation": round(breakdown["starvation"], 4)
        if breakdown else None,
    }))
    # instrumentation must be invisible to the compiler: identical
    # retrace counts with telemetry+tracing on vs off
    assert on == off, (on, off)
    assert breakdown is not None and breakdown["steps"] >= 3, breakdown
    assert breakdown["coverage"] >= 0.9, breakdown


def serve_smoke():
    """Serving-path CI mode (`make bench-smoke` step 2, `bench.py
    --serve-smoke`): stands up the dynamic-batching service on a tiny
    2-layer MLP and proves the three serving contracts on real
    concurrent traffic:

    1. **zero recompiles after warmup** — `Server.warmup()` pre-traces
       every batch bucket (>= 3 buckets here); the concurrent request
       storm afterwards must leave the executor-cache retrace counters
       FLAT (`executor_cache.watch_traces`);
    2. **batching is invisible** — every batched response is
       bitwise-equal to the same request run through a plain serverless
       `predict.Predictor` at the dispatched bucket shape (padding rows
       and co-batched neighbours cannot bleed into real rows), and equal
       up to float reassociation to a batch-1 predict;
    3. **rejections are typed and contained** — deadline and overload
       rejections fire only when the queue is intentionally starved/
       overfilled, each is the right exception class, each lands in
       `serving.rejected_total.<reason>`, and the dispatch thread
       survives all of it.
    """
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.observability import telemetry
    from mxnet_tpu.predict import Predictor

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    # the smoke's deadline/overload phases construct their rejections
    # deliberately; an ambient default deadline would expire the storm's
    # ordinary requests and read as a contract failure
    os.environ.pop("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS", None)
    os.environ.pop("MXNET_TPU_SERVING_QUEUE_DEPTH", None)

    rng = np.random.RandomState(0)
    telemetry.reset()
    executor_cache.clear()
    executor_cache.reset_stats()

    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, 8))
    arg_params = {
        n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")}

    server = serving.Server(max_batch_size=8, batch_window_ms=3.0,
                            queue_depth=64)
    server.add_model("mlp", sym, arg_params, input_shapes={"data": (8,)})
    report = server.warmup()  # raises if the verify sweep retraces
    buckets = report["mlp"]["buckets"]
    assert len(buckets) >= 3, report

    # 1+2) concurrent storm, counters flat, responses bitwise-unbatched
    n_requests = 48
    payloads = [rng.rand(1 + i % 3, 8).astype(np.float32)
                for i in range(n_requests)]
    with executor_cache.watch_traces() as watch:
        futs = [server.submit_async("mlp", {"data": p}) for p in payloads]
        results = [f.result(timeout=60) for f in futs]
    assert watch.total() == 0, (
        "recompiles after warmup: %s" % watch.delta())

    # Bitwise oracle: a plain (serverless) Predictor run one request at
    # a time.  XLA specializes each program per batch SHAPE, so bitwise
    # reproduction pads the request to the bucket the service dispatched
    # it in (fut.request.dispatch_bucket); within one shape, results are
    # row- and offset-invariant, so zero-padding stands in for whatever
    # co-batched neighbours the request actually shipped with.  Any
    # routing/padding bug — rows swapped between requests, padding
    # bleeding into real rows, wrong slice offsets — breaks equality.
    params_blob = {"arg:%s" % k: v for k, v in arg_params.items()}
    oracles = {}
    mismatches = 0
    dispatch_buckets = set()
    for payload, fut, outs in zip(payloads, futs, results):
        b = fut.request.dispatch_bucket
        dispatch_buckets.add(b)
        oracle = oracles.get(b)
        if oracle is None:
            oracle = oracles[b] = Predictor(sym.tojson(), params_blob,
                                            {"data": (b, 8)})
        solo = np.zeros((b, 8), np.float32)
        solo[:payload.shape[0]] = payload
        oracle.forward(data=solo)
        want = oracle.get_output(0).asnumpy()[:payload.shape[0]]
        if not np.array_equal(outs[0], want):
            mismatches += 1
    assert mismatches == 0, (
        "%d responses differ from unbatched predict" % mismatches)
    assert len(dispatch_buckets) >= 2, dispatch_buckets
    # and semantically (up to float reassociation across shapes) every
    # row matches a batch-1 predict
    one = Predictor(sym.tojson(), params_blob, {"data": (1, 8)})
    for payload, outs in zip(payloads, results):
        for row in range(payload.shape[0]):
            one.forward(data=payload[row:row + 1])
            want = one.get_output(0).asnumpy()[0]
            assert np.allclose(outs[0][row], want, rtol=1e-5, atol=1e-7)

    # 3) typed rejections only under intentional starvation/overfill
    snap = telemetry.snapshot()
    storm_rejects = {k: v for k, v in snap.items()
                     if k.startswith("serving.rejected_total.")}
    assert not storm_rejects, storm_rejects

    stalled = serving.Server(registry=server.registry,  # warmed model
                             max_batch_size=4, queue_depth=4,
                             auto_start=False)
    n_overload = n_deadline = 0
    doomed = stalled.submit_async("mlp", {"data": payloads[0]},
                                  deadline_ms=20)
    queued = [stalled.submit_async("mlp", {"data": p})
              for p in payloads[1:4]]
    try:
        stalled.submit_async("mlp", {"data": payloads[4]})
    except serving.Overloaded:
        n_overload += 1
    time.sleep(0.05)  # the doomed request's deadline expires while queued
    stalled.start()
    try:
        doomed.result(timeout=30)
    except serving.DeadlineExceeded:
        n_deadline += 1
    drained = [f.result(timeout=30) for f in queued]
    stalled.close(drain=True, timeout=30)
    assert n_overload == 1 and n_deadline == 1, (n_overload, n_deadline)
    assert len(drained) == 3 and not stalled.batcher.alive
    server.close(drain=True, timeout=30)

    snap = telemetry.snapshot()
    rejected = {k.rsplit(".", 1)[1]: snap[k]["value"] for k in snap
                if k.startswith("serving.rejected_total.")}
    assert rejected.get("overloaded") == 1, rejected
    assert rejected.get("deadline_exceeded") == 1, rejected

    # 4) locksan leg: the same serving path under MXNET_TPU_LOCKSAN=1 —
    # a fresh server whose locks are all sanitizer proxies must show
    # zero violations (the serving lock discipline is inversion-free and
    # dispatch-clear) and zero added retraces (proxies are host-side
    # bookkeeping; no program signature changes)
    from mxnet_tpu.analysis import locksan
    prev_locksan = os.environ.get("MXNET_TPU_LOCKSAN")
    os.environ["MXNET_TPU_LOCKSAN"] = "1"
    locksan.reset()
    try:
        sanitized = serving.Server(max_batch_size=8, batch_window_ms=3.0,
                                   queue_depth=64)
        sanitized.add_model("mlp", sym, arg_params,
                            input_shapes={"data": (8,)})
        sanitized.warmup(expect_warm=True)  # programs already cached
        with executor_cache.watch_traces() as watch:
            futs = [sanitized.submit_async("mlp", {"data": p})
                    for p in payloads[:16]]
            for f in futs:
                f.result(timeout=60)
        sanitized.close(drain=True, timeout=30)
        assert watch.total() == 0, (
            "recompiles under LOCKSAN=1: %s" % watch.delta())
        assert locksan.violations() == [], locksan.violations()
    finally:
        locksan.reset()
        if prev_locksan is None:
            os.environ.pop("MXNET_TPU_LOCKSAN", None)
        else:
            os.environ["MXNET_TPU_LOCKSAN"] = prev_locksan

    telem_path = "/tmp/mxnet_tpu_serve_smoke_telemetry.json"
    with open(telem_path, "w") as f:
        f.write(telemetry.to_json_lines())
    lat = snap.get("serving.request_latency_ms", {})
    print(json.dumps({
        "metric": "bench_serve_smoke",
        "buckets": buckets,
        "requests": n_requests,
        "rows_bitwise_checked": int(sum(p.shape[0] for p in payloads)),
        "recompiles_after_warmup": 0,
        "warmup_traces": report["mlp"]["traces_first_pass"],
        "request_latency_ms_avg": round(
            lat.get("sum", 0.0) / lat["count"], 3) if lat.get("count")
        else None,
        "rejections": rejected,
        "locksan": {"violations": 0, "recompiles": 0},
        "telemetry": telem_path,
    }))


class OpenLoopTraffic:
    """Open-loop traffic generator for the serving SLO harness: Poisson
    arrivals, heavy-tailed request sizes, burst phases.

    Open-loop is the property that matters for tail-latency claims: a
    closed-loop client (submit, wait, submit) self-throttles when the
    server slows down, silently hiding the very overload the harness
    exists to measure.  Here arrivals follow the SCHEDULE — a request
    fires at its arrival time whether or not earlier ones completed —
    so overload manifests as queueing and shedding, exactly like real
    fleet traffic.

    - **Arrivals**: Poisson — exponential inter-arrival gaps at each
      phase's rate.
    - **Sizes**: heavy-tailed via a Zipf(a) draw clamped to
      [1, max_rows] — most requests are 1-2 rows, the tail fills whole
      buckets (the skewed-traffic shape the ServingBucketTuner and the
      padded-row accounting care about).
    - **Bursts**: ``phases`` = [(duration_s, rate_multiplier), ...]
      replayed in order; a multiplier > 1 is a burst riding on the base
      rate.

    Deterministic per seed: the (arrival gap, rows) schedule is drawn
    up front, so two runs at the same seed offer the same traffic.
    """

    def __init__(self, rate_rps, duration_s, max_rows=8, zipf_a=1.6,
                 phases=None, seed=0):
        rng = np.random.RandomState(seed)
        self.schedule = []  # (t_offset_s, n_rows)
        t = 0.0
        for dur, mult in (phases or [(duration_s, 1.0)]):
            end = t + dur
            rate = max(1e-6, rate_rps * mult)
            while True:
                t += rng.exponential(1.0 / rate)
                if t >= end:
                    t = end
                    break
                rows = int(min(max_rows, rng.zipf(zipf_a)))
                self.schedule.append((t, rows))

    def total_rows(self):
        return sum(r for _, r in self.schedule)

    def run(self, submit, payload_for):
        """Replay the schedule against ``submit(payload, n_rows)``
        (returns a future or raises a typed rejection).  Returns
        [(t_offset, n_rows, future_or_None, exc_or_None)].  Late
        arrivals are fired immediately (the generator never skips —
        an overloaded server sees ALL the offered load)."""
        results = []
        t0 = time.monotonic()
        for t_off, rows in self.schedule:
            delay = t0 + t_off - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            payload = payload_for(rows)
            try:
                fut = submit(payload, rows)
                results.append((t_off, rows, fut, None))
            except Exception as exc:  # typed rejections recorded per arrival
                results.append((t_off, rows, None, exc))
        return results


def _fleet_slo_setup(queue_depth=16, seed=0):
    """Shared scaffolding of the slo/reqtrace smokes — ONE recipe for
    the seeded MLP, the 2-replica fleet, the SLO declared from
    MEASURED warmup cost (widest bucket's verified execution cost x
    worst-case queue occupancy ahead of an admitted request, plus
    scheduling slack for a 2-core CI box), and the 1x open-loop rate
    derived from measured capacity — so the two harnesses cannot
    drift apart in calibration."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.serving import metrics as _smetrics

    rng = np.random.RandomState(seed)
    feat, classes = 8, 4
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=32,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, feat))
    arg_params = {
        n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")}

    fleet = serving.FleetServer(n_replicas=2, max_batch_size=8,
                                batch_window_ms=1.0,
                                queue_depth=queue_depth)
    fleet.add_model("mlp", sym, arg_params,
                    input_shapes={"data": (feat,)})
    report = fleet.warmup()

    # declared SLO from MEASURED cost; shedding at the bounded queue
    # is what makes it a guarantee rather than a hope
    max_bucket = max(report["mlp"]["buckets"])
    cost_ms = max(
        per_rep.get("bucket_cost_ms", {}).get(str(max_bucket), 0.0)
        for per_rep in report["mlp"]["per_replica"].values())
    slo_ms = max(500.0, (queue_depth + 4) * max(cost_ms, 1.0) * 3.0)
    fleet.registry.get("mlp").slo_ms = slo_ms
    _smetrics.record_slo("mlp", slo_ms)

    # measured capacity: rows/s through the widest bucket across the
    # group (two replicas work in parallel)
    capacity_rows_s = 2 * max_bucket / max(cost_ms / 1e3, 1e-4)
    mean_rows = 2.2  # Zipf(1.6) clamped to 8, empirically ~2.2
    # cap so 1x stays genuinely sub-capacity even where PYTHON
    # per-request overhead (not the measured program cost) is the
    # bottleneck — a 2-core CI box serves this MLP at >1k req/s
    rate_1x = min(max(20.0, 0.45 * capacity_rows_s / mean_rows), 250.0)
    return {"fleet": fleet, "sym": sym, "args": arg_params, "rng": rng,
            "feat": feat, "report": report, "slo_ms": slo_ms,
            "rate_1x": rate_1x, "queue_depth": queue_depth}


def _collect_fleet_results(results, timeout=60):
    """Resolve an OpenLoopTraffic run against a fleet: (served list of
    (request, outs), typed Overloaded sheds, everything else)."""
    from mxnet_tpu import serving
    served, sheds, others = [], [], []
    for t_off, rows, fut, exc in results:
        if exc is not None:
            (sheds if isinstance(exc, serving.Overloaded)
             else others).append(exc)
            continue
        try:
            outs = fut.result(timeout=timeout)
        except serving.Overloaded as e:
            sheds.append(e)
            continue
        except Exception as e:
            others.append(e)
            continue
        served.append((fut.request, outs))
    return served, sheds, others


def slo_smoke():
    """Fleet SLO harness CI mode (`make bench-smoke`, `bench.py
    --slo-smoke`): a 2-replica FleetServer under open-loop traffic,
    proving the fleet contracts the tests can't see at scale:

    1. **1x load**: skewed open-loop traffic (Poisson arrivals,
       Zipf-tailed sizes) at ~half the measured capacity — ZERO
       executor retraces after warmup across both replicas, every
       served response BITWISE-equal to a plain serverless Predictor
       replay at its recorded dispatch bucket (regardless of which
       replica served it), declared SLO met, (almost) nothing shed;
    2. **2x overload with a burst phase**: the bounded admission queue
       sheds load — every rejection is a TYPED `Overloaded`, and the
       p99 of the requests actually SERVED stays within the declared
       SLO (shedding converts overload into refusals, not into
       unbounded latency for everyone);
    3. both replicas took traffic, and `tools/traceview.py --serving`
       renders the per-replica routing breakdown + SLO attainment
       table from the telemetry dump.

    The SLO itself is declared from MEASURED warmup cost (a structural
    bound: admission queue depth x the widest bucket's verified
    execution cost across replicas, plus scheduling slack) — the
    harness proves the shedding MECHANISM bounds tail latency, on any
    box speed.
    """
    import os
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.observability import telemetry
    from mxnet_tpu.predict import Predictor

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    os.environ.pop("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS", None)
    os.environ.pop("MXNET_TPU_SERVING_QUEUE_DEPTH", None)
    os.environ.pop("MXNET_TPU_AUTOTUNE_EVERY_S", None)

    telemetry.reset()
    executor_cache.clear()
    executor_cache.reset_stats()

    setup = _fleet_slo_setup()
    fleet, sym, arg_params = setup["fleet"], setup["sym"], setup["args"]
    rng, feat = setup["rng"], setup["feat"]
    report, slo_ms, rate_1x = (setup["report"], setup["slo_ms"],
                               setup["rate_1x"])
    assert len(report["replicas"]) == 2, report

    def payload_for(rows):
        return rng.rand(rows, feat).astype(np.float32)

    def submit(payload, rows):
        return fleet.submit_async("mlp", {"data": payload})

    collect = _collect_fleet_results

    # -- phase 1: 1x load -----------------------------------------------------
    traffic_1x = OpenLoopTraffic(rate_1x, duration_s=4.0, max_rows=8,
                                 seed=1)
    with executor_cache.watch_traces() as watch:
        results_1x = collect(traffic_1x.run(submit, payload_for))
    served_1x, sheds_1x, others_1x = results_1x
    assert watch.total() == 0, (
        "retraces under 1x steady-state load: %s" % watch.delta())
    assert not others_1x, others_1x[:3]
    n_1x = len(traffic_1x.schedule)
    assert len(sheds_1x) <= max(2, 0.05 * n_1x), (
        "1x load shed %d of %d" % (len(sheds_1x), n_1x))

    snap = telemetry.snapshot()
    mlat = snap.get("serving.request_latency_ms.mlp", {})
    from mxnet_tpu.observability.telemetry import quantile_from_snapshot
    p99_1x = quantile_from_snapshot(mlat, 0.99) if mlat.get("count") \
        else 0.0
    assert p99_1x <= slo_ms, (
        "1x p99 %.1f ms blew the declared SLO %.1f ms" % (p99_1x, slo_ms))

    # bitwise oracle: every served response replayed at its recorded
    # dispatch bucket through a plain serverless Predictor — whichever
    # replica served it, the bytes must match.  ONE replay helper for
    # both phases, so what "verified" means cannot drift between them.
    params_blob = {"arg:%s" % k: v for k, v in arg_params.items()}
    oracles = {}

    def replay_mismatches(served):
        checked = mismatches = 0
        for req, outs in served:
            b = req.dispatch_bucket
            oracle = oracles.get(b)
            if oracle is None:
                oracle = oracles[b] = Predictor(
                    sym.tojson(), params_blob, {"data": (b, feat)})
            solo = np.zeros((b, feat), np.float32)
            solo[:req.n_rows] = req.inputs["data"]
            oracle.forward(data=solo)
            want = oracle.get_output(0).asnumpy()[:req.n_rows]
            checked += 1
            if not np.array_equal(outs[0], want):
                mismatches += 1
        return checked, mismatches

    checked, mismatches = replay_mismatches(served_1x)
    assert checked and mismatches == 0, (
        "%d/%d served responses differ from the serverless replay"
        % (mismatches, checked))

    # -- phase 2: 2x overload with a burst ------------------------------------
    lat_before = dict(snap.get("serving.request_latency_ms.mlp", {}))
    # sustained >=2x of the 1x rate, with a burst phase whose arrival
    # rate exceeds ANY box's service rate (the submit path costs ~30us;
    # the serve path costs a device dispatch) — so the bounded queue
    # provably overflows and shedding must engage
    traffic_2x = OpenLoopTraffic(
        rate_1x, duration_s=4.0, max_rows=8, seed=2,
        phases=[(1.0, 2.0), (1.0, 50.0), (2.0, 3.0)])
    results_2x = collect(traffic_2x.run(submit, payload_for))
    served_2x, sheds_2x, others_2x = results_2x
    assert not others_2x, (
        "untyped failures under overload: %r" % others_2x[:3])
    assert sheds_2x, "2x overload shed nothing — queue bound not binding"
    for exc in sheds_2x:
        assert isinstance(exc, serving.Overloaded), type(exc)

    snap = telemetry.snapshot()
    mlat2 = snap.get("serving.request_latency_ms.mlp", {})
    # overload-phase p99 estimated over the POST-phase-1 observations
    # only: the shared delta estimator subtracts phase 1's bucket counts
    from mxnet_tpu.observability.telemetry import quantile_between
    p99_2x = quantile_between(lat_before, mlat2, 0.99) \
        if mlat2.get("count") else 0.0
    assert p99_2x <= slo_ms, (
        "served-request p99 %.1f ms blew the SLO %.1f ms under 2x "
        "overload — shedding failed to bound tail latency"
        % (p99_2x, slo_ms))

    # bitwise oracle holds under overload too
    checked_2x, mismatches_2x = replay_mismatches(served_2x)
    assert checked_2x and mismatches_2x == 0, (
        "%d/%d overload-phase responses differ from the serverless "
        "replay" % (mismatches_2x, checked_2x))

    # both replicas took traffic, none quarantined
    stats = fleet.group.stats()
    assert all(s["healthy"] for s in stats), stats
    assert all(s["dispatches"] > 0 for s in stats), (
        "a replica served nothing: %s" % stats)

    fleet.close(drain=True, timeout=30)

    # traceview renders the fleet view from the telemetry dump
    telem_path = "/tmp/mxnet_tpu_slo_smoke_telemetry.json"
    with open(telem_path, "w") as f:
        f.write(telemetry.to_json_lines())
    traceview = _load_traceview()
    kind, payload = traceview.load_any(telem_path)
    rendered = traceview.summarize_serving(kind, payload)
    assert "per-replica routing" in rendered and "SLO attainment" in \
        rendered, rendered[:400]
    tstats = traceview.serving_from_telemetry(payload)
    assert len(tstats["replicas"]) == 2, tstats["replicas"]
    assert tstats["slo"] and tstats["slo"][0]["model"] == "mlp", \
        tstats["slo"]

    shed_frac_2x = len(sheds_2x) / float(len(traffic_2x.schedule))
    print(json.dumps({
        "metric": "bench_slo_smoke",
        "replicas": 2,
        "slo_ms": round(slo_ms, 1),
        "rate_1x_rps": round(rate_1x, 1),
        "phase_1x": {"offered": n_1x, "served": len(served_1x),
                     "shed": len(sheds_1x),
                     "p99_ms": round(p99_1x, 2),
                     "bitwise_checked": checked,
                     "retraces": 0},
        "phase_2x": {"offered": len(traffic_2x.schedule),
                     "served": len(served_2x),
                     "shed": len(sheds_2x),
                     "shed_frac": round(shed_frac_2x, 3),
                     "p99_ms": round(p99_2x, 2)},
        "replica_dispatches": {str(s["replica"]): s["dispatches"]
                               for s in stats},
        "telemetry": telem_path,
    }))


def alert_smoke():
    """Fleet health-plane CI mode (`make bench-smoke`, `bench.py
    --alert-smoke`): the time-series sampler + SLO burn-rate alerting
    over the same 2-replica overload recipe as `--slo-smoke`, proving
    the health plane's contracts:

    1. **off by default, bitwise off**: with `MXNET_TPU_TS_INTERVAL_S`
       unset nothing is spawned or sampled, and a fixed deterministic
       request replay produces byte-identical responses (and identical
       executor-cache trace counters) to the same replay with sampling
       ON — observability must not perturb the observed;
    2. **zero added retraces with sampling on**: the sampler ticking
       through replay + overload leaves the retrace counters flat;
    3. **the fast-burn rule provably trips and resolves**: a 2x+burst
       open-loop overload drives typed sheds, the multi-window burn
       rule (declared via `MXNET_TPU_ALERT_RULES` inline JSON — the env
       parse path) records a `firing` transition in the flight-recorder
       `alerts` ring with the window burn values that tripped it, and
       calm 1x traffic afterwards records the `resolved` transition;
    4. **the dashboards render**: `traceview --alerts` (flight dump)
       and `traceview --dash` (shipped series dir) both exit 0, the
       dash showing the shed-rate spike and p99-vs-SLO rows;
    5. teardown is leak-clean: `stop_sampler()` joins the thread
       (`threads.live_package_threads()` empty).
    """
    import hashlib
    import os
    import shutil
    import tempfile
    from mxnet_tpu import executor_cache, serving, threads
    from mxnet_tpu.observability import (alerts, flight_recorder,
                                         telemetry, timeseries)

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    os.environ.pop("MXNET_TPU_TS_INTERVAL_S", None)
    os.environ.pop("MXNET_TPU_TS_RING", None)
    os.environ.pop("MXNET_TPU_ALERT_RULES", None)
    os.environ.pop("MXNET_TPU_REQTRACE_CTX", None)
    os.environ.pop("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS", None)

    telemetry.reset()
    timeseries.reset()
    alerts.reset()
    flight_recorder.reset()
    executor_cache.clear()
    executor_cache.reset_stats()

    setup = _fleet_slo_setup()
    fleet, rate_1x, slo_ms = (setup["fleet"], setup["rate_1x"],
                              setup["slo_ms"])
    rng, feat = setup["rng"], setup["feat"]

    # fixed request sequence for the bitwise legs: sequential submits
    # (each awaited) pin every request to its own padded bucket, so the
    # byte stream is a pure function of the inputs
    replay_rng = np.random.RandomState(7)
    replay_reqs = [(rows, replay_rng.rand(rows, feat).astype(np.float32))
                   for rows in [1, 2, 4, 8] * 6]

    def replay_digest():
        h = hashlib.sha256()
        for _, payload in replay_reqs:
            fut = fleet.submit_async("mlp", {"data": payload})
            outs = fut.result(timeout=60)
            h.update(np.ascontiguousarray(
                np.asarray(outs[0]), dtype=np.float32).tobytes())
        return h.hexdigest()

    # -- leg 1: env unset — nothing sampled, bitwise baseline ---------------
    timeseries.ensure_sampler()  # must no-op
    assert timeseries.current_sampler() is None, \
        "sampler started with MXNET_TPU_TS_INTERVAL_S unset"
    with executor_cache.watch_traces() as watch_off:
        sha_off = replay_digest()
    traces_off = watch_off.total()
    assert traces_off == 0, (
        "retraces in the warmed replay: %s" % watch_off.delta())
    assert len(timeseries.get_timeseries()) == 0, \
        "samples recorded with sampling off"

    # -- leg 2: sampling + an env-declared fast burn rule -------------------
    ship_dir = tempfile.mkdtemp(prefix="mxnet_tpu_alert_smoke_")
    os.environ["MXNET_TPU_TS_INTERVAL_S"] = "0.25"
    # tight windows so a ~4 s overload trips and ~6 s of calm resolves;
    # inline JSON exercises the MXNET_TPU_ALERT_RULES parse path
    os.environ["MXNET_TPU_ALERT_RULES"] = json.dumps([{
        "kind": "burn_rate", "name": "fast_burn.mlp", "model": "mlp",
        "objective": 0.95, "fast_s": 2.0, "slow_s": 8.0, "burn": 2.0}])
    alerts.reset()  # re-read the rules env
    sampler = timeseries.start_sampler(ship_dir=ship_dir)
    assert sampler is not None and sampler.alive

    with executor_cache.watch_traces() as watch_on:
        sha_on = replay_digest()

        # overload: same 2x + 50x-burst shape as --slo-smoke, so the
        # bounded queue provably sheds and the error budget burns
        def payload_for(rows):
            return rng.rand(rows, feat).astype(np.float32)

        traffic = OpenLoopTraffic(
            rate_1x, duration_s=4.0, max_rows=8, seed=2,
            phases=[(1.0, 2.0), (1.0, 50.0), (2.0, 3.0)])
        served, sheds, others = _collect_fleet_results(
            traffic.run(lambda p, r: fleet.submit_async(
                "mlp", {"data": p}), payload_for))
        assert not others, others[:3]
        assert sheds, "overload shed nothing — no error budget burned"
        for exc in sheds:
            assert isinstance(exc, serving.Overloaded), type(exc)

        # calm 1x traffic, then wait for the fast window to cool
        calm = OpenLoopTraffic(rate_1x, duration_s=3.0, max_rows=8,
                               seed=3)
        _collect_fleet_results(
            calm.run(lambda p, r: fleet.submit_async(
                "mlp", {"data": p}), payload_for))
        engine = alerts.get_engine()
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            hist = engine.history()
            if any(r["state"] == "resolved"
                   and r["rule"] == "fast_burn.mlp" for r in hist):
                break
            time.sleep(0.25)
    traces_on = watch_on.total()

    assert sha_on == sha_off, (
        "sampling perturbed the served bytes: %s != %s"
        % (sha_on[:16], sha_off[:16]))
    assert traces_on == traces_off == 0, (
        "sampling added retraces: %s" % watch_on.delta())

    hist = engine.history()
    fired = [r for r in hist if r["state"] == "firing"
             and r["rule"] == "fast_burn.mlp"]
    resolved = [r for r in hist if r["state"] == "resolved"
                and r["rule"] == "fast_burn.mlp"]
    assert fired, (
        "overload never tripped the fast burn rule; history: %s" % hist)
    assert resolved, (
        "calm traffic never resolved the rule; history: %s" % hist)
    fire_fast = fired[0]["windows"]["fast"]
    assert fire_fast["burn"] >= 2.0 and fire_fast["rejected"] > 0, \
        fired[0]
    assert len(timeseries.get_timeseries()) >= 8, \
        "sampler barely ticked"
    n_samples = len(timeseries.get_timeseries())

    # every transition also rode the flight-recorder alerts ring
    n_flight_alerts = flight_recorder.get_recorder().alerts_recorded()
    assert n_flight_alerts >= 2, (
        "flight alerts ring holds %d record(s), want the firing + "
        "resolved pair" % n_flight_alerts)

    # leak-clean teardown BEFORE rendering (flushes the series file)
    fleet.close(drain=True, timeout=30)
    timeseries.stop_sampler()
    assert not sampler.alive
    leaked = threads.live_package_threads()
    assert not leaked, "health plane leaked threads: %s" % leaked

    # -- render: traceview --alerts (flight dump) + --dash (series dir) -----
    dump_path = os.path.join(ship_dir, "flight.json")
    flight_recorder.get_recorder().dump(dump_path)
    traceview = _load_traceview()
    with open(dump_path) as f:
        dumped_alerts = traceview.alert_records(json.load(f))
    assert any(r["state"] == "firing" for r in dumped_alerts), \
        dumped_alerts
    assert any(r["state"] == "resolved" for r in dumped_alerts), \
        dumped_alerts
    rc_alerts = traceview.main(["--alerts", dump_path])
    assert rc_alerts == 0, "traceview --alerts exited %d" % rc_alerts
    rc_dash = traceview.main(["--dash", ship_dir])
    assert rc_dash == 0, "traceview --dash exited %d" % rc_dash
    dash_stats = traceview.dash_stats(traceview.dash_sources(ship_dir))
    assert dash_stats["shed_total"] >= len(sheds) * 0.5, dash_stats
    assert any(m["model"] == "mlp" and m["slo_ms"]
               for m in dash_stats["models"]), dash_stats["models"]

    os.environ.pop("MXNET_TPU_TS_INTERVAL_S", None)
    os.environ.pop("MXNET_TPU_ALERT_RULES", None)
    shutil.rmtree(ship_dir, ignore_errors=True)

    print(json.dumps({
        "metric": "bench_alert_smoke",
        "slo_ms": round(slo_ms, 1),
        "rate_1x_rps": round(rate_1x, 1),
        "bitwise_off_vs_on": sha_off == sha_on,
        "retraces_off": traces_off, "retraces_on": traces_on,
        "samples": n_samples,
        "overload": {"offered": len(traffic.schedule),
                     "served": len(served), "shed": len(sheds)},
        "fired": {"rule": fired[0]["rule"],
                  "fast_burn": fire_fast["burn"],
                  "shed_in_window": fire_fast["rejected"]},
        "resolved": resolved[0]["windows"]["fast"]["burn"],
        "flight_alert_records": n_flight_alerts,
    }))


def decode_smoke():
    """Paged-KV continuous-decode CI mode (`make bench-smoke`,
    `bench.py --decode-smoke`): open-loop autoregressive traffic
    against the paged-KV transformer decoder (serving/decode.py over
    serving/kv_cache.py) proving the decode contracts:

    1. **zero steady-state retraces** — `warmup()` pre-traces the one
       fixed-shape decode-step program plus the COW clone; the churn
       afterwards (streams joining/leaving mid-flight, prefill mixed
       with decode, page allocation/recycling, copy-on-write) must
       leave the executor-cache retrace counters FLAT;
    2. **batching is invisible** — every served stream's (token ids,
       logits) is bitwise-equal to decoding it ALONE on a fresh
       decoder over the same weights;
    3. **the prefix cache pays** — a shared-prompt phase (one popular
       prompt head resubmitted with different continuations) must
       reuse cached pages (hit ratio asserted) and COW-clone when a
       fully cached prompt diverges;
    4. the page pool is observable end to end: `memprof.report()`
       carries the pool row, `traceview --serving` renders the
       page-pool section from the telemetry dump;
    5. a tokens/s + decode-MFU row rides alongside the LSTM row
       (FLOPs estimated matmul-style at 2 * params per token).
    """
    import os
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.gluon.model_zoo import transformer_lm
    from mxnet_tpu.observability import memprof, telemetry

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    rng = np.random.RandomState(7)
    telemetry.reset()
    executor_cache.clear()
    executor_cache.reset_stats()

    VOCAB, EMBED, HEADS, LAYERS, SEQ, SLOTS = 96, 64, 4, 2, 80, 4
    lm = transformer_lm(VOCAB, embed_dim=EMBED, num_heads=HEADS,
                        num_layers=LAYERS, seq_len=SEQ)
    lm.initialize()
    # one forward materializes the deferred Dense shapes
    _ = lm(mx.nd.array(np.zeros((1, SEQ), np.float32)))
    params = lm.decode_param_arrays()
    n_params = sum(int(np.asarray(v).size) for v in params.values())

    dec = serving.PagedTransformerDecoder(params, lm.config,
                                          slot_count=SLOTS, name="bench")
    report = dec.warmup()  # raises if the verify iteration retraces
    assert report["traces"] >= 1, report

    # 1) open-loop churn: staggered submits so streams join and leave
    # mid-flight with prefill interleaved into steady decode
    prompts = [rng.randint(0, VOCAB, size=int(rng.randint(3, 40)))
               for _ in range(10)]
    gen_lens = [int(rng.randint(4, 16)) for _ in prompts]
    t0 = time.perf_counter()
    with executor_cache.watch_traces() as watch:
        streams = []
        for p, g in zip(prompts, gen_lens):
            streams.append(dec.submit(p, max_new_tokens=g))
            dec.step()
            dec.step()
        dec.drain()
    elapsed = time.perf_counter() - t0
    assert watch.total() == 0, (
        "decode retraces after warmup: %s" % watch.delta())
    served = [s.wait(60).outputs() for s in streams]
    generated = sum(len(toks) for toks, _ in served)
    # every appended token (prefill + decode) runs one full step row
    tokens_appended = sum(len(p) + len(toks)
                          for p, (toks, _) in zip(prompts, served))

    # 2) bitwise oracle: each stream alone on a fresh-pool decoder
    solo = serving.PagedTransformerDecoder(params, lm.config,
                                           slot_count=SLOTS, name="solo")
    solo.warmup()
    for p, g, (toks, logits) in zip(prompts, gen_lens, served):
        ref = solo.submit(p, max_new_tokens=g)
        solo.drain()
        ref_toks, ref_logits = ref.outputs()
        assert ref_toks == toks, "served tokens != solo decode"
        assert np.array_equal(ref_logits, logits), (
            "served logits not bitwise-equal to solo decode")

    # 3) shared-prompt phase: one popular 2-page head, resubmitted with
    # continuations of 0 (fully cached -> COW on divergence), 3 and 9
    # extra tokens
    def _count(name):
        snap = telemetry.snapshot().get(name)
        return snap["value"] if snap else 0

    shared = rng.randint(0, VOCAB, size=2 * dec.page_size)
    lookups0 = _count("serving.decode.prefix_lookups")
    hits0 = _count("serving.decode.prefix_hits")
    cow0 = dec.pool.stats()["cow_clones"]
    with executor_cache.watch_traces() as watch2:
        seed_stream = dec.submit(shared, max_new_tokens=6)
        dec.drain()  # fills + registers the shared head's pages
        tails = [rng.randint(0, VOCAB, size=k) for k in (0, 3, 9)]
        phase = [dec.submit(np.concatenate([shared, t]).astype(np.int64),
                            max_new_tokens=6) for t in tails]
        dec.drain()
    assert watch2.total() == 0, (
        "shared-prompt phase retraced: %s" % watch2.delta())
    hits = _count("serving.decode.prefix_hits") - hits0
    lookups = _count("serving.decode.prefix_lookups") - lookups0
    hit_ratio = hits / float(lookups or 1)
    assert hits >= 4 and hit_ratio >= 0.5, (
        "prefix cache did not pay: %d hits / %d lookups"
        % (hits, lookups))
    cow_clones = dec.pool.stats()["cow_clones"] - cow0
    assert cow_clones >= 1, "fully-cached prompt did not COW-clone"
    # the prefix-reusing streams still match solo decode bitwise
    for t, stream in zip(tails, phase):
        ref = solo.submit(np.concatenate([shared, t]).astype(np.int64),
                          max_new_tokens=6)
        solo.drain()
        ref_toks, ref_logits = ref.outputs()
        toks, logits = stream.outputs()
        assert ref_toks == toks and np.array_equal(ref_logits, logits), (
            "prefix-cached stream not bitwise-equal to solo decode")
    assert seed_stream.outputs()[0] == phase[0].outputs()[0]

    # 4) the pool is observable: memprof row + traceview page-pool rows
    pools = {p["name"]: p for p in memprof.report().get("pools", [])}
    assert "bench.kv" in pools, pools
    assert pools["bench.kv"]["pages_used"] >= 2, pools["bench.kv"]
    telem_path = "/tmp/mxnet_tpu_decode_smoke_telemetry.json"
    with open(telem_path, "w") as f:
        f.write(telemetry.to_json_lines())
    traceview = _load_traceview()
    kind, payload = traceview.load_any(telem_path)
    rendered = traceview.summarize_serving(kind, payload)
    assert "continuous decode / page pool" in rendered, rendered[:400]
    tstats = traceview.serving_from_telemetry(payload)
    assert tstats["decode"] is not None
    assert tstats["decode"]["kv_pages_total"] == dec.pool.num_pages
    assert (tstats["decode"]["prefix_hits"] or 0) >= hits

    dec.close()
    solo.close()

    # 5) the tokens/s + MFU row (CPU numbers are a correctness check of
    # the bench itself, not a measurement)
    kind_dev = jax.devices()[0].device_kind
    peak = PEAK_TFLOPS.get(kind_dev)
    tok_s = tokens_appended / elapsed if elapsed else 0.0
    flops_s = tok_s * 2.0 * n_params
    print(json.dumps({
        "metric": "bench_decode_smoke",
        "decode_tokens_s": round(tok_s, 1),
        "decode_generated_tokens": generated,
        "decode_tokens_appended": tokens_appended,
        "decode_tflops": round(flops_s / 1e12, 4),
        "decode_mfu": (round(flops_s / 1e12 / peak, 4)
                       if peak else None),
        "model": {"vocab": VOCAB, "embed": EMBED, "heads": HEADS,
                  "layers": LAYERS, "params": n_params},
        "slot_count": SLOTS,
        "page_size": dec.page_size,
        "prefix_hit_ratio": round(hit_ratio, 3),
        "cow_clones": cow_clones,
        "steady_state_retraces": 0,
        "bitwise_vs_solo": True,
        "device_kind": kind_dev,
        "telemetry": telem_path,
    }))


def reqtrace_fleet_worker():
    """Subprocess half of ``--reqtrace-smoke``'s fleet-merge proof: a
    SECOND serving process that inherits the parent's env-propagated
    trace context (``MXNET_TPU_REQTRACE_CTX``), serves a few requests
    with a deliberately-unmeetable SLO (every journey tail-captures),
    and writes its standalone reqtrace dump into the shared fleet dir
    — the artifact ``traceview --fleet`` merges onto the parent's
    shared-epoch timeline."""
    import os
    import sys
    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.observability import reqtrace

    out_path = sys.argv[sys.argv.index("--reqtrace-worker") + 1]
    os.environ["MXNET_TPU_REQTRACE"] = "1"
    rng = np.random.RandomState(3)
    feat = 8
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="wfc1")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, feat))
    args = {n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    srv = serving.Server(max_batch_size=4, batch_window_ms=0.5)
    # slo_ms far below any real dispatch: every served request
    # breaches and pins, so the worker dump holds full waterfalls
    srv.add_model("worker_mlp", sym, args,
                  input_shapes={"data": (feat,)}, slo_ms=0.001)
    srv.warmup()
    for _ in range(8):
        srv.submit("worker_mlp",
                   {"data": rng.rand(2, feat).astype(np.float32)})
    srv.close()
    assert reqtrace.stats()["pinned"] > 0, reqtrace.stats()
    reqtrace.dump(out_path)
    print(json.dumps({"metric": "reqtrace_fleet_worker",
                      "root": reqtrace.fleet_header()["root"],
                      "pinned": reqtrace.stats()["pinned"],
                      "dump": out_path}))


def reqtrace_smoke():
    """Request-tracing harness CI mode (`make bench-smoke`, `bench.py
    --reqtrace-smoke`): slo-smoke-style open-loop traffic against a
    2-replica fleet, proving the reqtrace contracts:

    1. tracing adds ZERO executor retraces (all instrumentation is
       host-side segment appends);
    2. every SLO-breaching served request and every typed shed appears
       in the flight recorder's ``requests`` ring, breaches with a
       COMPLETE fleet waterfall (queue/route/lane/assemble/dispatch/
       split) whose segments explain ~100% of measured latency;
    3. the head-sampled ring stays under its configured byte cap;
    4. ``traceview --requests`` renders the flight dump and
       ``traceview --fleet`` merges it with a subprocess worker's dump
       (env-propagated trace root), both rc 0.
    """
    import os
    import shutil
    import subprocess
    import sys
    from mxnet_tpu import executor_cache
    from mxnet_tpu.observability import (flight_recorder, reqtrace,
                                         telemetry)

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    os.environ.pop("MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS", None)
    os.environ.pop("MXNET_TPU_SERVING_QUEUE_DEPTH", None)
    os.environ.pop("MXNET_TPU_AUTOTUNE_EVERY_S", None)
    os.environ.pop("MXNET_TPU_FLIGHT_PATH", None)
    os.environ.pop("MXNET_TPU_REQTRACE_CTX", None)  # fresh trace root
    os.environ["MXNET_TPU_REQTRACE"] = "8"          # head-sample 1/8
    ring_bytes = 256 * 1024
    os.environ["MXNET_TPU_REQTRACE_RING"] = "256"
    os.environ["MXNET_TPU_REQTRACE_RING_BYTES"] = str(ring_bytes)
    # the tail ring must hold EVERY shed of the overload phase — the
    # assertion below is exhaustive, not sampled
    os.environ["MXNET_TPU_REQTRACE_PINNED"] = "8192"

    telemetry.reset()
    executor_cache.clear()
    executor_cache.reset_stats()
    flight_recorder.reset()
    reqtrace.reset()

    # same fleet + measured-SLO + rate recipe as slo_smoke (shared
    # helper — the two harnesses must not drift apart in calibration)
    setup = _fleet_slo_setup()
    fleet, rng, feat = setup["fleet"], setup["rng"], setup["feat"]
    slo_ms, rate_1x = setup["slo_ms"], setup["rate_1x"]
    mlp = fleet.registry.get("mlp")
    from mxnet_tpu.serving import metrics as _smetrics

    def payload_for(rows):
        return rng.rand(rows, feat).astype(np.float32)

    def submit(payload, rows):
        return fleet.submit_async("mlp", {"data": payload})

    collect = _collect_fleet_results

    with executor_cache.watch_traces() as watch:
        # phase 1: 1x steady state at the measured SLO
        traffic_1x = OpenLoopTraffic(rate_1x, duration_s=2.5,
                                     max_rows=8, seed=1)
        served_1x, sheds_1x, others_1x = collect(
            traffic_1x.run(submit, payload_for))
        assert not others_1x, others_1x[:3]

        # phase 2: tighten the declared SLO below any real dispatch, so
        # every SERVED request of the overload phase breaches — the
        # tail-capture path must catch 100% of them — while the burst
        # overflows the bounded queue and sheds type as Overloaded
        mlp.slo_ms = 0.01
        _smetrics.record_slo("mlp", mlp.slo_ms)
        traffic_2x = OpenLoopTraffic(
            rate_1x, duration_s=2.5, max_rows=8, seed=2,
            phases=[(0.75, 2.0), (0.5, 50.0), (1.25, 3.0)])
        served_2x, sheds_2x, others_2x = collect(
            traffic_2x.run(submit, payload_for))
        assert not others_2x, others_2x[:3]
        assert sheds_2x, "overload shed nothing — queue bound not binding"
    assert watch.total() == 0, (
        "request tracing added retraces: %s" % watch.delta())

    stats = reqtrace.stats()
    assert stats["sampled"] > 0, stats
    assert stats["sampled_bytes"] <= ring_bytes, stats

    fleet.close(drain=True, timeout=30)

    # the flight dump IS the black box: every shed and every breaching
    # served request must be in its requests ring
    fleet_dir = "/tmp/mxnet_tpu_reqtrace_fleet"
    shutil.rmtree(fleet_dir, ignore_errors=True)
    os.makedirs(fleet_dir)
    flight_path = os.path.join(fleet_dir, "flight_parent.json")
    assert flight_recorder.dump(path=flight_path,
                                reason="reqtrace_smoke") == flight_path
    with open(flight_path) as f:
        doc = json.load(f)
    pinned = doc.get("requests") or []
    n_sheds = len(sheds_1x) + len(sheds_2x)
    overloaded = [r for r in pinned if r.get("reason") == "overloaded"]
    assert len(overloaded) == n_sheds, (
        "%d typed sheds but %d pinned overloaded traces"
        % (n_sheds, len(overloaded)))
    for r in overloaded:
        assert r["segments"] and r["segments"][-1]["name"] == "reject", r

    breach_ids = {r["trace_id"] for r in pinned
                  if r.get("pinned") == "slo_breach"}
    by_id = {r["trace_id"]: r for r in pinned}
    hop_names = ("queue", "route", "lane", "assemble", "dispatch",
                 "split")
    missing = 0
    for req, _ in served_2x:
        tid = req.ctx.trace_id if req.ctx is not None else None
        if tid is None or tid not in breach_ids:
            missing += 1
            continue
        names = [s["name"] for s in by_id[tid]["segments"]]
        for hop in hop_names:
            assert hop in names, (hop, by_id[tid])
    assert missing == 0, (
        "%d of %d SLO-breaching served requests missing from the "
        "flight requests ring" % (missing, len(served_2x)))

    # attribution: segments explain ~100% of measured tail latency
    traceview = _load_traceview()
    rstats = traceview.requests_stats(pinned,
                                      doc.get("requests_sampled") or [])
    mlp_rows = [m for m in rstats["models"] if m["model"] == "mlp"]
    assert mlp_rows, rstats
    coverage = mlp_rows[0]["coverage"]
    assert coverage >= 0.90, (
        "waterfall segments explain only %.1f%% of tail latency"
        % (coverage * 100.0,))

    # fleet-merge proof: a subprocess worker inherits the trace root
    # from the environment and its dump merges onto our timeline
    worker_dump = os.path.join(fleet_dir, "reqtrace_worker.json")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--reqtrace-worker", worker_dump],
        # this process holds the chip (if any): the child serves on cpu
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    with open(worker_dump) as f:
        wdoc = json.load(f)
    root = reqtrace.fleet_header()["root"]
    assert wdoc["fleet"]["root"] == root, (
        "worker did not inherit the env-propagated trace root: %r vs "
        "%r" % (wdoc["fleet"].get("root"), root))
    assert wdoc["requests"], "worker pinned no traces"

    # the CLI contracts: --requests renders the flight dump, --fleet
    # merges the dir, both rc 0
    rc_requests = traceview.main(["--requests", flight_path])
    assert rc_requests == 0, rc_requests
    rc_fleet = traceview.main(["--fleet", fleet_dir])
    assert rc_fleet == 0, rc_fleet
    fstats = traceview.fleet_stats(traceview.fleet_sources(fleet_dir))
    assert len(fstats["sources"]) == 2, fstats["sources"]
    assert fstats["roots"] == [root], fstats["roots"]

    print(json.dumps({
        "metric": "bench_reqtrace_smoke",
        "slo_ms": round(slo_ms, 1),
        "phase_1x": {"offered": len(traffic_1x.schedule),
                     "served": len(served_1x), "shed": len(sheds_1x)},
        "phase_2x": {"offered": len(traffic_2x.schedule),
                     "served": len(served_2x), "shed": len(sheds_2x)},
        "retraces": 0,
        "pinned": len(pinned),
        "pinned_overloaded": len(overloaded),
        "pinned_slo_breach": len(breach_ids),
        "sampled": stats["sampled"],
        "sampled_bytes": stats["sampled_bytes"],
        "sampled_byte_cap": ring_bytes,
        "tail_coverage": round(coverage, 4),
        "fleet_dir": fleet_dir,
        "trace_root": root,
    }))


def health_smoke():
    """Health-sentinel CI mode (`make bench-smoke` step 3, `bench.py
    --health-smoke`): proves the sentinel's three contracts on a real
    3-step fit:

    1. **health off is free and bit-identical** — two fresh fits with
       ``MXNET_TPU_HEALTH=0`` produce identical exec-cache trace
       counters and bitwise-identical trained parameters, and register
       zero ``health.*`` telemetry series (the off path IS this PR's
       parent path);
    2. **enabling costs at most one retrace per program** — the same
       fit with ``MXNET_TPU_HEALTH=1`` adds <=1 to the total retrace
       count (the health program is a distinct cache entry);
    3. **a forced-NaN run leaves evidence** — NaN data at batch 1
       stops the fit with ``TrainingDivergedError`` naming step 1 and
       writes a flight dump that ``tools/traceview.py --flight``
       resolves to the same step with exit code 1.
    """
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache
    from mxnet_tpu.observability import flight_recorder, health, telemetry

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    os.environ.pop("MXNET_TPU_HEALTH_RULES", None)
    os.environ.pop("MXNET_TPU_FLIGHT_PATH", None)

    ctx = mx.cpu()

    def mlp():
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                    name="fc1")
        net = mx.sym.Activation(net, act_type="relu", name="relu1")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def fit_once(nan_batch=None):
        """One fresh 3-step fit; returns (trace counts, params)."""
        executor_cache.clear()
        executor_cache.reset_stats()
        telemetry.reset()
        flight_recorder.reset()
        mx.random.seed(0)  # identical init across runs (bitwise oracle)
        rng = np.random.RandomState(0)
        x = rng.rand(24, 8).astype(np.float32)
        y = rng.randint(0, 4, (24,)).astype(np.float32)
        if nan_batch is not None:
            x[nan_batch * 8:(nan_batch + 1) * 8] = np.nan
        from mxnet_tpu.io import NDArrayIter
        mod = mx.mod.Module(mlp(), context=ctx)
        mod.fit(NDArrayIter(x, y, batch_size=8), num_epoch=1,
                optimizer_params={"learning_rate": 0.1})
        params = {k: v.asnumpy().copy()
                  for k, v in mod.get_params()[0].items()}
        return executor_cache.trace_counts(), params

    # 1) off path: identical counters, bitwise-identical params, zero
    #    health.* series — the sentinel off is indistinguishable from
    #    the parent
    os.environ["MXNET_TPU_HEALTH"] = "0"
    counts_off, params_a = fit_once()
    counts_off2, params_b = fit_once()
    assert counts_off == counts_off2, (counts_off, counts_off2)
    assert set(params_a) == set(params_b)
    assert all(np.array_equal(params_a[k], params_b[k]) for k in params_a)
    snap = telemetry.snapshot()
    leaked = sorted(k for k in snap if k.startswith("health."))
    assert not leaked, leaked

    # 2) on path: <=1 added retrace, health series + flight steps live
    os.environ["MXNET_TPU_HEALTH"] = "1"
    counts_on, _ = fit_once()
    delta = sum(counts_on.values()) - sum(counts_off.values())
    assert 0 <= delta <= 1, (counts_on, counts_off)
    snap = telemetry.snapshot()
    assert any(k.startswith("health.") for k in snap), sorted(snap)
    steps_recorded = flight_recorder.get_recorder().steps_recorded()
    assert steps_recorded == 3, steps_recorded

    # 3) forced NaN at batch 1: diverge at step 1 + parseable dump
    dump_path = "/tmp/mxnet_tpu_health_smoke_flight.json"
    os.environ["MXNET_TPU_FLIGHT_PATH"] = dump_path
    try:
        diverged = None
        try:
            fit_once(nan_batch=1)
        except health.TrainingDivergedError as exc:
            diverged = exc
        assert diverged is not None, "forced-NaN fit did not diverge"
        assert diverged.step == 1, diverged.step
        assert diverged.rule == "nonfinite", diverged.rule
        assert diverged.dump_path == dump_path and os.path.exists(dump_path)
    finally:
        os.environ.pop("MXNET_TPU_FLIGHT_PATH", None)
        os.environ["MXNET_TPU_HEALTH"] = "0"

    traceview = _load_traceview()
    rc = traceview.main(["--flight", dump_path])
    assert rc == 1, "traceview --flight must exit 1 on an anomalous dump"
    with open(dump_path) as f:
        doc = json.load(f)
    assert doc["first_anomaly_step"] == diverged.step, doc[
        "first_anomaly_step"]

    print(json.dumps({
        "metric": "bench_health_smoke",
        "trace_counters_off": counts_off,
        "trace_counters_on": counts_on,
        "retrace_delta_on": delta,
        "flight_steps_recorded": steps_recorded,
        "nan_diverged_step": diverged.step,
        "flight_dump": dump_path,
        "traceview_exit": rc,
    }))


def mem_smoke():
    """Memory & compile observability CI mode (`make bench-smoke`
    step 6, `bench.py --mem-smoke`): proves the memprof contracts on
    the same 3-step fit the health smoke uses:

    1. **memprof is invisible to the compiler** — identical 3-step fits
       with ``MXNET_TPU_MEMPROF=0`` and ``=1`` produce IDENTICAL
       exec-cache trace counters (zero added retraces/dispatches) and
       bitwise-identical trained parameters (the AOT dispatch twin runs
       the same lowering/compile pipeline), while the on-run captures
       per-program ``memory_analysis`` and the compile-time histogram —
       and `traceview --memory` renders the written report;
    2. **the retrace explainer names the component** — a forced
       same-symbol miss (same graph re-bound at a different batch
       shape) emits a ``recompile_cause`` naming "shapes";
    3. **a simulated OOM leaves the augmented black box** — a
       monkeypatched serving dispatch raising RESOURCE_EXHAUSTED writes
       a flight dump embedding the memory report (program table +
       census) that ``tools/traceview.py --flight`` parses with exit 1.
    """
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.observability import flight_recorder, memprof, telemetry

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    os.environ["MXNET_TPU_HEALTH"] = "0"
    os.environ.pop("MXNET_TPU_FLIGHT_PATH", None)

    ctx = mx.cpu()

    def mlp():
        net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                    name="fc1")
        net = mx.sym.Activation(net, act_type="relu", name="relu1")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def fit_once():
        """One fresh 3-step fit; returns (trace counts, params)."""
        executor_cache.clear()
        executor_cache.reset_stats()
        memprof.reset()
        telemetry.reset()
        flight_recorder.reset()
        mx.random.seed(0)  # identical init across runs (bitwise oracle)
        rng = np.random.RandomState(0)
        x = rng.rand(24, 8).astype(np.float32)
        y = rng.randint(0, 4, (24,)).astype(np.float32)
        from mxnet_tpu.io import NDArrayIter
        mod = mx.mod.Module(mlp(), context=ctx)
        mod.fit(NDArrayIter(x, y, batch_size=8), num_epoch=1,
                optimizer_params={"learning_rate": 0.1})
        params = {k: v.asnumpy().copy()
                  for k, v in mod.get_params()[0].items()}
        return executor_cache.trace_counts(), params

    # 1) memprof on/off: identical counters, bitwise params, and the
    #    on-run actually captures the attribution
    os.environ["MXNET_TPU_MEMPROF"] = "0"
    counts_off, params_off = fit_once()
    stats_off = executor_cache.stats()
    assert not any(r.get("memory") for r in stats_off["programs"]), \
        "memprof off must not capture memory_analysis"
    os.environ["MXNET_TPU_MEMPROF"] = "1"
    counts_on, params_on = fit_once()
    assert counts_on == counts_off, (counts_on, counts_off)
    assert set(params_on) == set(params_off)
    assert all(np.array_equal(params_on[k], params_off[k])
               for k in params_on), "AOT dispatch changed the math"
    stats_on = executor_cache.stats()
    with_mem = [r for r in stats_on["programs"] if r.get("memory")]
    assert with_mem, "memprof on captured no memory_analysis"
    assert all(r["memory"]["total_bytes"] > 0 for r in with_mem)
    assert stats_on["compile_ms"]["count"] >= 1, stats_on["compile_ms"]
    snap = telemetry.snapshot()
    assert snap.get("exec_cache.compile_ms", {}).get("count"), \
        "exec_cache.compile_ms histogram did not fill"

    report_path = "/tmp/mxnet_tpu_mem_smoke_report.json"
    memprof.write_report(report_path)

    # 2) forced same-symbol reshape miss -> recompile_cause "shapes"
    executor_cache.reset_stats()
    sym = mlp()
    for batch in (8, 16):
        mod = mx.mod.Module(sym, context=ctx)
        mod.bind(data_shapes=[("data", (batch, 8))],
                 label_shapes=[("softmax_label", (batch,))])
        mod.init_params()
    causes = executor_cache.stats()["recompile_causes"]
    assert causes.get("shapes", 0) >= 1, causes

    # 3) simulated OOM through the serving dispatch path
    flight_recorder.reset()
    dump_path = "/tmp/mxnet_tpu_mem_smoke_flight.json"
    os.environ["MXNET_TPU_FLIGHT_PATH"] = dump_path
    try:
        if os.path.exists(dump_path):
            os.remove(dump_path)
        server = serving.Server(max_batch_size=4)
        mod = mx.mod.Module(mlp(), context=ctx)
        mod.bind(data_shapes=[("data", (4, 8))],
                 label_shapes=[("softmax_label", (4,))])
        mod.init_params()
        args_d, _ = mod.get_params()
        served = server.add_model("mlp", mlp(), dict(args_d),
                                  input_shapes={"data": (8,)})
        server.warmup()

        class XlaRuntimeError(RuntimeError):
            """Stand-in for jaxlib's class (is_oom matches the status
            token, not the import path)."""

        def boom(bucket, inputs):
            raise XlaRuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating "
                "9876543210 bytes (simulated)")

        served.run_batch = boom
        oom_seen = False
        try:
            server.submit("mlp", np.ones((2, 8), np.float32), timeout=30)
        except RuntimeError as exc:
            oom_seen = "RESOURCE_EXHAUSTED" in str(exc)
        server.close(drain=True, timeout=30)
        assert oom_seen, "the simulated OOM did not reach the client"
        assert os.path.exists(dump_path), "OOM wrote no flight dump"
        with open(dump_path) as f:
            doc = json.load(f)
        assert doc["reason"] == "oom", doc["reason"]
        assert any(a.get("rule") == "oom" for a in doc["anomalies"])
        mem = doc.get("memory") or {}
        assert mem.get("programs") is not None
        assert (mem.get("census") or {}).get("array_count", 0) > 0
    finally:
        os.environ.pop("MXNET_TPU_FLIGHT_PATH", None)
        os.environ["MXNET_TPU_MEMPROF"] = "0"

    traceview = _load_traceview()
    rc_flight = traceview.main(["--flight", dump_path])
    assert rc_flight == 1, \
        "traceview --flight must exit 1 on the OOM dump"
    rc_mem = traceview.main(["--memory", report_path])
    assert rc_mem == 0, "traceview --memory failed on the report"

    print(json.dumps({
        "metric": "bench_mem_smoke",
        "trace_counters_off": counts_off,
        "trace_counters_on": counts_on,
        "params_bitwise_identical": True,
        "programs_with_memory": len(with_mem),
        "compile_ms_total": stats_on["compile_ms"]["total_ms"],
        "recompile_causes": causes,
        "memory_report": report_path,
        "oom_flight_dump": dump_path,
        "traceview_flight_exit": rc_flight,
    }))


def io_smoke():
    """Input-pipeline CI mode (`make bench-smoke` step 4, `bench.py
    --io-smoke`): proves the io_pipeline contracts on a real record
    file and a real fit at the PR 4/5 bench batch size (32):

    1. **determinism across worker counts** — the full epoch batch
       sequence (data bytes, labels, pad) is bitwise-identical for a
       fixed seed at 1, 2 and 4 workers; throughput per worker count is
       reported;
    2. **zero added retraces** — a fit fed by the pipeline adapter
       produces exec-cache trace counters IDENTICAL to the same fit fed
       by a plain NDArrayIter (the pipeline is invisible to the
       compiler), and a second pipeline-fed fit over the warm cache
       retraces nothing (`executor_cache.watch_traces`);
    3. **starvation vs measured baseline + overlap contract** — over
       warm-cache fits fed by the PROCESS-pool pipeline (this smoke's
       decode is pure Python, i.e. GIL-bound — exactly the case the
       process pool exists for; thread-mode python decode convoys on
       GIL handoffs with the driving thread), the fit loop's
       `data_wait` share of step time — median of 3 runs — stays
       within 2x (+0.2pp) of the same-module, same-host floor measured
       by a median-of-3 IN-MEMORY NDArrayIter sweep (zero decode, zero
       prefetch: whatever data_wait that shows is host noise — queue
       take, GIL reacquisition — not pipeline behavior), never worse
       than an absolute 2%; and the uploads were issued AHEAD of
       consumption (`io_pipeline.h2d_ahead_total`) — batch N's H2D
       rides under step N-1's compute.  (The old absolute <1% bar was
       verified flaky at BASELINE on this shared box: 3/4 plain
       NDArrayIter runs measured 1.04-1.28%.)

    Environment shaping, applied before jax loads: XLA's cpu eigen
    pool is pinned to one thread so the 2-core CI host keeps a core of
    input-pipeline headroom (production TPU hosts have many spare host
    cores; with BOTH cores saturated by XLA the smoke measures the
    OS scheduler, not the pipeline), and the GIL switch interval drops
    to 0.5 ms so parent-side per-batch work (unpickle, device_put)
    isn't quantized to 5 ms GIL stalls.  The starvation phase retries
    once — it is a wall-clock measurement on a shared host.
    """
    import os
    import shutil
    import sys as _sys
    import tempfile

    assert "jax" not in _sys.modules, \
        "--io-smoke must run in a fresh process (it shapes XLA_FLAGS)"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_cpu_multi_thread_eigen=false"
                               ).strip()
    _sys.setswitchinterval(0.0005)

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, io_pipeline, recordio
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.observability import telemetry

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    for knob in ("MXNET_TPU_IO_WORKERS", "MXNET_TPU_IO_PREFETCH_DEPTH",
                 "MXNET_TPU_IO_DOUBLE_BUFFER"):
        os.environ.pop(knob, None)

    batch = 32          # the PR 4/5 bench batch size
    n_rec, feat = 256, 512
    tmpd = tempfile.mkdtemp(prefix="io_smoke_")
    try:
        rec = os.path.join(tmpd, "t.rec")
        rng = np.random.RandomState(0)
        writer = recordio.MXIndexedRecordIO(rec + ".idx", rec, "w")
        feats = rng.rand(n_rec, feat).astype(np.float32)
        labels = (np.arange(n_rec) % 4).astype(np.float32)
        for i in range(n_rec):
            writer.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(labels[i]), i, 0),
                feats[i].tobytes()))
        writer.close()
        source = io_pipeline.RecordFileSource(rec, rec + ".idx")
        decode = io_pipeline.NDArrayRecordDecoder((feat,))

        def make_pipeline(workers=2, mode="thread"):
            return io_pipeline.Pipeline(
                source, decode, batch_size=batch, shuffle=True, seed=7,
                num_workers=workers, prefetch_depth=4, mode=mode,
                ctx=mx.cpu())

        # 1) determinism sweep + img/s per worker count
        sweep, ref_seq = [], None
        for workers in (1, 2, 4):
            pipe = make_pipeline(workers)
            t0 = time.perf_counter()
            seq = [(b.data.tobytes(), b.label.tobytes(), b.pad)
                   for b in pipe.host_batches(0)]
            wall = time.perf_counter() - t0
            sweep.append({"workers": workers,
                          "img_s": round(n_rec / wall, 1)})
            if ref_seq is None:
                ref_seq = seq
            else:
                assert seq == ref_seq, (
                    "batch sequence differs at %d workers" % workers)

        def mlp():
            # sized so one step is >100 ms on the single-eigen-thread
            # cpu backend: the starvation assert compares a per-step
            # data_wait FLOOR (queue take + GIL reacquisition, ~0.5 ms)
            # against step time, so the step must dwarf the floor for
            # the <1% bar to measure the pipeline, not host jitter
            net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                        num_hidden=16384, name="fc1")
            net = mx.sym.Activation(net, act_type="relu", name="relu1")
            net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
            return mx.sym.SoftmaxOutput(net, name="softmax")

        def fit_once(it, clear=True):
            if clear:
                executor_cache.clear()
                executor_cache.reset_stats()
            mx.random.seed(0)
            mod = mx.mod.Module(mlp(), context=mx.cpu())
            mod.fit(it, num_epoch=2,
                    optimizer_params={"learning_rate": 0.1})
            if hasattr(it, "close"):
                it.close()
            return executor_cache.trace_counts()

        # 2) trace counters identical: pipeline on vs off
        counts_off = fit_once(NDArrayIter(feats, labels,
                                          batch_size=batch))
        counts_on = fit_once(make_pipeline().as_dataiter())
        assert counts_on == counts_off, (counts_on, counts_off)

        # 3) starvation + overlap over a WARM fit fed by the
        #    process-pool pipeline (pure-python decode is GIL-bound —
        #    the config the process pool exists for).  Same module both
        #    times: the second fit reuses every traced program, so the
        #    trace watch proves the pipeline itself compiles nothing.
        proc_pipe = make_pipeline(workers=2, mode="process")
        mx.random.seed(0)
        mod = mx.mod.Module(mlp(), context=mx.cpu())
        warm_it = proc_pipe.as_dataiter()
        mod.fit(warm_it, num_epoch=1,
                optimizer_params={"learning_rate": 0.1})

        # the adapters share proc_pipe's persistent spawn pool; closing
        # one would tear the pool down and make the retry re-pay the
        # worker interpreter starts — close everything at the end
        measured_iters = []

        def measured_fit(make_it):
            telemetry.reset()
            it = make_it()
            if hasattr(it, "close"):
                measured_iters.append(it)
            with executor_cache.watch_traces() as watch:
                mod.fit(it, num_epoch=2,
                        optimizer_params={"learning_rate": 0.1})
            # warm module + warm cache: the pipeline compiles nothing
            assert watch.total() == 0, watch.delta()
            snap = telemetry.snapshot()
            step_ms = snap["module.step.total_ms"]["sum"]
            wait_ms = snap["module.step.data_wait_ms"]["sum"]
            ahead = snap.get("io_pipeline.h2d_ahead_total",
                             {}).get("value", 0)
            steps = snap["module.steps"]["value"]
            assert steps == 2 * (n_rec // batch), steps
            return (wait_ms / step_ms if step_ms else 0.0, step_ms,
                    steps, ahead)

        # starvation is a wall-clock measurement on a shared host: the
        # absolute <1% bar was flaky at BASELINE (an in-memory iterator
        # measured 1.04-1.28% in 3/4 runs on this box).  Measure the
        # host's data_wait floor with the same module over a plain
        # NDArrayIter (median of 3), then hold the pipeline's median of
        # 3 to a ratio of that floor, never worse than an absolute 2%.
        baseline_runs = sorted(
            measured_fit(lambda: NDArrayIter(feats, labels,
                                             batch_size=batch))[0]
            for _ in range(3))
        pipe_runs = sorted((measured_fit(proc_pipe.as_dataiter)
                            for _ in range(3)), key=lambda r: r[0])
        baseline = baseline_runs[1]
        starvation, step_ms, steps, h2d_ahead = pipe_runs[1]
        for it in measured_iters:
            it.close()
        warm_it.close()
        bar = min(max(2.0 * baseline + 0.002, 0.01), 0.02)
        assert starvation < bar, (
            "fit data_wait is %.2f%% of step time (bar %.2f%%; measured "
            "in-memory baseline %.2f%%)"
            % (100 * starvation, 100 * bar, 100 * baseline))
        # overlap contract: all but the primed pulls of each epoch were
        # taken AHEAD of consumption (their H2D issued under compute)
        assert h2d_ahead >= 2 * (n_rec // batch - 2), h2d_ahead

        print(json.dumps({
            "metric": "bench_io_smoke",
            "batch_size": batch,
            "records": n_rec,
            "worker_sweep": sweep,
            "trace_counters_off": counts_off,
            "trace_counters_on": counts_on,
            "starvation_data_wait": round(starvation, 5),
            "starvation_baseline": round(baseline, 5),
            "starvation_bar": round(bar, 5),
            "step_ms_avg": round(step_ms / steps, 2) if steps else None,
            "h2d_ahead": int(h2d_ahead),
            "recompiles_after_warm": 0,
        }))
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)


def kernel_smoke():
    """Pallas-kernel CI mode (`make bench-smoke` step 5, `bench.py
    --kernel-smoke`): proves the kernel-layer contracts (docs/kernels.md)
    on the CPU test backend, where every Pallas kernel runs through the
    interpreter (same kernel code path as the chip):

    1. **direct parity** — max-pooling backward (stride != kernel)
       and the BN channel-sums epilogue match their XLA fallbacks on
       CPU-shaped inputs; int8 predict matches f32 predict to quant
       tolerance with identical argmax;
    2. **flag contract** — with the flags off, two identical
       forward_backward runs produce identical exec-cache counters and
       bitwise-identical gradients (the off path IS the parent program);
       enabling `MXNET_TPU_PALLAS_POOL`+`MXNET_TPU_PALLAS_BN` re-keys the
       program for exactly ONE retrace (`executor_cache.watch_traces`),
       kernel-path gradients agree with the fallback to tolerance, and
       flipping back off retraces NOTHING (the off entry is still cached)
       with gradients bitwise equal to the first off run — the off-path
       program is untouched.
    """
    import os
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.predict import Predictor

    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ.pop("MXNET_TPU_EXEC_CACHE_SIZE", None)
    for flag in ("MXNET_TPU_PALLAS_POOL", "MXNET_TPU_PALLAS_BN",
                 "MXNET_TPU_QUANTIZE"):
        os.environ.pop(flag, None)

    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    executor_cache.clear()
    executor_cache.reset_stats()

    # 1) direct kernel-vs-fallback parity (interpret mode on cpu)
    parity = {}
    x = jnp.asarray(rng.randn(2, 4, 12, 14).astype(np.float32))
    from mxnet_tpu.ops.nn import _pool_core
    cfg = ("max", (3, 3), (2, 2), (1, 1), "valid", True)  # avg/sum: no kernel
    ref = jax.grad(lambda v: jnp.sum(_pool_core(*cfg, "off")(v) ** 2))(x)
    got = jax.grad(
        lambda v: jnp.sum(_pool_core(*cfg, "interpret")(v) ** 2))(x)
    err = float(jnp.max(jnp.abs(got - ref)))
    parity["pool_bwd_max"] = err
    assert err < 1e-5, err
    s1, s2 = pk.bn_channel_sums(x, interpret=True)
    err = max(float(jnp.max(jnp.abs(s1 - jnp.sum(x, (0, 2, 3))))),
              float(jnp.max(jnp.abs(s2 - jnp.sum(x * x, (0, 2, 3))))))
    parity["bn_channel_sums"] = err
    assert err < 1e-3, err

    def convnet():
        net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                                 num_filter=8, pad=(1, 1), name="conv1")
        net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
        net = mx.sym.Activation(net, act_type="relu", name="relu1")
        net = mx.sym.Pooling(net, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                             pool_type="max", name="pool1")
        net = mx.sym.Flatten(net, name="flat1")
        net = mx.sym.FullyConnected(net, num_hidden=4, name="fc1")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    net_sym = convnet()  # ONE symbol: revisits must share its programs

    def batch():
        from mxnet_tpu.io import DataBatch, DataDesc
        r = np.random.RandomState(7)
        return DataBatch(
            data=[mx.nd.array(r.rand(8, 3, 8, 8).astype(np.float32))],
            label=[mx.nd.array(r.randint(0, 4, (8,)).astype(np.float32))],
            provide_data=[DataDesc("data", (8, 3, 8, 8))],
            provide_label=[DataDesc("softmax_label", (8,))])

    def run_fb():
        mod = mx.mod.Module(net_sym, context=mx.cpu())
        mod.bind([("data", (8, 3, 8, 8))], [("softmax_label", (8,))])
        mx.random.seed(0)
        mod.init_params(mx.initializer.Xavier())
        with executor_cache.watch_traces() as w:
            mod.forward_backward(batch())
        exe = mod._exec_group.execs[0]
        grads = {n: np.asarray(g._h.array)
                 for n, g in exe.grad_dict.items()}
        return w, grads

    # 2) flag contract through the executor program
    w_off1, g_off1 = run_fb()
    w_off2, g_off2 = run_fb()
    assert w_off2.total() == 0, ("off revisit retraced", w_off2.delta())
    assert all(np.array_equal(g_off1[k], g_off2[k]) for k in g_off1)

    os.environ["MXNET_TPU_PALLAS_POOL"] = "1"
    os.environ["MXNET_TPU_PALLAS_BN"] = "1"
    w_on, g_on = run_fb()
    on_delta = w_on.delta()
    assert w_on.total() == 1 and on_delta.get("traces_fwd_bwd") == 1, (
        "enabling the kernel flags must cost exactly one retrace of the "
        "fused fwd_bwd program", on_delta)
    kernel_vs_fallback = max(
        float(np.max(np.abs(g_on[k].astype(np.float32)
                            - g_off1[k].astype(np.float32))))
        for k in g_off1)
    assert kernel_vs_fallback < 1e-3, kernel_vs_fallback

    os.environ.pop("MXNET_TPU_PALLAS_POOL")
    os.environ.pop("MXNET_TPU_PALLAS_BN")
    w_back, g_back = run_fb()
    assert w_back.total() == 0, (
        "the flag-off path must come back from the cache untouched",
        w_back.delta())
    assert all(np.array_equal(g_off1[k], g_back[k]) for k in g_off1), \
        "off-path gradients changed after a kernel-flag round trip"

    # 3) int8 predict vs f32 (dynamic ranges; docs/serving.md §int8)
    qsym = convnet()
    arg_shapes, _, _ = qsym.infer_shape(data=(1, 3, 8, 8))
    params = {"arg:%s" % n: mx.nd.array(
        rng.normal(0, 0.3, s).astype(np.float32))
        for n, s in zip(qsym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")}
    xq = rng.rand(8, 3, 8, 8).astype(np.float32)
    p32 = Predictor(qsym.tojson(), dict(params), {"data": (8, 3, 8, 8)})
    p8 = Predictor(qsym.tojson(), dict(params), {"data": (8, 3, 8, 8)},
                   quantize="int8")
    p32.forward(data=xq)
    p8.forward(data=xq)
    o32 = p32.get_output(0).asnumpy()
    o8 = p8.get_output(0).asnumpy()
    int8_dev = float(np.max(np.abs(o8 - o32)))
    int8_top1 = float((np.argmax(o8, 1) == np.argmax(o32, 1)).mean())
    assert int8_dev < 0.05 and int8_top1 == 1.0, (int8_dev, int8_top1)

    print(json.dumps({
        "metric": "bench_kernel_smoke",
        "parity_max_err": parity,
        "enable_retraces": on_delta,
        "disable_retraces": w_back.delta(),
        "kernel_vs_fallback_grad_err": kernel_vs_fallback,
        "off_path_bitwise": True,
        "int8_vs_f32_max_dev": int8_dev,
        "int8_top1_agreement": int8_top1,
    }))


def comm_smoke():
    """Overlapped-gradient-collectives CI mode (`make bench-smoke`
    step 7, `bench.py --comm-smoke`), on the 8-virtual-device cpu
    harness (the MULTICHIP topology).  Proves the contracts of
    docs/distributed.md:

    1. bucketed overlap (`MXNET_TPU_COMM_BUCKET_MB`) trains to the SAME
       parameters as the monolithic step (allclose; bitwise where XLA's
       reduction order permits) with an IDENTICAL retrace count, and the
       compiled fused-step HLO shows >= 2 distinct all-reduce ops (one
       per bucket) instead of a combined tail collective;
    2. the executor-cache flag contract: flipping the knob re-keys
       gradient-taking programs (enable = exactly 1 retrace, disable =
       0, off-path gradients bitwise identical across the round trip);
    3. 2-bit compression (`MXNET_TPU_GRAD_COMPRESS=2bit`) moves <= 1/8
       of the f32 gradient bytes on the wire (counter-verified: exactly
       2 bits/value + padding) while the smoke task still converges;
    4. writes MULTICHIP_r06.json recording both modes against r05
       (which had no comm instrumentation at all).
    """
    import io as _io
    import contextlib
    import os
    import sys as _sys

    assert "jax" not in _sys.modules, \
        "--comm-smoke must run in a fresh process (it shapes XLA_FLAGS)"
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        os.environ["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    _COMM_KNOBS = ("MXNET_TPU_COMM_BUCKET_MB", "MXNET_TPU_GRAD_COMPRESS",
                   "MXNET_TPU_GRAD_COMPRESS_THRESHOLD")
    for knob in _COMM_KNOBS:
        os.environ.pop(knob, None)

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache
    from mxnet_tpu.observability import telemetry
    from mxnet_tpu.parallel import comm

    n_dev = 8
    rng = np.random.RandomState(0)
    W = rng.randn(16, 4)
    X = rng.randn(512, 16).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)

    def mlp():
        h = mx.sym.Activation(mx.sym.FullyConnected(
            mx.sym.var("data"), num_hidden=32, name="fc1"),
            act_type="relu")
        return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h, num_hidden=4, name="fc2"), name="softmax")

    def set_knobs(**env):
        for knob in _COMM_KNOBS:
            os.environ.pop(knob, None)
        os.environ.update({k: str(v) for k, v in env.items()})

    def fit_once(epochs=4, lr=0.1):
        mx.random.seed(0)
        it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=False)
        mod = mx.mod.Module(mlp(), context=[mx.cpu(i)
                                            for i in range(n_dev)])
        with executor_cache.watch_traces() as w:
            mod.fit(it, num_epoch=epochs, kvstore="tpu_ici",
                    optimizer_params={"learning_rate": lr,
                                      "momentum": 0.9},
                    initializer=mx.initializer.Xavier(
                        rnd_type="uniform", magnitude=2.0))
        it.reset()
        acc = dict(mod.score(it, mx.metric.Accuracy()))["accuracy"]
        params = {n: mod._exec_group.execs[0].arg_dict[n].asnumpy()
                  for n in mod._exec_group.param_names}
        return mod, acc, params, w.delta()

    # -- 1. overlap parity + HLO evidence + retrace parity -------------
    mod0, acc0, p0, d0 = fit_once()
    assert mod0._fused_step is not None and \
        mod0._fused_step._comm_plan is None
    set_knobs(MXNET_TPU_COMM_BUCKET_MB=0.001)  # ~1 KB -> several buckets
    telemetry.reset()
    mod1, acc1, p1, d1 = fit_once()
    fs = mod1._fused_step
    assert fs is not None and fs._comm_plan is not None, \
        "overlap did not engage: %s" % (fs and fs.overlap_off_reason,)
    n_buckets = len(fs._comm_plan.buckets)
    assert n_buckets >= 2, fs._comm_plan.buckets
    param_max_diff = max(float(np.max(np.abs(p0[k] - p1[k])))
                         for k in p0)
    for k in p0:
        np.testing.assert_allclose(p0[k], p1[k], rtol=1e-4, atol=1e-6)
    assert d1 == d0, ("overlap flag changed the retrace count",
                      d0, d1)
    hlo = fs.compiled_hlo()
    cc = comm.collective_counts(hlo)
    assert cc["all-reduce"] >= 2, cc
    steps = 4 * (512 // 64)
    snap = telemetry.snapshot()
    overlapped = snap.get("comm.overlapped_bytes", {}).get("value", 0)
    assert overlapped == fs._comm_plan.wire_bytes * steps, \
        (overlapped, fs._comm_plan.wire_bytes, steps)

    # -- 2. executor-cache flag contract -------------------------------
    set_knobs()
    sym = mlp()

    def fb_grads():
        exe = sym.simple_bind(mx.cpu(), grad_req="write",
                              data=(8, 16), softmax_label=(8,))
        exe.arg_dict["data"][:] = mx.nd.array(X[:8])
        exe.arg_dict["softmax_label"][:] = mx.nd.array(y[:8])
        with executor_cache.watch_traces() as w:
            exe.forward_backward(is_train=True)
        return {k: v.asnumpy() for k, v in exe.grad_dict.items()
                if v is not None}, w.delta().get("traces_fwd_bwd", 0)

    g_off1, t_cold = fb_grads()
    _, t_warm = fb_grads()
    assert t_warm == 0, t_warm
    set_knobs(MXNET_TPU_COMM_BUCKET_MB=4)
    _, t_on = fb_grads()
    assert t_on == 1, ("enabling the comm flag must cost exactly one "
                       "retrace", t_on)
    _, t_on2 = fb_grads()
    assert t_on2 == 0, t_on2
    set_knobs()
    g_off2, t_off = fb_grads()
    assert t_off == 0, ("disabling must hit the cached program", t_off)
    for k in g_off1:
        assert np.array_equal(g_off1[k], g_off2[k]), \
            "off path not bitwise across the flag round trip: %s" % k
    causes = executor_cache.stats()["recompile_causes"]
    assert causes.get("comm_flags", 0) >= 1, causes

    # -- 3. 2-bit compression: wire bytes + convergence ----------------
    set_knobs(MXNET_TPU_COMM_BUCKET_MB=0.001,
              MXNET_TPU_GRAD_COMPRESS="2bit",
              MXNET_TPU_GRAD_COMPRESS_THRESHOLD=0.05)
    telemetry.reset()
    modc, accc, pc, dc = fit_once(epochs=12)
    fsc = modc._fused_step
    assert fsc._comm_plan is not None and fsc._comm_plan.compress == "2bit"
    plan = fsc._comm_plan
    wire_ratio = plan.wire_bytes / plan.grad_f32_bytes
    assert wire_ratio <= 1.0 / 8.0, \
        ("2-bit mode must move <= 1/8 of the f32 gradient bytes",
         plan.wire_bytes, plan.grad_f32_bytes)
    csteps = 12 * (512 // 64)
    snap = telemetry.snapshot()
    cbytes = snap.get("comm.overlapped_bytes", {}).get("value", 0)
    assert cbytes == plan.wire_bytes * csteps, (cbytes, plan.wire_bytes)
    ccc = comm.collective_counts(fsc.compiled_hlo())
    assert ccc["all-gather"] >= 2, ccc
    assert accc >= 0.5, ("compressed smoke task did not converge "
                         "(chance = 0.25)", accc)
    set_knobs()

    # -- 4. MULTICHIP_r06.json: both modes vs r05 ----------------------
    tail = _io.StringIO()
    dryrun_ok = True
    try:
        import __graft_entry__
        with contextlib.redirect_stdout(tail):
            __graft_entry__.dryrun_multichip(n_dev)
    except Exception as e:  # the dryrun is lineage, not the contract
        dryrun_ok = False
        tail.write("dryrun failed: %r\n" % (e,))
    record = {
        "n_devices": n_dev,
        "rc": 0,
        "ok": True,
        "skipped": False,
        "source": "bench.py --comm-smoke (PR: overlapped gradient "
                  "collectives)",
        "comm": {
            "overlap": {
                "bucket_mb": 0.001,
                "n_buckets": n_buckets,
                "hlo_all_reduce_ops": cc["all-reduce"],
                "param_max_diff_vs_monolithic": param_max_diff,
                "acc_monolithic": acc0,
                "acc_overlap": acc1,
                "retrace_delta_vs_monolithic": 0,
                "overlapped_bytes_per_step": fs._comm_plan.wire_bytes,
            },
            "compress_2bit": {
                "threshold": 0.05,
                "wire_bytes_per_step": plan.wire_bytes,
                "f32_bytes_per_step": plan.grad_f32_bytes,
                "wire_ratio": wire_ratio,
                "hlo_all_gather_ops": ccc["all-gather"],
                "acc": accc,
            },
            "vs_r05": "r05 had no gradient-comm instrumentation: the "
                      "fused DP step let XLA place per-parameter "
                      "all-reduces with no bucket control, the kvstore "
                      "path dispatched one psum program per key, and "
                      "every comm byte was exposed.  r06 adds in-program "
                      "reverse-autodiff-bucketed collectives (one "
                      "all-reduce per bucket, barrier-chained against "
                      "combining), an opt-in 2-bit error-feedback wire "
                      "format at 1/16 the f32 payload, batched "
                      "push_pull_list collectives, and comm.bytes_total/"
                      "comm.exposed_ms observability.",
        },
        "dryrun_ok": dryrun_ok,
        "tail": tail.getvalue()[-2000:],
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MULTICHIP_r06.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)

    print(json.dumps({
        "metric": "bench_comm_smoke",
        "n_buckets": n_buckets,
        "hlo_all_reduce_ops": cc["all-reduce"],
        "param_max_diff": param_max_diff,
        "retrace_parity": True,
        "flag_contract": {"enable": t_on, "re_enable": t_on2,
                          "disable": t_off, "off_bitwise": True},
        "wire_ratio_2bit": wire_ratio,
        "acc_monolithic": acc0,
        "acc_overlap": acc1,
        "acc_2bit": accc,
        "multichip_record": out_path,
    }))


def tune_smoke():
    """Autotune CI mode (`make bench-smoke` step 9, `bench.py
    --tune-smoke`): closes the observability loop into control
    (observability/autotune.py, docs/autotune.md) on the 8-virtual-
    device cpu harness:

    1. **ServingBucketTuner**: skewed synthetic request sizes through
       the power-of-two default, then the tuner derives a
       traffic-shaped bucket set from the recorded
       ``serving.request_rows`` histogram, stages it, and a re-warmup
       adopts it — the SAME traffic replayed must cut
       ``serving.padded_rows_total`` by >= 30% with ZERO steady-state
       retraces after the re-warmup;
    2. **CommBucketTuner**: hill-climbs ``MXNET_TPU_COMM_BUCKET_MB``
       over short DP-8 training windows, each candidate costing exactly
       one fused-step retrace (the PR 10 cache-key contract), and
       converges within its <= 4-retrace budget;
    3. **decision log**: every decision rides the flight recorder —
       a flight dump's ``tuning`` section parses through
       ``tools/traceview.py --tuning``, and the APPLIED serving change
       has a matching record recoverable from the dump.
    """
    import os
    import sys as _sys
    import time as _time

    assert "jax" not in _sys.modules, \
        "--tune-smoke must run in a fresh process (it shapes XLA_FLAGS)"
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        os.environ["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["MXNET_TPU_EXEC_CACHE"] = "1"
    os.environ["MXNET_TPU_TELEMETRY"] = "1"
    for knob in ("MXNET_TPU_COMM_BUCKET_MB", "MXNET_TPU_GRAD_COMPRESS",
                 "MXNET_TPU_AUTOTUNE",
                 "MXNET_TPU_SERVING_DEFAULT_DEADLINE_MS",
                 "MXNET_TPU_SERVING_QUEUE_DEPTH"):
        os.environ.pop(knob, None)

    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, serving
    from mxnet_tpu.observability import (autotune, flight_recorder,
                                         telemetry)
    from mxnet_tpu.parallel import comm

    rng = np.random.RandomState(0)
    telemetry.reset()
    executor_cache.clear()
    executor_cache.reset_stats()
    autotune.clear_decisions()

    # -- 1. serving: traffic-shaped buckets beat power-of-two ----------
    FEAT = 8
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=16,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, FEAT))
    arg_params = {
        n: mx.nd.array(rng.normal(0, 0.1, s).astype(np.float32))
        for n, s in zip(sym.list_arguments(), arg_shapes)
        if n not in ("data", "softmax_label")}

    # window 0 + serial blocking submits: one request per batch, so the
    # padded-rows comparison is deterministic traffic arithmetic
    server = serving.Server(max_batch_size=16, batch_window_ms=0.0)
    model = server.add_model("mlp", sym, arg_params,
                             input_shapes={"data": (FEAT,)})
    server.warmup()
    buckets_po2 = list(model.buckets)

    # skewed sizes: a 5-row mode the power-of-two table pads 3 rows each
    sizes = [5] * 40 + [3] * 12 + [16] * 4
    traffic_rng = np.random.RandomState(3)

    def serve_traffic():
        for n in sizes:
            server.submit("mlp", {"data": traffic_rng.normal(
                0, 1, (n, FEAT)).astype(np.float32)})

    padded = telemetry.counter("serving.padded_rows_total")
    p0 = padded.value
    serve_traffic()
    padded_po2 = padded.value - p0
    assert padded_po2 > 0, "skewed traffic must pad under power-of-two"

    os.environ["MXNET_TPU_AUTOTUNE"] = "apply"
    serving_rec = autotune.ServingBucketTuner().run(model)
    assert serving_rec["action"] == "apply", serving_rec
    assert model.pending_buckets() == serving_rec["decision"]["buckets"]
    server.warmup()  # adopts the staged set, traces it, verifies
    buckets_shaped = list(model.buckets)
    assert buckets_shaped == serving_rec["decision"]["buckets"]

    p1 = padded.value
    with executor_cache.watch_traces() as w:
        serve_traffic()
    assert w.total() == 0, (
        "steady-state retraces after re-warmup: %s" % w.delta())
    padded_shaped = padded.value - p1
    reduction = 1.0 - padded_shaped / padded_po2
    assert reduction >= 0.30, (
        "traffic-shaped buckets must cut padded rows >= 30%%: "
        "%d -> %d (%.1f%%)" % (padded_po2, padded_shaped,
                               reduction * 100.0))
    server.close()

    # -- 2. comm tuner: hill-climb within the retrace budget -----------
    n_dev = 8
    W = rng.randn(16, 4)
    X = rng.randn(512, 16).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)

    def mlp_train():
        h = mx.sym.Activation(mx.sym.FullyConnected(
            mx.sym.var("data"), num_hidden=32, name="fc1"),
            act_type="relu")
        return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h, num_hidden=4, name="fc2"), name="softmax")

    def measure(bucket_mb):
        """Cost of one candidate: a fresh DP-8 fit whose FIRST epoch
        compiles the re-keyed fused step (the retrace the tuner
        budgets) and whose steady epochs are timed — the median keeps
        cpu-harness noise out of the climb."""
        mx.random.seed(0)
        it = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=False)
        mod = mx.mod.Module(mlp_train(),
                            context=[mx.cpu(i) for i in range(n_dev)])
        marks = []
        mod.fit(it, num_epoch=4, kvstore="tpu_ici",
                optimizer_params={"learning_rate": 0.1,
                                  "momentum": 0.9},
                initializer=mx.initializer.Xavier(
                    rnd_type="uniform", magnitude=2.0),
                epoch_end_callback=lambda *a: marks.append(
                    _time.monotonic()))
        warm = sorted(b - a for a, b in zip(marks[1:], marks[2:]))
        return warm[len(warm) // 2] * 1e3  # median warm epoch, ms

    budget = 4
    comm_tuner = autotune.CommBucketTuner(measure, budget=budget,
                                          mode="recommend",
                                          start_mb=0.002,
                                          min_mb=0.0005, max_mb=64.0)
    comm_rec = comm_tuner.run()
    assert comm_rec is not None
    assert comm_rec["action"] in ("recommend", "stop"), comm_rec
    spent = comm_rec["cost"]["retraces"]
    assert spent <= budget, comm_rec["cost"]
    assert len(comm_rec["candidates"]) >= 2, comm_rec["candidates"]
    # the PR 10 cache-key contract, observed: every candidate (a fresh
    # module per measurement window) costs exactly one fused-step
    # retrace — the budget buys bucket sizes, nothing hidden
    for trial in comm_rec["candidates"]:
        assert trial["retraces"] == 1, comm_rec["candidates"]
    # recommend mode leaves the knob exactly as found (unset here)
    assert comm.BUCKET_ENV not in os.environ

    # -- 3. the decision log rides the flight recorder -----------------
    dump_path = "/tmp/mxnet_tpu_tune_smoke_flight.json"
    assert flight_recorder.dump(path=dump_path,
                                reason="tune_smoke") == dump_path
    doc = json.load(open(dump_path))
    tv = _load_traceview()
    records = tv.tuning_records(doc)
    stats = tv.tuning_stats(records)
    assert stats["by_controller"].get("serving_buckets") == 1, stats
    assert stats["by_controller"].get("comm_bucket") == 1, stats
    # the applied change is recoverable from the dump alone
    applied = [r for r in records if r["action"] == "apply"]
    assert applied and applied[0]["controller"] == "serving_buckets"
    assert applied[0]["decision"]["buckets"] == buckets_shaped
    assert tv.main(["--tuning", dump_path]) == 0

    print(json.dumps({
        "metric": "bench_tune_smoke",
        "buckets_po2": buckets_po2,
        "buckets_shaped": buckets_shaped,
        "padded_rows_po2": padded_po2,
        "padded_rows_shaped": padded_shaped,
        "padded_reduction_frac": round(reduction, 4),
        "steady_state_retraces": 0,
        "comm": {"decision_mb": comm_rec["decision"]["bucket_mb"],
                 "candidates": [t["bucket_mb"]
                                for t in comm_rec["candidates"]],
                 "retraces_spent": spent,
                 "retrace_budget": budget,
                 "budget_exhausted":
                     comm_rec["decision"]["budget_exhausted"]},
        "flight_dump": dump_path,
        "decisions_in_dump": stats["decisions"],
    }))


def coldstart_smoke():
    """Cold-start economics CI mode (`make bench-smoke` step 8,
    `bench.py --coldstart-smoke`): proves the persistent compiled-
    program cache's replica-boot contract end to end, in real
    subprocesses (the unit of a cold start is a PROCESS — nothing
    in-memory may carry over):

    1. **cold**: a fresh subprocess stands up the serving stack on an
       empty cache dir, populates it via `Server.prewarm()`, and serves
       one request — time-to-serving measured, executables written;
    2. **warm**: a SECOND fresh subprocess on the now-populated dir
       boots through `warmup(expect_warm=True)` — ZERO executor
       retraces and ZERO backend-compile records (the PR 9 compile-time
       listener's build totals), every program restored from disk — and
       serves the same request;
    3. outputs and params must be BITWISE identical across the two
       processes (a deserialized executable is the same XLA binary),
       and warm time-to-serving must beat cold by >= 5x on the cpu
       smoke.  Both measurements land in COLDSTART_r07.json.
    """
    import os
    import shutil
    import subprocess
    import sys
    import tempfile

    tmpd = tempfile.mkdtemp(prefix="coldstart_cache_")
    env = dict(os.environ)
    # explicit, not inherited: a child of a JAX-holding parent must never
    # reach for the chip
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_TPU_PROGRAM_CACHE_DIR"] = tmpd
    for k in ("MXNET_TPU_EXEC_CACHE", "MXNET_TPU_MEMPROF",
              "MXNET_TPU_PROGRAM_CACHE_RO", "MXNET_TPU_QUANTIZE"):
        env.pop(k, None)

    def run_child(role):
        e = dict(env)
        e["MXTPU_COLDSTART_ROLE"] = role
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--coldstart-child"],
            capture_output=True, text=True, env=e, timeout=900)
        assert r.returncode == 0, (
            "coldstart %s child failed (rc %d):\n--- stdout ---\n%s\n"
            "--- stderr ---\n%s" % (role, r.returncode,
                                    r.stdout[-4000:], r.stderr[-4000:]))
        return json.loads(r.stdout.strip().splitlines()[-1])

    try:
        cold = run_child("cold")
        warm = run_child("warm")
        entries = [n for n in os.listdir(tmpd) if n.endswith(".mxprog")]
    finally:
        shutil.rmtree(tmpd, ignore_errors=True)

    # the warm replica compiled NOTHING: no retraces, no backend
    # compiles, every bucket program restored from disk
    assert warm["builds"]["built"] == 0, warm["builds"]
    assert warm["builds"]["backend_compiles"] == 0, warm["builds"]
    assert warm["traces_total"] == 0, warm
    assert warm["disk"]["hits"] >= len(warm["buckets"]), warm["disk"]
    assert cold["disk"]["writes"] >= len(cold["buckets"]), cold["disk"]
    assert len(entries) >= len(cold["buckets"]), entries
    # bitwise: same params, same request, byte-identical responses
    assert cold["param_sha"] == warm["param_sha"], "nondeterministic init"
    assert cold["out_sha"] == warm["out_sha"], (
        "restored executable answered differently from the freshly "
        "compiled one: %s vs %s" % (cold["out_sha"], warm["out_sha"]))
    speedup = cold["serving_ready_s"] / max(warm["serving_ready_s"], 1e-9)
    assert speedup >= 5.0, (
        "warm start %.2fs vs cold %.2fs — only %.1fx (need >= 5x)"
        % (warm["serving_ready_s"], cold["serving_ready_s"], speedup))

    record = {
        "metric": "coldstart",
        "source": "bench.py --coldstart-smoke (PR: persistent "
                  "compiled-program cache)",
        "created": time.time(),
        "platform": env["JAX_PLATFORMS"],
        "buckets": cold["buckets"],
        "cold": cold,
        "warm": warm,
        "speedup_time_to_serving": round(speedup, 2),
        "cache_entries": len(entries),
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "COLDSTART_r07.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({
        "metric": "bench_coldstart_smoke",
        "cold_serving_ready_s": cold["serving_ready_s"],
        "warm_serving_ready_s": warm["serving_ready_s"],
        "speedup": round(speedup, 2),
        "warm_backend_compiles": warm["builds"]["backend_compiles"],
        "warm_retraces": warm["traces_total"],
        "disk_restores": warm["builds"]["restored"],
        "bitwise_outputs": True,
        "record": out_path,
    }))


def coldstart_child():
    """One replica boot, driven by `coldstart_smoke` in a fresh
    subprocess (role via MXTPU_COLDSTART_ROLE): cold populates the
    cache dir through prewarm, warm must restore everything.  Prints
    ONE JSON line the parent asserts on.  Time-to-serving excludes
    interpreter/framework import (identical in both roles and not what
    the disk tier optimizes); the with-import number rides along."""
    import hashlib
    import os
    import time as _time

    role = os.environ["MXTPU_COLDSTART_ROLE"]
    t_start = _time.time()
    import mxnet_tpu as mx
    from mxnet_tpu import executor_cache, program_cache, serving
    from mxnet_tpu.observability import memprof
    t_import = _time.time()

    rng = np.random.RandomState(7)
    # deep enough that backend compile dominates cold time-to-serving
    # (the fleet regime this cache exists for); tiny enough for CI
    net = mx.sym.Variable("data")
    for i in range(12):
        net = mx.sym.Convolution(net, kernel=(3, 3), pad=(1, 1),
                                 num_filter=32, name="conv%d" % i)
        net = mx.sym.Activation(net, act_type="relu", name="relu%d" % i)
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max", name="pool")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=16,
                                name="head")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    arg_shapes, _, _ = sym.infer_shape(data=(1, 3, 16, 16))
    arg_params = {n: mx.nd.array(rng.normal(0, 0.05, s).astype(np.float32))
                  for n, s in zip(sym.list_arguments(), arg_shapes)
                  if n not in ("data", "softmax_label")}
    param_sha = hashlib.sha256()
    for n in sorted(arg_params):
        param_sha.update(arg_params[n].asnumpy().tobytes())

    totals0 = memprof.build_totals()
    with executor_cache.watch_traces() as watch:
        server = serving.Server(max_batch_size=8, batch_window_ms=2.0)
        server.add_model("mlp", sym, arg_params,
                         input_shapes={"data": (3, 16, 16)})
        if role == "cold":
            report = server.prewarm()
            buckets = report["models"]["mlp"]["buckets"]
        else:
            # expect_warm subsumes the verify sweep: zero retraces over
            # the ENTIRE first pass is strictly stronger than "a second
            # sweep adds none" — raises on any compile
            report = server.warmup(verify=False, expect_warm=True)
            buckets = report["mlp"]["buckets"]
        payload = np.linspace(-1.0, 1.0, 5 * 3 * 16 * 16,
                              dtype=np.float32).reshape(5, 3, 16, 16)
        outs = server.submit("mlp", {"data": payload}, timeout=120)
    t_ready = _time.time()
    totals1 = memprof.build_totals()
    out_sha = hashlib.sha256()
    for o in outs:
        out_sha.update(np.ascontiguousarray(o).tobytes())
    server.close(drain=True, timeout=30)

    print(json.dumps({
        "role": role,
        "buckets": list(buckets),
        "serving_ready_s": round(t_ready - t_import, 4),
        "with_import_s": round(t_ready - t_start, 4),
        "traces_total": watch.total(),
        "builds": {k: totals1[k] - totals0[k] for k in totals1},
        "disk": {k: v for k, v in program_cache.stats().items()
                 if isinstance(v, int) and not isinstance(v, bool)},
        "param_sha": param_sha.hexdigest(),
        "out_sha": out_sha.hexdigest(),
    }))


def elastic_smoke():
    """Preemption-safe elastic-training CI mode (`make bench-smoke`
    step 10, `bench.py --elastic-smoke`): proves the checkpoint/resume
    contracts of docs/elastic.md end to end on the 8-virtual-device
    MULTICHIP harness, in real subprocesses (a preemption kills a
    PROCESS — nothing in-memory may carry over), under a declarative
    chaos plan (`mxnet_tpu/elastic/chaos.py`):

    1. **straight**: an uninterrupted dp=8 run records the reference
       final params (and populates the shared program-cache volume);
    2. **victim**: the same run with a `Checkpointer` on a 5-step
       schedule and a `kill_at_step: 22` fault — the process dies
       mid-epoch with snapshots 10/15/20 retained (keep=3);
    3. the parent CORRUPTS the newest snapshot (flipped bytes, intact
       manifest — `chaos.corrupt_snapshot`);
    4. **resume8**: `elastic.resume_fit` on the same dp=8 factorization
       must reject the corrupt snapshot at manifest verify, fall back
       to step 15, fast-forward the iterator, finish the run with final
       params BITWISE-equal to the uninterrupted ones, and boot WARM:
       zero backend compiles in the whole resumed process (every
       program restores from the `MXNET_TPU_PROGRAM_CACHE_DIR` volume
       the earlier runs populated);
    5. **resume4**: the same resume onto a RE-factorized dp=4 mesh
       (half the workers survived) must train to final params allclose
       to the uninterrupted dp=8 run (reduction-order differences
       only);
    6. the resumed flight dump's `elastic` ring parses through
       `tools/traceview.py --elastic` (rc 0, shows the rejected
       snapshot + the resume), and `--flight` notes the last
       checkpoint step.
    """
    import os
    import shutil
    import subprocess
    import sys
    import tempfile

    cache_dir = tempfile.mkdtemp(prefix="elastic_cache_")
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_ckpt_")
    out_dir = tempfile.mkdtemp(prefix="elastic_out_")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # children never reach for the chip
    xla = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        env["XLA_FLAGS"] = \
            (xla + " --xla_force_host_platform_device_count=8").strip()
    env["MXNET_TPU_PROGRAM_CACHE_DIR"] = cache_dir
    env["MXNET_TPU_CKPT_DIR"] = ckpt_dir
    env["MXNET_TPU_CKPT_STEPS"] = "5"
    env["MXNET_TPU_CKPT_KEEP"] = "3"
    env["MXTPU_ELASTIC_OUT"] = out_dir
    for k in ("MXNET_TPU_CHAOS_PLAN", "MXNET_TPU_COMM_BUCKET_MB",
              "MXNET_TPU_GRAD_COMPRESS", "MXNET_TPU_EXEC_CACHE",
              "MXNET_TPU_PROGRAM_CACHE_RO", "MXNET_TPU_FLIGHT_PATH",
              "MXNET_TPU_HEALTH", "MXNET_TPU_QUANTIZE",
              "MXNET_TPU_LOCKSAN", "MXNET_TPU_LOCKSAN_RULES"):
        env.pop(k, None)

    def run_child(role, extra=None, expect_rc=0):
        e = dict(env)
        e["MXTPU_ELASTIC_ROLE"] = role
        e["MXNET_TPU_FLIGHT_PATH"] = os.path.join(
            out_dir, "flight_%s.json" % role)
        if extra:
            e.update(extra)
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--elastic-child"],
            capture_output=True, text=True, env=e, timeout=900)
        assert r.returncode == expect_rc, (
            "elastic %s child exited %d (wanted %d):\n--- stdout ---\n"
            "%s\n--- stderr ---\n%s" % (role, r.returncode, expect_rc,
                                        r.stdout[-4000:],
                                        r.stderr[-4000:]))
        if expect_rc != 0:
            return None
        return json.loads(r.stdout.strip().splitlines()[-1])

    from mxnet_tpu.elastic import chaos
    kill_step = 22
    try:
        straight = run_child("straight")
        run_child("victim", extra={
            "MXNET_TPU_CHAOS_PLAN": json.dumps(
                [{"kind": "kill_at_step", "step": kill_step}])},
            expect_rc=chaos.DEFAULT_KILL_EXIT)
        snaps = sorted(d for d in os.listdir(ckpt_dir)
                       if d.startswith("snap-"))
        # keep=3 over the 5-step schedule before the step-22 kill
        assert snaps == ["snap-%010d" % s for s in (10, 15, 20)], snaps
        chaos.corrupt_snapshot(os.path.join(ckpt_dir, snaps[-1]))
        # resume8's own schedule keeps writing (and retention keeps
        # pruning) the shared dir — give resume4 a pristine copy of
        # the post-kill post-corruption state so it too resumes from
        # step 15 and trains the long re-factorized tail
        ckpt_dir4 = ckpt_dir + "_dp4"
        shutil.copytree(ckpt_dir, ckpt_dir4)
        ckpt_dir_ls = ckpt_dir + "_ls"
        shutil.copytree(ckpt_dir, ckpt_dir_ls)

        resumed8 = run_child("resume8")
        # corrupt newest rejected at manifest verify -> previous wins
        assert resumed8["resume"]["step"] == 15, resumed8["resume"]
        assert resumed8["resume"]["skip_batches"] == 7, \
            resumed8["resume"]
        assert not resumed8["resume"]["refactorized"]
        # same factorization: the resumed trajectory IS the
        # uninterrupted one — bitwise
        assert resumed8["params_sha"] == straight["params_sha"], (
            "resumed dp=8 params differ from the uninterrupted run")
        # warm resume: the whole resumed process compiled NOTHING — it
        # restored every program from the shared cache volume
        assert resumed8["builds"]["backend_compiles"] == 0, \
            resumed8["builds"]
        assert resumed8["builds"]["built"] == 0, resumed8["builds"]
        assert resumed8["builds"]["restored"] >= 1, resumed8["builds"]

        # LOCKSAN leg: the identical dp=8 resume under the runtime lock
        # sanitizer (MXNET_TPU_LOCKSAN=1) — the elastic loop's lock
        # discipline shows zero violations, the warm resume still
        # compiles nothing (proxies are host-side bookkeeping, no
        # program changes), and final params stay BITWISE-equal
        resumed_ls = run_child("resume8ls", extra={
            "MXNET_TPU_CKPT_DIR": ckpt_dir_ls, "MXNET_TPU_LOCKSAN": "1"})
        assert resumed_ls["locksan_violations"] == 0, resumed_ls
        assert resumed_ls["resume"]["step"] == 15, resumed_ls["resume"]
        assert resumed_ls["params_sha"] == straight["params_sha"], (
            "LOCKSAN=1 resume params differ from the uninterrupted run")
        assert resumed_ls["builds"]["backend_compiles"] == 0, \
            resumed_ls["builds"]
        assert resumed_ls["builds"]["built"] == 0, resumed_ls["builds"]

        resumed4 = run_child("resume4",
                             extra={"MXNET_TPU_CKPT_DIR": ckpt_dir4})
        assert resumed4["resume"]["step"] == 15, resumed4["resume"]
        assert resumed4["resume"]["refactorized"], resumed4["resume"]
        assert resumed4["resume"]["n_dev_to"] == 4
        pS = np.load(os.path.join(out_dir, "straight.npz"))
        p4 = np.load(os.path.join(out_dir, "resume4.npz"))
        param_max_diff = 0.0
        for k in pS.files:
            np.testing.assert_allclose(pS[k], p4[k], rtol=1e-4,
                                       atol=1e-6)
            param_max_diff = max(param_max_diff,
                                 float(np.max(np.abs(pS[k] - p4[k]))))

        # the lineage is recoverable from the flight dump
        tv = _load_traceview()
        with open(resumed8["flight"]) as f:
            doc = json.load(f)
        records = tv.elastic_records(doc)
        stats = tv.elastic_stats(records)
        assert stats["rejected"], "rejected snapshot not in lineage"
        assert stats["resumes"] and \
            stats["resumes"][0]["from_step"] == 15, stats["resumes"]
        rendered = tv.summarize_elastic(records)
        assert "RESUME from step 15" in rendered, rendered
        flight_text = tv.summarize_flight(doc)
        assert "last checkpoint: step" in flight_text, flight_text
    finally:
        for d in (cache_dir, ckpt_dir, ckpt_dir + "_dp4",
                  ckpt_dir + "_ls", out_dir):
            shutil.rmtree(d, ignore_errors=True)

    print(json.dumps({
        "metric": "bench_elastic_smoke",
        "kill_step": kill_step,
        "resume_step": 15,
        "corrupt_newest_skipped": True,
        "bitwise_same_factorization": True,
        "warm_resume_backend_compiles": resumed8["builds"][
            "backend_compiles"],
        "warm_resume_disk_restores": resumed8["builds"]["restored"],
        "refactorized_param_max_diff": param_max_diff,
        "locksan_resume_violations": 0,
        "straight_sha": straight["params_sha"][:16],
    }))


def elastic_child():
    """One worker of `elastic_smoke`, in a fresh subprocess (role via
    MXTPU_ELASTIC_ROLE): `straight` trains uninterrupted, `victim`
    trains under the env-shipped chaos plan until the kill fault
    `os._exit`s it, `resume8`/`resume4` resume from the checkpoint
    volume onto 8/4 devices.  Prints ONE JSON line the parent asserts
    on; final params land in MXTPU_ELASTIC_OUT/<role>.npz."""
    import hashlib
    import os

    role = os.environ["MXTPU_ELASTIC_ROLE"]
    out_dir = os.environ["MXTPU_ELASTIC_OUT"]
    import mxnet_tpu as mx
    from mxnet_tpu import elastic
    from mxnet_tpu.analysis import locksan
    from mxnet_tpu.elastic import chaos
    from mxnet_tpu.observability import flight_recorder, memprof

    n_dev = 4 if role == "resume4" else 8
    epochs, batch = 4, 64
    rng = np.random.RandomState(0)
    W = rng.randn(16, 4)
    X = rng.randn(512, 16).astype(np.float32)
    y = np.argmax(X @ W, axis=1).astype(np.float32)

    def mlp():
        h = mx.sym.Activation(mx.sym.FullyConnected(
            mx.sym.var("data"), num_hidden=32, name="fc1"),
            act_type="relu")
        return mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
            h, num_hidden=4, name="fc2"), name="softmax")

    mx.random.seed(0)
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=False)
    mod = mx.mod.Module(mlp(), context=[mx.cpu(i) for i in range(n_dev)])
    opt_params = {"learning_rate": 0.1, "momentum": 0.9}
    totals0 = memprof.build_totals()
    report = None
    if role == "straight":
        mod.fit(it, num_epoch=epochs, kvstore="tpu_ici",
                optimizer_params=opt_params)
    elif role == "victim":
        ckpt = elastic.Checkpointer()  # env-configured dir/steps/keep
        ckpt.attach(mod)
        chaos.ChaosMonkey(chaos.FaultPlan.from_env()).arm(ckpt)
        mod.fit(it, num_epoch=epochs, kvstore="tpu_ici",
                optimizer_params=opt_params)
        raise SystemExit("chaos kill_at_step did not fire")
    else:
        report = elastic.resume_fit(mod, it, num_epoch=epochs,
                                    kvstore="tpu_ici",
                                    optimizer_params=opt_params)
    totals1 = memprof.build_totals()

    params = {n: mod._exec_group.execs[0].arg_dict[n].asnumpy()
              for n in mod._exec_group.param_names}
    sha = hashlib.sha256()
    for n in sorted(params):
        sha.update(params[n].tobytes())
    np.savez(os.path.join(out_dir, role + ".npz"), **params)
    dump = flight_recorder.dump(reason="elastic_smoke")
    print(json.dumps({
        "role": role,
        "n_dev": n_dev,
        "params_sha": sha.hexdigest(),
        "builds": {k: totals1[k] - totals0[k] for k in totals1},
        "resume": None if report is None else report.describe(),
        "locksan_violations": len(locksan.violations()),
        "flight": dump,
    }))


if __name__ == "__main__":
    import sys
    if "--serve-smoke" in sys.argv:
        serve_smoke()
    elif "--slo-smoke" in sys.argv:
        slo_smoke()
    elif "--alert-smoke" in sys.argv:
        alert_smoke()
    elif "--decode-smoke" in sys.argv:
        decode_smoke()
    elif "--reqtrace-smoke" in sys.argv:
        reqtrace_smoke()
    elif "--reqtrace-worker" in sys.argv:
        reqtrace_fleet_worker()
    elif "--health-smoke" in sys.argv:
        health_smoke()
    elif "--io-smoke" in sys.argv:
        io_smoke()
    elif "--kernel-smoke" in sys.argv:
        kernel_smoke()
    elif "--mem-smoke" in sys.argv:
        mem_smoke()
    elif "--comm-smoke" in sys.argv:
        comm_smoke()
    elif "--tune-smoke" in sys.argv:
        tune_smoke()
    elif "--coldstart-smoke" in sys.argv:
        coldstart_smoke()
    elif "--coldstart-child" in sys.argv:
        coldstart_child()
    elif "--elastic-smoke" in sys.argv:
        elastic_smoke()
    elif "--elastic-child" in sys.argv:
        elastic_child()
    elif "--smoke" in sys.argv:
        smoke()
    else:
        main()

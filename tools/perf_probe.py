"""Perf decomposition probe for the ResNet-50 training step (round 3).

Uses the bench.py methodology (data-chained fori_loop, scalar host fetch,
marginal windows) to A/B variants on the real chip:

  infer       f32 inference forward
  fwd_train   train-mode forward only (BN batch stats)
  train_f32   full fused step, f32 (the 17.5%-MFU baseline)
  train_bf16  bf16 compute (params+data cast inside step), f32 master weights
  conv micro  NCHW vs NHWC, fwd+bwd, representative ResNet-50 layers

Run: python tools/perf_probe.py [experiments...]
"""
from __future__ import annotations

import sys
import time

import numpy as np

BATCH = 32
N_SMALL = 5
N_LARGE = 25
REPS = 5


def _timed(loop_fn, *args, reps=REPS):
    loop_fn(2, *args)
    est = []
    for _ in range(reps):
        t0 = time.perf_counter()
        loop_fn(N_SMALL, *args)
        t1 = time.perf_counter()
        loop_fn(N_LARGE, *args)
        t2 = time.perf_counter()
        est.append(((t2 - t1) - (t1 - t0)) / (N_LARGE - N_SMALL))
    est.sort()
    return est[len(est) // 2]


def _flops_of(compiled):
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return 0.0
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return float(ca.get("flops", 0.0)) if ca else 0.0


def build():
    import mxnet_tpu as mx
    ctx = mx.context.accelerator()
    from mxnet_tpu.models import resnet
    sym = resnet.get_symbol(num_classes=1000, num_layers=50,
                            image_shape="3,224,224")
    rng = np.random.RandomState(0)
    exe = sym.simple_bind(ctx, grad_req="write",
                          data=(BATCH, 3, 224, 224), softmax_label=(BATCH,))
    for name, arr in exe.arg_dict.items():
        if name == "data":
            arr[:] = rng.uniform(0, 1, arr.shape).astype(np.float32)
        elif name == "softmax_label":
            arr[:] = rng.randint(0, 1000, arr.shape).astype(np.float32)
        else:
            arr[:] = rng.normal(0, 0.01, arr.shape).astype(np.float32)
    return exe


def report(name, sec, flops):
    tf = flops / sec / 1e12
    print(f"{name:>14}: {sec*1e3:8.2f} ms/iter  {BATCH/sec:9.1f} img/s  "
          f"{tf:7.2f} TF/s  mfu={tf/197.0:.3f}", flush=True)


def run_fwd(exe, train_mode, tag, cast=None):
    import jax
    import jax.numpy as jnp
    prog = exe._prog
    arg_names, aux_names = prog.arg_names, prog.aux_names
    arg_vals = tuple(exe.arg_dict[n]._h.array for n in arg_names)
    aux_vals = tuple(exe.aux_dict[n]._h.array for n in aux_names)

    def fwd(amap0, aux_map):
        if cast is not None:
            amap0 = {n: (v.astype(cast)
                         if v.dtype == jnp.float32 and n != "softmax_label"
                         else v) for n, v in amap0.items()}
        return prog.evaluate(amap0, aux_map, (), train_mode)

    flops = _flops_of(jax.jit(
        lambda a, x: fwd(dict(zip(arg_names, a)), dict(zip(aux_names, x)))
    ).lower(arg_vals, aux_vals).compile())

    @jax.jit
    def loop(n, arg_vals, aux_vals):
        amap0 = dict(zip(arg_names, arg_vals))
        aux_map = dict(zip(aux_names, aux_vals))

        def body(i, carry):
            data, acc = carry
            amap = dict(amap0)
            amap["data"] = data
            outs, _ = fwd(amap, aux_map)
            m = jnp.mean(outs[0].astype(jnp.float32))
            return data * (1.0 + jnp.tanh(m) * 1e-12), acc + m

        _, acc = jax.lax.fori_loop(0, n, body,
                                   (amap0["data"], jnp.float32(0.0)))
        return acc

    def runner(n, a, x):
        return float(loop(n, a, x))

    sec = _timed(runner, arg_vals, aux_vals)
    report(tag, sec, flops)


def _conv_saveable(prim, *_, **__):
    """Remat policy: keep only MXU-product tensors (conv/dot outputs) as
    backward residuals; recompute the elementwise/BN chains between them.
    On a bandwidth-bound step this trades spare MXU FLOPs for the HBM
    store+reload of every BN/ReLU intermediate."""
    return prim.name in ("conv_general_dilated", "dot_general")


def run_train(exe, tag, compute_dtype=None, lr=0.01, momentum=0.9,
              remat=None):
    """Full SGD+momentum step; optionally cast params+data to compute_dtype
    inside the step (f32 master weights, grads arrive f32 via the cast vjp)."""
    import jax
    import jax.numpy as jnp
    prog = exe._prog
    arg_names, aux_names = prog.arg_names, prog.aux_names
    param_names = [n for n in arg_names if n not in ("data", "softmax_label")]
    other_names = [n for n in arg_names if n in ("data", "softmax_label")]
    other_vals = tuple(exe.arg_dict[n]._h.array for n in other_names)
    params0 = tuple(exe.arg_dict[n]._h.array for n in param_names)
    aux0 = tuple(exe.aux_dict[n]._h.array for n in aux_names)

    def sgd_step(params, mom, aux, other):
        amap = dict(zip(other_names, other))
        if compute_dtype is not None and "data" in amap:
            amap["data"] = amap["data"].astype(compute_dtype)
        aux_map = dict(zip(aux_names, aux))

        def f(pvals):
            m = dict(amap)
            if compute_dtype is not None:
                pvals = [p.astype(compute_dtype) for p in pvals]
            m.update(zip(param_names, pvals))
            outs, new_aux = prog.evaluate(m, aux_map, (), True)
            return outs, tuple(new_aux[n] for n in aux_names)

        if remat is not None:
            f = jax.checkpoint(f, policy=remat)
        (outs, new_aux), vjp_fn = jax.vjp(f, list(params))
        heads = [jnp.ones_like(o) for o in outs]
        zeros_aux = tuple(jnp.zeros_like(a) for a in new_aux)
        (grads,) = vjp_fn((heads, zeros_aux))
        new_params, new_mom = [], []
        for w, g, m in zip(params, grads, mom):
            m2 = momentum * m - lr * g.astype(w.dtype)
            new_params.append(w + m2)
            new_mom.append(m2)
        return tuple(new_params), tuple(new_mom), new_aux, outs

    mom0 = tuple(jnp.zeros_like(p) for p in params0)
    flops = _flops_of(
        jax.jit(sgd_step).lower(params0, mom0, aux0, other_vals).compile())

    @jax.jit
    def loop(n, params, mom, aux, other):
        def body(i, carry):
            params, mom, aux, acc = carry
            params, mom, aux, outs = sgd_step(params, mom, aux, other)
            return (params, mom, aux,
                    acc + jnp.mean(outs[0].astype(jnp.float32)))

        _, _, _, acc = jax.lax.fori_loop(
            0, n, body, (params, mom, aux, jnp.float32(0.0)))
        return acc

    def runner(n, p, m, a, o):
        return float(loop(n, p, m, a, o))

    sec = _timed(runner, params0, mom0, aux0, other_vals)
    report(tag, sec, flops)


def conv_micro():
    """NCHW vs NHWC fwd+bwd on representative ResNet-50 convs."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    shapes = [  # (N, C_in, H, W, C_out, k, stride)
        (32, 3, 224, 224, 64, 7, 2),
        (32, 64, 56, 56, 64, 3, 1),
        (32, 128, 28, 28, 128, 3, 1),
        (32, 256, 14, 14, 256, 3, 1),
        (32, 512, 7, 7, 512, 3, 1),
        (32, 256, 56, 56, 64, 1, 1),
        (32, 2048, 7, 7, 512, 1, 1),
    ]
    rng = np.random.RandomState(0)
    for dtype in (jnp.float32, jnp.bfloat16):
        for (n, ci, h, w, co, k, s) in shapes:
            pad = k // 2
            x_nchw = jnp.asarray(
                rng.normal(0, 1, (n, ci, h, w)).astype(np.float32), dtype)
            w_oihw = jnp.asarray(
                rng.normal(0, 0.05, (co, ci, k, k)).astype(np.float32), dtype)
            x_nhwc = jnp.transpose(x_nchw, (0, 2, 3, 1))
            w_hwio = jnp.transpose(w_oihw, (2, 3, 1, 0))

            def mk(dn):
                def f(x, wt):
                    def loss(x, wt):
                        o = lax.conv_general_dilated(
                            x, wt, (s, s), [(pad, pad)] * 2,
                            dimension_numbers=dn,
                            preferred_element_type=jnp.float32)
                        return jnp.sum(o * o.astype(jnp.float32)) * 1e-6
                    l, grads = jax.value_and_grad(loss, argnums=(0, 1))(x, wt)
                    return l, grads
                return f

            for tag, dn, xv, wv in (
                    ("NCHW", ("NCHW", "OIHW", "NCHW"), x_nchw, w_oihw),
                    ("NHWC", ("NHWC", "HWIO", "NHWC"), x_nhwc, w_hwio)):
                f = mk(dn)
                flops = _flops_of(jax.jit(f).lower(xv, wv).compile())

                @jax.jit
                def loop(nn, x, wt):
                    def body(i, carry):
                        x, wt, acc = carry
                        l, (gx, gw) = f(x, wt)
                        return (x + gx.astype(x.dtype) * 0,
                                wt - gw.astype(wt.dtype) * 1e-7, acc + l)
                    x, wt, acc = jax.lax.fori_loop(
                        0, nn, body, (x, wt, jnp.float32(0.0)))
                    return acc

                def runner(nn, x, wt):
                    return float(loop(nn, x, wt))

                sec = _timed(runner, xv, wv, reps=3)
                tf = flops / sec / 1e12
                print(f"  conv {ci:4d}x{h:3d} k{k} s{s} -> {co:4d} "
                      f"{str(np.dtype(dtype)) if dtype == jnp.float32 else 'bf16':>8} "
                      f"{tag}: {sec*1e3:7.2f} ms  {tf:7.2f} TF/s", flush=True)


def raw_resnet(layout="NCHW", dtype_name="bf16", batch=BATCH):
    """Upper-bound probe: hand-written JAX ResNet-50 (bottleneck v1) full
    train step, chosen layout and compute dtype, f32 master weights +
    momentum.  What XLA gives an ideal framework on this chip."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    cdt = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    nhwc = layout == "NHWC"
    dn = ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW")
    caxis = 3 if nhwc else 1
    rng = np.random.RandomState(0)
    params = {}
    bn_stats = {}

    def conv_p(name, ci, co, k):
        w = rng.normal(0, 0.05, (k, k, ci, co) if nhwc
                       else (co, ci, k, k)).astype(np.float32)
        params[name + "_w"] = jnp.asarray(w)

    def bn_p(name, c):
        params[name + "_g"] = jnp.ones((c,), np.float32)
        params[name + "_b"] = jnp.zeros((c,), np.float32)
        bn_stats[name + "_mm"] = jnp.zeros((c,), np.float32)
        bn_stats[name + "_mv"] = jnp.ones((c,), np.float32)

    stages = [(3, 64, 256), (4, 128, 512), (6, 256, 1024), (3, 512, 2048)]
    conv_p("c0", 3, 64, 7)
    bn_p("bn0", 64)
    ci = 64
    for si, (nblk, mid, out) in enumerate(stages):
        for bi in range(nblk):
            p = f"s{si}b{bi}"
            conv_p(p + "a", ci, mid, 1); bn_p(p + "a", mid)
            conv_p(p + "b", mid, mid, 3); bn_p(p + "b", mid)
            conv_p(p + "c", mid, out, 1); bn_p(p + "c", out)
            if bi == 0:
                conv_p(p + "d", ci, out, 1); bn_p(p + "d", out)
            ci = out
    params["fc_w"] = jnp.asarray(
        rng.normal(0, 0.01, (2048, 1000)).astype(np.float32))
    params["fc_b"] = jnp.zeros((1000,), np.float32)

    def bn(x, p, st, name):
        red = tuple(i for i in range(4) if i != caxis)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        var = jnp.var(x32, axis=red)
        sh = tuple(-1 if i == caxis else 1 for i in range(4))
        out = (x32 - mean.reshape(sh)) * lax.rsqrt(var + 1e-5).reshape(sh)
        out = out.astype(cdt) * p[name + "_g"].astype(cdt).reshape(sh) \
            + p[name + "_b"].astype(cdt).reshape(sh)
        new = {name + "_mm": st[name + "_mm"] * 0.9 + mean * 0.1,
               name + "_mv": st[name + "_mv"] * 0.9 + var * 0.1}
        return out, new

    def conv(x, p, name, stride=1, k=1):
        w = p[name + "_w"].astype(cdt)
        pad = k // 2
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad)] * 2, dimension_numbers=dn)

    def net(p, st, x, labels):
        new_st = {}
        x = conv(x, p, "c0", 2, 7)
        x, u = bn(x, p, st, "bn0"); new_st.update(u)
        x = jnp.maximum(x, 0)
        window = (1, 3, 3, 1) if nhwc else (1, 1, 3, 3)
        strides = (1, 2, 2, 1) if nhwc else (1, 1, 2, 2)
        pads = ((0, 0), (1, 1), (1, 1), (0, 0)) if nhwc \
            else ((0, 0), (0, 0), (1, 1), (1, 1))
        x = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        for si, (nblk, mid, out) in enumerate(stages):
            for bi in range(nblk):
                pre = f"s{si}b{bi}"
                stride = 2 if (bi == 0 and si > 0) else 1
                sc = x
                y = conv(x, p, pre + "a", stride, 1)
                y, u = bn(y, p, st, pre + "a"); new_st.update(u)
                y = jnp.maximum(y, 0)
                y = conv(y, p, pre + "b", 1, 3)
                y, u = bn(y, p, st, pre + "b"); new_st.update(u)
                y = jnp.maximum(y, 0)
                y = conv(y, p, pre + "c", 1, 1)
                y, u = bn(y, p, st, pre + "c"); new_st.update(u)
                if bi == 0:
                    sc = conv(x, p, pre + "d", stride, 1)
                    sc, u = bn(sc, p, st, pre + "d"); new_st.update(u)
                x = jnp.maximum(y + sc, 0)
        x = jnp.mean(x.astype(jnp.float32),
                     axis=(1, 2) if nhwc else (2, 3))
        logits = x @ p["fc_w"] + p["fc_b"]
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        return loss, new_st

    def step(p, mom, st, x, labels):
        (loss, new_st), grads = jax.value_and_grad(
            net, has_aux=True)(p, st, x, labels)
        new_p, new_m = {}, {}
        for k in p:
            m2 = 0.9 * mom[k] - 0.01 * grads[k].astype(jnp.float32)
            new_p[k] = p[k] + m2
            new_m[k] = m2
        return new_p, new_m, new_st, loss

    x0 = jnp.asarray(rng.uniform(0, 1, (batch, 224, 224, 3) if nhwc
                                 else (batch, 3, 224, 224))
                     .astype(np.float32), cdt)
    lab = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    mom0 = {k: jnp.zeros_like(v) for k, v in params.items()}
    flops = _flops_of(
        jax.jit(step).lower(params, mom0, bn_stats, x0, lab).compile())

    @jax.jit
    def loop(n, p, mom, st, x, labels):
        def body(i, carry):
            p, mom, st, acc = carry
            p, mom, st, loss = step(p, mom, st, x, labels)
            return (p, mom, st, acc + loss)
        _, _, _, acc = jax.lax.fori_loop(
            0, n, body, (p, mom, st, jnp.float32(0.0)))
        return acc

    def runner(n, *a):
        return float(loop(n, *a))

    sec = _timed(runner, params, mom0, bn_stats, x0, lab)
    tf = flops / sec / 1e12
    print(f"raw_{layout}_{dtype_name}_b{batch}: {sec*1e3:8.2f} ms/iter  "
          f"{batch/sec:9.1f} img/s  {tf:7.2f} TF/s  mfu={tf/197.0:.3f}",
          flush=True)


def main():
    import jax
    which = set(sys.argv[1:]) or {"infer", "fwd_train", "train_f32",
                                  "train_bf16"}
    print("backend:", jax.default_backend(),
          jax.devices()[0].device_kind, flush=True)
    if which & {"infer", "fwd_train", "train_f32", "train_bf16",
                "fwd_bf16", "train_bf16_remat", "train_f32_remat"}:
        exe = build()
        if "infer" in which:
            run_fwd(exe, False, "infer")
        if "fwd_train" in which:
            run_fwd(exe, True, "fwd_train")
        if "fwd_bf16" in which:
            import jax.numpy as jnp
            run_fwd(exe, True, "fwd_bf16", cast=jnp.bfloat16)
        if "train_f32" in which:
            run_train(exe, "train_f32")
        if "train_bf16" in which:
            import jax.numpy as jnp
            run_train(exe, "train_bf16", compute_dtype=jnp.bfloat16)
        if "train_bf16_remat" in which:
            import jax.numpy as jnp
            run_train(exe, "train_bf16_remat", compute_dtype=jnp.bfloat16,
                      remat=_conv_saveable)
        if "train_f32_remat" in which:
            run_train(exe, "train_f32_remat", remat=_conv_saveable)
    if "conv" in which:
        conv_micro()
    for spec in sorted(which):
        if spec.startswith("raw_"):
            parts = spec.split("_")  # raw_LAYOUT_DTYPE[_BATCH]
            raw_resnet(parts[1], parts[2],
                       int(parts[3]) if len(parts) > 3 else BATCH)


if __name__ == "__main__":
    main()

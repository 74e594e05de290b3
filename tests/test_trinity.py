"""Trinity (``model_type: afmoe``) at toy size on the CPU against its plain
reference (``benchmark/references/trinity.py``, which imports nothing of the
program): the sigmoid router of ``moe_experts`` against a plain loop over
experts, the chip's share of an expert layer, the whole model's logits, loss
and every gradient leaf over a dense, a window and a full layer, the
executor's recomputation, and ``Module.fit`` through the fused step against
the reference's Adam steps with the counters it feeds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.executor import _Program
from mxnet_tpu.observability import telemetry
from mxnet_tpu.ops import lm_ops

from benchmark.references import trinity as ref

CFG = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=3, num_dense_layers=1,
    layer_types=["sliding_attention", "sliding_attention", "full_attention"],
    sliding_window=16, intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=10000, rms_norm_eps=1e-5,
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=3, route_norm=True, route_scale=2.826,
    score_func="sigmoid", moe_intermediate_size=16, num_shared_experts=1,
    mup_enabled=True)
BATCH, SEQ = 2, 40          # 40 tokens: two and a half windows
PLAIN = (lambda a: a, lambda a: a)


def _normal(seed, shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).normal(0, scale, shape),
                       jnp.float32)


def _params(cfg, seed=0, scale=0.3):
    return {n: _normal(seed + i, s, scale)
            for i, (n, s) in enumerate(sorted(ref.param_shapes(cfg).items()))}


def _tokens(seed=0, cfg=CFG, batch=BATCH, seq=SEQ):
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (batch, seq + 1))
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


# -- the router's two scoring functions --------------------------------------------

def _moe_params(cfg, seed=40):
    return {k[len("layer1_"):]: v for k, v in _params(cfg, seed).items()
            if k.startswith(("layer1_moe_", "layer1_shared_"))}


def _routed(x, p, cfg, **changed):
    kw = dict(num_experts=cfg["router_num_experts"],
              num_hidden=cfg["moe_intermediate_size"],
              experts_held=cfg["num_experts"],
              first_expert=cfg["first_expert"],
              top_k=cfg["num_experts_per_tok"], norm_topk_prob=True,
              score_func="sigmoid", route_scale=cfg["route_scale"],
              use_expert_bias=True)
    kw.update(changed)
    rest = (p["moe_expert_bias"],) if kw["use_expert_bias"] else ()
    return lm_ops._moe_experts(
        x, p["moe_router_weight"], p["moe_gate_weight"], p["moe_up_weight"],
        p["moe_down_weight"], *rest, **kw)


def _loop_over_experts(x, p, cfg, norm=True):
    """The sigmoid-routed layer's held part, one expert at a time."""
    x, w = np.asarray(x, np.float64), {k: np.asarray(v, np.float64)
                                       for k, v in p.items()}
    scores = 1.0 / (1.0 + np.exp(-(x @ w["moe_router_weight"].T)))
    out = np.zeros_like(x)
    for t in range(len(x)):
        chosen = np.argsort(-(scores[t] + w["moe_expert_bias"]),
                            kind="stable")[:cfg["num_experts_per_tok"]]
        total = scores[t, chosen].sum() + 1e-20 if norm else 1.0
        for e in chosen:
            local = e - cfg["first_expert"]
            if 0 <= local < cfg["num_experts"]:
                gate = x[t] @ w["moe_gate_weight"][local]
                mid = gate / (1.0 + np.exp(-gate)) \
                    * (x[t] @ w["moe_up_weight"][local])
                out[t] += cfg["route_scale"] * scores[t, e] / total \
                    * (mid @ w["moe_down_weight"][local])
    return out


@pytest.mark.parametrize("norm", [True, False])
def test_sigmoid_router_is_a_plain_loop_over_experts(norm):
    p, x = _moe_params(CFG), _normal(41, (24, 32))
    p["moe_expert_bias"] = _normal(42, (16,), 0.2)
    y, counts = _routed(x, p, CFG, norm_topk_prob=norm)
    _close(y, _loop_over_experts(x, p, CFG, norm), 1e-4)
    assert counts.shape == (16,) and float(counts.sum()) == 24 * 3
    # the reference's layer (bias at its zeros), the shared expert taken out
    p["moe_expert_bias"] = jnp.zeros((16,))
    want = ref.moe(x[None], dict(p, shared_down_proj_weight=jnp.zeros_like(
        p["shared_down_proj_weight"])), CFG, PLAIN)[0]
    _close(_routed(x, p, CFG)[0], want, 1e-4)


def test_the_expert_bias_chooses_and_weighs_nothing():
    p, x = _moe_params(CFG), _normal(43, (24, 32))
    p["moe_expert_bias"] = jnp.zeros((16,)).at[5].set(10.0)   # held: 4..7
    y, counts = _routed(x, p, CFG)
    assert float(counts[5]) == 24                  # every token takes it
    # ... at its own score: the weights of the three chosen still add up to
    # route_scale, the 10 is nowhere in them
    weights, top_e = ref.routed_weights(x, p, CFG)
    assert bool(jnp.all(jnp.any(top_e == 5, axis=-1)))
    assert float(weights.sum(1).max()) <= CFG["route_scale"] + 1e-5
    _close(y, _loop_over_experts(x, p, CFG), 1e-4)
    grads = jax.grad(lambda b, r: jnp.sum(_routed(x, dict(
        p, moe_expert_bias=b, moe_router_weight=r), CFG)[0] ** 2),
        argnums=(0, 1))(p["moe_expert_bias"], p["moe_router_weight"])
    assert not np.any(np.asarray(grads[0]))
    assert np.any(np.asarray(grads[1]))


def _softmax_experts_before(data, router_weight, gate_weight, up_weight,
                            down_weight, num_experts, held, first_expert, k):
    """``moe_experts`` as it stood before it knew a second scoring function
    or a capacity (PR 27's body, norm_topk_prob on: every array at tokens x
    top-k rows), kept here as the oracle."""
    from jax import lax
    f32 = jnp.float32
    n, h = data.shape
    logits = jnp.matmul(data.astype(f32), router_weight.astype(f32).T)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = lax.stop_gradient(top_e).reshape(-1)
    counts = jnp.zeros((num_experts,), f32).at[chosen].add(1.0)
    local = chosen - first_expert
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    live = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
    mine_only = lambda x: jnp.where(live, x, jnp.zeros((), x.dtype))
    rows = mine_only(data[order // k])
    mid = mine_only(lm_ops._swiglu(lax.ragged_dot(rows, gate_weight, sizes),
                                   lax.ragged_dot(rows, up_weight, sizes)))
    weight = jnp.where(mine, top_p.reshape(-1), 0.0)[order]
    out = mine_only(lax.ragged_dot(mid, down_weight, sizes).astype(f32)
                    * weight[:, None]).astype(data.dtype)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype))
    y = jnp.sum(out[back].reshape(n, k, h).astype(f32), axis=1)
    return y.astype(data.dtype), lax.stop_gradient(counts)


def test_softmax_routing_is_what_it_was():
    """The defaults are the softmax router, and the routing is what it was
    before the op knew another or worked in rounds of its capacity: the same
    counts to the bit; the same results and gradients to rounding (a token's
    choices are now added in the order of their experts, in float32)."""
    p, x = _moe_params(CFG), _normal(44, (24, 32))
    names = ("moe_router_weight", "moe_gate_weight", "moe_up_weight",
             "moe_down_weight")
    weights = [p[n] for n in names]
    now = lambda x, *w: lm_ops._moe_experts(
        x, *w, num_experts=16, num_hidden=16, experts_held=4, first_expert=4,
        top_k=3)
    before = lambda x, *w: _softmax_experts_before(x, *w, 16, 4, 4, 3)
    assert np.array_equal(np.asarray(now(x, *weights)[1]),
                          np.asarray(before(x, *weights)[1]))
    both = lambda fn: jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a)[0] ** 2), argnums=range(5))(x, *weights)
    for a, b in zip(jax.tree_util.tree_leaves((now(x, *weights), both(now))),
                    jax.tree_util.tree_leaves((before(x, *weights),
                                               both(before)))):
        _close(a, b, 1e-6)
    node = mx.sym.moe_experts(mx.sym.Variable("x"), num_experts=16,
                              num_hidden=16, experts_held=4, top_k=3)
    assert node.list_arguments()[-1].endswith("down_weight")   # five inputs
    with pytest.raises(ValueError, match="score_func"):
        _routed(x, p, CFG, score_func="tanh")


def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 16 experts each of a 128-expert top-8 sigmoid layer: the
    routed parts summed, the shared expert counted once, are the uncut
    reference's layer output."""
    cut = dict(CFG, router_num_experts=128, num_experts=16,
               num_experts_per_tok=8)
    whole = dict(cut, num_experts=128, first_expert=0)
    p, x = _moe_params(whole, seed=50), _normal(51, (2, 13, 32))
    p["moe_expert_bias"] = jnp.zeros((128,))
    want = ref.moe(x, p, whole, PLAIN)
    flat = x.reshape(-1, 32)
    routed, held = 0.0, 0.0
    for share in range(8):
        lo = 16 * share
        mine = dict(p, **{k: p[k][lo:lo + 16] for k in (
            "moe_gate_weight", "moe_up_weight", "moe_down_weight")})
        part, counts = _routed(flat, mine, dict(cut, first_expert=lo))
        routed = routed + part
        held += float(counts[lo:lo + 16].sum())
        assert float(counts.sum()) == 26 * 8        # every share routes all
    shared = ref._ffn(flat, p["shared_gate_proj_weight"].T,
                      p["shared_up_proj_weight"].T,
                      p["shared_down_proj_weight"].T, PLAIN)
    _close((routed + shared).reshape(x.shape), want, 1e-4)
    assert held == 26 * 8                           # each choice held once


# -- each kind of layer, and the whole model ---------------------------------------------

@pytest.mark.parametrize("kind", [ref.WINDOW, ref.FULL])
def test_gated_attention_is_the_reference_layer(kind):
    p = {k[len("layer0_"):]: v for k, v in _params(CFG).items()
         if k.startswith("layer0_attn_")}
    x = _normal(31, (BATCH, SEQ, 32))
    net = models.trinity._Builder(CFG, "float32").attention(
        mx.sym.Variable("x"), "attn_", kind)
    got = _Program(net).evaluate(dict(p, x=x), {}, (), True)[0][0]
    _close(got, ref.gated_attention(x, p, CFG, kind, PLAIN), 1e-4)
    other = ref.FULL if kind == ref.WINDOW else ref.WINDOW
    assert not np.allclose(got, ref.gated_attention(x, p, CFG, other, PLAIN),
                           atol=1e-3)


def test_layer_kinds_follow_the_published_pattern():
    published = ["sliding_attention"] * 3 + ["full_attention"]
    cfg = dict(CFG, layer_types=published * 8, num_hidden_layers=5,
               layers_kept=[1, 4, 5, 6, 7])
    assert models.trinity.layer_kinds(cfg) == ref.layer_kinds(cfg) \
        == ["sliding_attention"] * 4 + ["full_attention"]
    assert models.trinity.layer_kinds(dict(cfg, layers_kept=None)) \
        == published + ["sliding_attention"]
    with pytest.raises(ValueError, match="layers"):
        models.trinity.layer_kinds(dict(cfg, layers_kept=[1, 4]))


def _evaluate(net, params, x, y):
    prog = _Program(net)

    def f(p):
        outs, _ = prog.evaluate(dict(p, data=jnp.asarray(x),
                                     softmax_label=jnp.asarray(y)),
                                {}, (), True)
        return jnp.mean(outs[0]), outs[1]
    # MakeLoss hands every sequence's loss a gradient of one whatever is
    # made of it afterwards: the sum's gradient, BATCH times the mean's
    out, grads = jax.value_and_grad(f, has_aux=True)(params)
    return prog, (out, {n: g / BATCH for n, g in grads.items()})


def test_logits_loss_and_every_gradient_leaf():
    """One dense + one window + one full layer."""
    params, (x, y) = _params(CFG), _tokens()
    params = {n: jnp.zeros_like(a) if n.endswith("expert_bias") else a
              for n, a in params.items()}
    net = models.trinity.get_symbol(CFG)
    assert sorted(n for n in net.list_arguments()
                  if n not in ("data", "softmax_label")) == sorted(params)
    logits = net.get_internals()["lm_head_output"]
    got = _Program(logits).evaluate(dict(params, data=jnp.asarray(x)), {}, (),
                                    False)[0][0]
    _close(got, ref.logits(params, x, CFG), 1e-4)
    prog, ((loss, counts), grads) = _evaluate(net, params, x, y)
    want_loss, want = jax.value_and_grad(ref.loss_fn)(params, x, y, CFG)
    _close(loss, want_loss, 1e-5)
    # the dense block has no counts row
    assert counts.shape == (2, 16) and float(counts.sum()) == 2 * 80 * 3
    for name in sorted(params):
        _close(grads[name], want[name], 3e-4), name
    assert not np.any(np.asarray(grads["layer1_moe_expert_bias"]))
    # every mistake the reference can plant moves the loss
    for fault in ref.FAULTS:
        wrong = float(ref.loss_fn(params, x, y, CFG, fault=fault))
        assert abs(wrong - float(want_loss)) > 1e-4, fault


def test_mirroring_recomputes_and_changes_no_gradient():
    params, (x, y) = _params(CFG, seed=60), _tokens(1)
    on, (out_on, g_on) = _evaluate(models.trinity.get_symbol(CFG), params, x,
                                   y)
    off, (out_off, g_off) = _evaluate(
        models.trinity.get_symbol(CFG, recompute=False), params, x, y)
    assert on.mirror_stages == 6 and off.mirror_stages == 0
    _close(out_on[0], out_off[0], 1e-6)
    for name in sorted(params):
        _close(g_on[name], g_off[name], 1e-5), name


def test_the_dense_mlp_is_named_for_the_trace():
    params, (x, y) = _params(CFG, seed=61), _tokens(2)
    prog = _Program(models.trinity.get_symbol(CFG, recompute=False))
    text = jax.jit(lambda p: prog.evaluate(dict(
        p, data=jnp.asarray(x), softmax_label=jnp.asarray(y)), {}, (),
        True)[0][0]).lower(params).as_text(debug_info=True)
    for scope in ("mx:mlp", "mx:moe", "mx:attn/mx:attn:window",
                  "mx:attn/mx:attn:full"):
        assert scope in text, scope


# -- Module.fit -------------------------------------------------------------------------

def test_fit_trains_through_the_fused_step_like_three_adam_steps():
    params = _params(CFG, seed=70, scale=0.2)
    params = {n: jnp.zeros_like(a) if n.endswith("expert_bias") else a
              for n, a in params.items()}
    xs, ys = _tokens(2, batch=3 * BATCH)
    # an epsilon of the gradients' own size: the update then follows the
    # gradient smoothly, where 1e-8 would make it a sign
    opt = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, epsilon=1e-3, wd=0.0)
    telemetry.reset()
    mod = mx.mod.Module(models.trinity.get_symbol(CFG), context=mx.cpu())
    losses = []
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam", optimizer_params=opt,
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()},
            batch_end_callback=lambda p: losses.append(
                float(mod.get_outputs()[0].asnumpy().mean())))
    assert mod._fused_step is not None and mod._fused_step.ran
    assert len(mod.get_outputs()) == 2 and len(losses) == 3
    snap = telemetry.snapshot()
    assert snap["module.recompute.blocks"]["value"] == 3 * 6
    assert snap["module.moe.selections_total"]["value"] == 3 * 2 * 80 * 3
    held = snap["module.moe.selections_held"]["value"]
    assert 0.15 < held / (3 * 2 * 80 * 3) < 0.35        # 4 of 16 experts
    # three attention nodes a step: on the CPU the XLA reference computes
    # all 40 x 40 scores of each, forward and backward; the window of 16
    # lets 136 + 24 x 16 through, the full layer 820
    assert snap["module.attn.pairs_computed"]["value"] \
        == 3 * BATCH * 3 * 2 * 40 * 40
    assert snap["module.attn.pairs_visible"]["value"] \
        == 3 * BATCH * 2 * (2 * 520 + 820)

    p, m = dict(params), {n: jnp.zeros_like(a) for n, a in params.items()}
    v = dict(m)
    want = []
    for t in range(3):
        lo = t * BATCH
        loss, _, p, m, v = ref.adam_step(p, m, v, t + 1.0, xs[lo:lo + BATCH],
                                         ys[lo:lo + BATCH], CFG, opt)
        want.append(float(loss))
    _close(losses[:1], want[:1], 1e-5)
    _close(losses, want, 1e-4)
    got = dict(zip(mod._fused_step.param_names, mod._fused_step._masters))
    for name in sorted(params):
        moved = np.asarray(p[name] - params[name], np.float64)
        gap = np.asarray(got[name] - params[name], np.float64) - moved
        assert np.linalg.norm(gap) <= 0.05 * np.linalg.norm(moved) + 1e-12, \
            name


def test_a_model_without_attention_counts_no_pairs():
    telemetry.reset()
    x = np.random.RandomState(0).normal(0, 1, (8, 6)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.float32)
    mod = mx.mod.Module(models.mlp.get_symbol(num_classes=2), context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(x, y, batch_size=4), num_epoch=1)
    assert mod._fused_step.ran \
        and mod._fused_step._schedule_counts() == ((0, 0), (0, 0, 0))
    assert "module.attn.pairs_computed" not in telemetry.snapshot()
    assert "module.gdn.chunk_steps" not in telemetry.snapshot()

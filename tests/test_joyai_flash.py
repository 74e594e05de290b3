"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``) at toy size on the CPU
against its plain reference (``benchmark/references/joyai_flash.py``, which
imports nothing of the program): interleaved rotary against the pairwise
formula, the latent attention layer, the chip's share of the expert layer,
the whole model's two losses and every gradient leaf (the embedding's and the
head's the sum of their two uses), the executor's recomputation, and
``Module.fit`` through the fused step against the reference's Adam steps with
the counters it feeds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.executor import _Program
from mxnet_tpu.observability import telemetry
from mxnet_tpu.ops import lm_ops

from benchmark.references import joyai_flash as ref

CFG = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=2,
    first_k_dense_replace=1, intermediate_size=48, num_attention_heads=4,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=12, rope_theta=32000000, rope_interleave=True,
    rms_norm_eps=1e-6, n_routed_experts=4, router_num_experts=16,
    first_expert=4, num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, scoring_func="sigmoid",
    moe_intermediate_size=16, n_shared_experts=1,
    num_nextn_predict_layers=1, mtp_loss_weight=0.3)
BATCH, SEQ = 2, 24
PLAIN = (lambda a: a, lambda a: a)


def _normal(seed, shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).normal(0, scale, shape),
                       jnp.float32)


def _params(cfg, seed=0, scale=0.3):
    out = {n: _normal(seed + i, s, scale)
           for i, (n, s) in enumerate(sorted(ref.param_shapes(cfg).items()))}
    return {n: jnp.zeros_like(a) if n.endswith("expert_bias") else a
            for n, a in out.items()}


def _tokens(seed=0, cfg=CFG, batch=BATCH, seq=SEQ):
    ids = np.random.RandomState(seed).randint(0, cfg["vocab_size"],
                                              (batch, seq + 1))
    return ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


# -- rotary positions on neighbouring pairs ---------------------------------------

@pytest.mark.parametrize("offset,width", [(0, 8), (16, 8), (4, 6)])
def test_interleaved_rotary_is_the_pairwise_formula(offset, width):
    x = np.asarray(_normal(3, (2, 7, 3, offset + width + 2)), np.float64)
    got = lm_ops._rotary_embedding(jnp.asarray(x, jnp.float32),
                                   rotary_dim=width, base=32e6,
                                   interleaved=True, offset=offset)
    want = x.copy()
    for pos in range(7):
        for i in range(width // 2):
            ang = pos * 32e6 ** (-2.0 * i / width)
            a, b = x[:, pos, :, offset + 2 * i], x[:, pos, :, offset + 2 * i + 1]
            want[:, pos, :, offset + 2 * i] = a * np.cos(ang) - b * np.sin(ang)
            want[:, pos, :, offset + 2 * i + 1] = b * np.cos(ang) \
                + a * np.sin(ang)
    _close(got, want, 1e-5)
    # ... and the reference's own, where it turns the whole width
    if not offset:
        whole = jnp.asarray(x[..., :width], jnp.float32)
        _close(lm_ops._rotary_embedding(whole, base=32e6, interleaved=True),
               ref.rotary_pairs(whole, 32e6), 1e-5)


def test_rotate_half_is_what_it_was_and_takes_an_offset():
    x = _normal(4, (1, 5, 2, 12))
    plain = lm_ops._rotary_embedding(x, rotary_dim=8)
    _close(plain[..., 8:], x[..., 8:], 0)
    moved = lm_ops._rotary_embedding(
        jnp.concatenate([x[..., 8:], x[..., :8]], -1), rotary_dim=8, offset=4)
    _close(moved[..., 4:], plain[..., :8], 1e-6)
    assert not np.allclose(
        plain, lm_ops._rotary_embedding(x, rotary_dim=8, interleaved=True))


# -- the latent attention layer -------------------------------------------------------

def _attn_params(cfg, seed=20):
    return {k[len("layer0_"):]: v for k, v in _params(cfg, seed).items()
            if k.startswith("layer0_attn_")}


def test_latent_attention_is_the_reference_layer():
    p, x = _attn_params(CFG), _normal(31, (BATCH, SEQ, 32))
    net = models.joyai_flash._Builder(CFG, "float32").attention(
        mx.sym.Variable("x"), "attn_")
    got = _Program(net).evaluate(dict(p, x=x), {}, (), True)[0][0]
    assert got.shape == (BATCH, SEQ, 32)
    _close(got, ref.latent_attention(x, p, CFG, PLAIN), 1e-4)
    for fault in ("rotary_off_shared_key", "scale_by_value_width",
                  "latent_norm_left_out"):
        assert not np.allclose(
            got, ref.latent_attention(x, p, CFG, PLAIN, fault), atol=1e-3), \
            fault


def test_attention_node_takes_its_output_width_from_v():
    q, k, v, ks = (mx.sym.Variable(n) for n in ("q", "k", "v", "ks"))
    node = mx.sym.scaled_dot_product_attention(
        q, k, v, key_shared=ks, causal=True, use_shared_key=True)
    _, out, _ = node.infer_shape(q=(2, 9, 4, 24), k=(2, 9, 2, 16),
                                 v=(2, 9, 2, 12), ks=(2, 9, 8))
    assert out == [(2, 9, 4, 12)]
    plain = mx.sym.scaled_dot_product_attention(q, k, v)
    _, out, _ = plain.infer_shape(q=(2, 9, 4, 16), k=(2, 9, 2, 16),
                                  v=(2, 9, 2, 12))
    assert out == [(2, 9, 4, 12)]
    # v is no longer healed from k: its shape has to be given
    args, out, _ = plain.infer_shape_partial(q=(2, 9, 4, 16), k=(2, 9, 2, 16))
    assert args[2] is None and out == [None]


# -- the chip's share of the expert layer ------------------------------------------------

def test_sixteen_shares_add_up_to_the_whole_layer():
    """16 shares of 4 experts each of a 64-expert layer, the shared expert
    counted once, against the reference told it holds all 64."""
    whole = dict(CFG, n_routed_experts=64, router_num_experts=64,
                 first_expert=0, num_experts_per_tok=8)
    p = {k[len("layer1_"):]: v for k, v in _params(whole, 40).items()
         if k.startswith(("layer1_moe_", "layer1_shared_"))}
    x = _normal(41, (1, 26, 32))
    want = ref.moe(x, p, whole, PLAIN)
    flat = x.reshape(-1, 32)
    routed, held = 0.0, 0.0
    for share in range(16):
        lo = 4 * share
        mine = [p[k][lo:lo + 4] for k in ("moe_gate_weight", "moe_up_weight",
                                          "moe_down_weight")]
        part, counts = lm_ops._moe_experts(
            flat, p["moe_router_weight"], *mine, p["moe_expert_bias"],
            num_experts=64, num_hidden=16, experts_held=4, first_expert=lo,
            top_k=8, norm_topk_prob=True, score_func="sigmoid",
            route_scale=2.5, use_expert_bias=True)
        routed = routed + part
        held += float(counts[lo:lo + 4].sum())
        assert float(counts.sum()) == 26 * 8        # every share routes all
    shared = ref._ffn(flat, p["shared_gate_proj_weight"].T,
                      p["shared_up_proj_weight"].T,
                      p["shared_down_proj_weight"].T, PLAIN)
    _close((routed + shared).reshape(x.shape), want, 1e-4)
    assert held == 26 * 8                           # each choice held once


# -- the whole model -------------------------------------------------------------------

def _evaluate(net, params, x, y, output=0):
    prog = _Program(net)

    def f(p):
        outs, _ = prog.evaluate(dict(p, data=jnp.asarray(x),
                                     softmax_label=jnp.asarray(y)),
                                {}, (), True)
        return jnp.mean(outs[output]), outs[1:]
    # MakeLoss hands every sequence's loss a gradient of one whatever is
    # made of it afterwards: the sum's gradient, BATCH times the mean's
    out, grads = jax.value_and_grad(f, has_aux=True)(params)
    return prog, (out, {n: g / BATCH for n, g in grads.items()})


def test_both_losses_and_every_gradient_leaf():
    params, (x, y) = _params(CFG), _tokens()
    net = models.joyai_flash.get_symbol(CFG)
    args = [n for n in net.list_arguments()
            if n not in ("data", "softmax_label")]
    # one embedding, one head, one label: each a single argument
    assert sorted(args) == sorted(params) and len(set(args)) == len(args)
    assert net.list_arguments().count("softmax_label") == 1
    logits = net.get_internals()["lm_head_output"]
    got = _Program(logits).evaluate(dict(params, data=jnp.asarray(x)), {}, (),
                                    False)[0][0]
    _close(got, ref.logits(params, x, CFG), 1e-4)
    prog, ((loss, (counts, parts)), grads) = _evaluate(net, params, x, y)
    want_loss, want = jax.value_and_grad(ref.loss_fn)(params, x, y, CFG)
    l_main, l_mtp = ref.losses(params, x, y, CFG)
    _close(loss, want_loss, 1e-5)
    _close(jnp.mean(parts, axis=1), jnp.stack([l_main, l_mtp]), 1e-5)
    _close(loss, l_main + 0.3 * l_mtp, 1e-5)
    # layer 1's expert layer and the module's; the dense block has no row
    assert counts.shape == (2, 16) \
        and float(counts.sum()) == 2 * BATCH * SEQ * 3
    for name in sorted(params):
        _close(grads[name], want[name], 3e-4), name
    assert not np.any(np.asarray(grads["layer1_moe_expert_bias"]))
    # every mistake the reference can plant moves the loss or a gradient
    for fault in ref.FAULTS:
        wrong, g = jax.value_and_grad(ref.loss_fn)(params, x, y, CFG,
                                                   fault=fault)
        moved = abs(float(wrong) - float(want_loss)) > 1e-4 or not np.allclose(
            g["lm_head_weight"], want["lm_head_weight"], atol=1e-5)
        assert moved, fault


def test_shared_parameters_take_the_sum_of_both_uses():
    """The embedding and the head are each read by two nodes: the gradient
    the program hands out is the main model's use plus the module's."""
    params, (x, y) = _params(CFG, seed=50), _tokens(3)
    _, (_, grads) = _evaluate(models.joyai_flash.get_symbol(CFG), params, x, y)

    def part(name, which):
        """d loss / d ``name`` through one of its two uses alone: the other
        reads a copy that is held still."""
        def f(w):
            still = jax.lax.stop_gradient(params[name])
            main = dict(params, **{name: w if which == "main" else still})
            mtp = dict(params, **{name: w if which == "mtp" else still})
            h = ref.hidden(main if name == "embed_weight" else params, x, CFG,
                           PLAIN)
            every = jnp.ones(y.shape, bool)
            l_main = ref.head_loss(h, main["lm_head_weight"], y, every, PLAIN)
            if name == "embed_weight":
                # the main model's use reaches the module through h as well
                h_mtp = ref.mtp_hidden(mtp, h, y, CFG, PLAIN)
            else:
                h_mtp = ref.mtp_hidden(params, h, y, CFG, PLAIN)
            after = jnp.concatenate([y[:, 1:], jnp.zeros_like(y[:, :1])], 1)
            l_mtp = ref.head_loss(h_mtp, mtp["lm_head_weight"], after,
                                  every.at[:, -1].set(False), PLAIN)
            return l_main + 0.3 * l_mtp
        return jax.grad(f)(params[name])

    for name in ("embed_weight", "lm_head_weight"):
        main, mtp = part(name, "main"), part(name, "mtp")
        assert np.abs(np.asarray(mtp)).max() > 1e-4, name
        _close(grads[name], main + mtp, 3e-4)
        assert not np.allclose(grads[name], main, atol=1e-4), name


def test_a_model_without_the_module_has_one_loss():
    cfg = dict(CFG, num_nextn_predict_layers=0)
    params, (x, y) = _params(cfg, seed=55), _tokens(4)
    net = models.joyai_flash.get_symbol(cfg)
    assert len(net.list_outputs()) == 2
    assert not [n for n in net.list_arguments() if n.startswith("mtp_")]
    _, ((loss, _), _) = _evaluate(net, params, x, y)
    _close(loss, ref.loss_fn(params, x, y, cfg), 1e-5)
    with pytest.raises(ValueError, match="prediction modules"):
        models.joyai_flash.get_symbol(dict(CFG, num_nextn_predict_layers=2))


def test_mirroring_recomputes_and_changes_no_gradient():
    params, (x, y) = _params(CFG, seed=60), _tokens(1)
    on, (out_on, g_on) = _evaluate(models.joyai_flash.get_symbol(CFG), params,
                                   x, y)
    off, (out_off, g_off) = _evaluate(
        models.joyai_flash.get_symbol(CFG, recompute=False), params, x, y)
    # two blocks and the module's, each half a stage
    assert on.mirror_stages == 6 and off.mirror_stages == 0
    _close(out_on[0], out_off[0], 1e-6)
    for name in sorted(params):
        _close(g_on[name], g_off[name], 1e-5), name


def test_latents_and_the_module_are_named_for_the_trace():
    params, (x, y) = _params(CFG, seed=61), _tokens(2)
    prog = _Program(models.joyai_flash.get_symbol(CFG, recompute=False))
    text = jax.jit(lambda p: prog.evaluate(dict(
        p, data=jnp.asarray(x), softmax_label=jnp.asarray(y)), {}, (),
        True)[0][0]).lower(params).as_text(debug_info=True)
    for scope in ("mx:mla", "mx:mlp", "mx:moe", "mx:attn/mx:attn:full",
                  "mx:mtp", "mx:mtp/mx:mla"):
        assert scope in text, scope


# -- the second shift of the loss ---------------------------------------------------------

def test_cross_entropy_one_position_on():
    logits, label = _normal(5, (2, 6, 9)), np.random.RandomState(6).randint(
        0, 9, (2, 6)).astype(np.float32)
    got, grad = jax.value_and_grad(lambda z: jnp.sum(
        lm_ops._sequence_cross_entropy(z, jnp.asarray(label), shift=1)))(
            logits)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)

    def plain(z):
        logp = jax.nn.log_softmax(z[:, :-1], axis=-1)
        picked = jnp.take_along_axis(
            logp, jnp.asarray(label[:, 1:], jnp.int32)[..., None], -1)[..., 0]
        return jnp.sum(-jnp.mean(picked, axis=-1))
    want, want_grad = jax.value_and_grad(plain)(logits)
    _close(got, want, 1e-6)
    _close(grad, want_grad, 1e-6)
    assert not np.any(np.asarray(grad[:, -1]))      # the last has no target
    _close(lm_ops._sequence_cross_entropy(logits, jnp.asarray(label)),
           lm_ops._sequence_cross_entropy(logits, jnp.asarray(label),
                                          shift=0), 0)


# -- Module.fit -------------------------------------------------------------------------

def test_fit_trains_through_the_fused_step_like_three_adam_steps():
    params = _params(CFG, seed=70, scale=0.2)
    xs, ys = _tokens(2, batch=3 * BATCH)
    # an epsilon of the gradients' own size: the update then follows the
    # gradient smoothly, where 1e-8 would make it a sign
    opt = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, epsilon=1e-3, wd=0.0)
    telemetry.reset()
    mod = mx.mod.Module(models.joyai_flash.get_symbol(CFG), context=mx.cpu())
    losses = []
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam", optimizer_params=opt,
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()},
            batch_end_callback=lambda p: losses.append(
                float(mod.get_outputs()[0].asnumpy().mean())))
    assert mod._fused_step is not None and mod._fused_step.ran
    assert len(mod.get_outputs()) == 3 and len(losses) == 3
    snap = telemetry.snapshot()
    assert snap["module.recompute.blocks"]["value"] == 3 * 6
    assert snap["module.moe.selections_total"]["value"] \
        == 3 * 2 * BATCH * SEQ * 3
    held = snap["module.moe.selections_held"]["value"]
    assert 0.15 < held / (3 * 2 * BATCH * SEQ * 3) < 0.35   # 4 of 16 experts
    # three attention nodes a step: on the CPU the XLA reference computes
    # all 24 x 24 scores of each, forward and backward
    assert snap["module.attn.pairs_computed"]["value"] \
        == 3 * BATCH * 3 * 2 * SEQ * SEQ
    assert snap["module.attn.pairs_visible"]["value"] \
        == 3 * BATCH * 3 * 2 * (SEQ * (SEQ + 1) // 2)

    p, m = dict(params), {n: jnp.zeros_like(a) for n, a in params.items()}
    v = dict(m)
    want, parts = [], np.zeros(2)
    for t in range(3):
        lo = t * BATCH
        parts += np.asarray(ref.losses(p, xs[lo:lo + BATCH],
                                       ys[lo:lo + BATCH], CFG), np.float64)
        loss, _, p, m, v = ref.adam_step(p, m, v, t + 1.0, xs[lo:lo + BATCH],
                                         ys[lo:lo + BATCH], CFG, opt)
        want.append(float(loss))
    _close(losses[:1], want[:1], 1e-5)
    _close(losses, want, 1e-4)
    # the two parts, summed over the steps, and only the whole in the metric
    _close([snap["module.lm.loss_main"]["value"],
            snap["module.lm.loss_mtp"]["value"]], parts, 1e-4)
    got = dict(zip(mod._fused_step.param_names, mod._fused_step._masters))
    for name in sorted(params):
        moved = np.asarray(p[name] - params[name], np.float64)
        gap = np.asarray(got[name] - params[name], np.float64) - moved
        assert np.linalg.norm(gap) <= 0.05 * np.linalg.norm(moved) + 1e-12, \
            name

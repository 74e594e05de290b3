"""SDAR (``model_type: sdar_moe``) and its block-diffusion objective at toy
size on the CPU against the plain reference (``benchmark/references/sdar.py``,
which imports nothing of the program): the noising function, rotary positions
that restart at the clean half, the weighted cross-entropy, the chip's share
of a softmax-routed expert layer, the whole model's loss and every gradient
leaf, and ``Module.fit`` through the fused step against the reference's Adam
steps with the counters it feeds."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.executor import _Program
from mxnet_tpu.observability import telemetry
from mxnet_tpu.ops import lm_ops
from mxnet_tpu.ops import pallas_kernels as pk

from benchmark.references import sdar as ref

CFG = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=3, moe_intermediate_size=16, norm_topk_prob=True,
    block_length=4, decoder_sparse_step=1, mlp_only_layers=[])
BATCH, LENGTH = 2, 20           # 20 clean tokens: five blocks, 40 positions
MASK_ID = 49
PLAIN = (lambda a: a, lambda a: a)


def _normal(seed, shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).normal(0, scale, shape),
                       jnp.float32)


def _params(cfg=CFG, seed=0, scale=0.3):
    return {n: _normal(seed + i, s, scale)
            for i, (n, s) in enumerate(sorted(ref.param_shapes(cfg).items()))}


def _batch(seed=0, rows=BATCH, length=LENGTH):
    ids = np.random.RandomState(seed).randint(0, MASK_ID, (rows, length))
    return models.sdar.noise(ids, np.random.default_rng(seed), 4, MASK_ID,
                             1e-3)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


# -- the noise and the objective's parts --------------------------------------

def test_noise_is_made_from_the_seed_and_masks_about_t():
    ids = np.random.RandomState(1).randint(0, MASK_ID, (64, 512))
    data, weight = models.sdar.noise(ids, np.random.default_rng(7), 4,
                                     MASK_ID, 1e-3)
    again = models.sdar.noise(ids, np.random.default_rng(7), 4, MASK_ID, 1e-3)
    assert np.array_equal(data, again[0]) and np.array_equal(weight, again[1])
    assert data.shape == weight.shape == (64, 1024)
    assert np.array_equal(data[:, 512:], ids)            # the clean half
    masked = data[:, :512] == MASK_ID
    assert np.array_equal(masked, weight[:, :512] > 0)
    assert np.array_equal(data[:, :512][~masked], ids[~masked])
    assert not weight[:, 512:].any()
    # one t a block: every masked token of a block weighs 1 / t alike, and
    # the share masked is the mean of t ~ U[0.001, 1]
    blocks = weight[:, :512].reshape(64, 128, 4)
    top = blocks.max(axis=-1, keepdims=True)
    assert np.all((blocks == 0) | (blocks == top)) \
        and top[top > 0].min() >= 1.0
    assert 0.47 < masked.mean() < 0.53
    # E[1/t * 1{masked}] = 1 a token: the weighted loss is unbiased
    assert 0.9 < weight[:, :512].mean() < 1.1


def test_rotary_positions_restart_at_the_clean_half():
    x = _normal(2, (2, 40, 4, 16))
    got = lm_ops._rotary_embedding(x, rotary_dim=16, base=1e6, segments=2)
    _close(got, ref.rotary(x, 1e6), 1e-5)
    _close(got[:, 20:], lm_ops._rotary_embedding(x[:, 20:], rotary_dim=16,
                                                 base=1e6), 1e-6)
    plain = lm_ops._rotary_embedding(x, rotary_dim=16, base=1e6)
    assert np.array_equal(plain, lm_ops._rotary_embedding(
        x, rotary_dim=16, base=1e6, segments=1))
    _close(plain, ref.rotary(x, 1e6, fault="positions_run_on"), 1e-5)
    with pytest.raises(ValueError, match="equal parts"):
        lm_ops._rotary_embedding(x[:, :39], rotary_dim=16, segments=2)


def test_weighted_cross_entropy_and_its_gradient():
    logits = _normal(3, (2, 10, 7)).astype(jnp.bfloat16)
    label = jnp.asarray(np.random.RandomState(3).randint(0, 7, (2, 10)),
                        jnp.float32)
    weight = jnp.asarray(np.random.RandomState(4).uniform(0, 3, (2, 10))
                         * (np.arange(10) % 3 == 0), jnp.float32)

    def plain(x):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[..., None],
                                     axis=-1)[..., 0]
        return -jnp.sum(picked * weight, axis=-1) / 10

    ce = lambda x, w: lm_ops._sequence_cross_entropy(  # noqa: E731
        x, label, w, use_weight=True)
    _close(ce(logits, weight), plain(logits), 1e-6)
    g = jnp.asarray([1.0, 2.0], jnp.float32)
    d_x, d_w = jax.vjp(ce, logits, weight)[1](g)
    assert d_x.dtype == jnp.bfloat16 and not np.any(np.asarray(d_w))
    _close(d_x, jax.vjp(plain, logits)[1](g)[0], 1e-2)
    # weights of one: the plain mean, as without any
    _close(ce(logits, jnp.ones_like(weight)),
           lm_ops._sequence_cross_entropy(logits, label), 1e-6)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 16 experts each of a 128-expert top-8 softmax layer: the
    routed parts summed are the uncut reference's layer output."""
    cut = dict(CFG, router_num_experts=128, num_experts=16,
               num_experts_per_tok=8)
    whole = dict(cut, num_experts=128, first_expert=0)
    p = {k[len("layer1_"):]: v for k, v in _params(whole, seed=50).items()
         if k.startswith("layer1_moe_")}
    x = _normal(51, (2, 13, 32))
    want = ref.moe(x, p, whole, PLAIN)
    flat = x.reshape(-1, 32)
    routed, held = 0.0, 0.0
    for share in range(8):
        lo = 16 * share
        part, counts = lm_ops._moe_experts(
            flat, p["moe_router_weight"],
            *(p[k][lo:lo + 16] for k in ("moe_gate_weight", "moe_up_weight",
                                         "moe_down_weight")),
            num_experts=128, num_hidden=16, experts_held=16, first_expert=lo,
            top_k=8, norm_topk_prob=True, score_func="softmax")
        routed = routed + part
        held += float(counts[lo:lo + 16].sum())
        assert float(counts.sum()) == 26 * 8        # every share routes all
    _close(routed.reshape(x.shape), want, 1e-4)
    assert held == 26 * 8                           # each choice held once


# -- the whole model ---------------------------------------------------------

def _evaluate(net, params, data, weight):
    prog = _Program(net)

    def f(p):
        outs, _ = prog.evaluate(dict(p, data=jnp.asarray(data),
                                     softmax_label=jnp.asarray(weight)),
                                {}, (), True)
        return jnp.mean(outs[0]), outs[1:]
    # MakeLoss hands every sequence's loss a gradient of one: the sum's
    # gradient, BATCH times the mean's
    out, grads = jax.jit(jax.value_and_grad(f, has_aux=True))(params)
    return prog, (out, {n: g / BATCH for n, g in grads.items()})


@jax.jit
def _reference_grads(params, data, weight):
    return jax.value_and_grad(ref.loss_fn)(params, data, weight, CFG)


def test_loss_and_every_gradient_leaf():
    params, (data, weight) = _params(), _batch()
    net = models.sdar.get_symbol(CFG)
    assert sorted(n for n in net.list_arguments()
                  if n not in ("data", "softmax_label")) == sorted(params)
    prog, ((loss, (counts, bd)), grads) = _evaluate(net, params, data, weight)
    want_loss, want = _reference_grads(params, data, weight)
    _close(loss, want_loss, 1e-5)
    # the head reads the noisy half alone: 20 rows a sequence
    assert prog.symbol.get_internals()["lm_head_output"] is not None
    assert counts.shape == (2, 16) and float(counts.sum()) == 2 * 80 * 3
    assert np.array_equal(np.asarray(bd), np.stack(
        [(weight[:, :LENGTH] > 0).sum(1), np.full(BATCH, LENGTH)]))
    for name in sorted(params):
        _close(grads[name], want[name], 3e-4), name
    # every mistake the reference can plant moves the loss
    for fault in ref.FAULTS:
        wrong = float(jax.jit(lambda p, x, y, f=fault: ref.loss_fn(
            p, x, y, CFG, fault=f))(params, data, weight))
        assert abs(wrong - float(want_loss)) > 1e-4, fault


def test_mirroring_recomputes_and_changes_no_gradient():
    params, (data, weight) = _params(seed=60), _batch(1)
    on, (out_on, g_on) = _evaluate(models.sdar.get_symbol(CFG), params, data,
                                   weight)
    off, (out_off, g_off) = _evaluate(
        models.sdar.get_symbol(CFG, recompute=False), params, data, weight)
    assert on.mirror_stages == 4 and off.mirror_stages == 0
    _close(out_on[0], out_off[0], 1e-6)
    for name in sorted(params):
        _close(g_on[name], g_off[name], 1e-5), name
    text = jax.jit(lambda p: off.evaluate(dict(
        p, data=jnp.asarray(data), softmax_label=jnp.asarray(weight)), {}, (),
        True)[0][0]).lower(params).as_text(debug_info=True)
    for scope in ("mx:head", "mx:moe", "mx:attn/mx:attn:bd"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="expert layer"):
        models.sdar.get_symbol(dict(CFG, mlp_only_layers=[1]))


# -- Module.fit --------------------------------------------------------------

def test_fit_trains_through_the_fused_step_like_three_adam_steps():
    params = _params(seed=70, scale=0.2)
    data, weight = _batch(2, rows=3 * BATCH)
    # an epsilon of the gradients' own size: the update then follows the
    # gradient smoothly, where 1e-8 would make it a sign
    opt = dict(learning_rate=1e-2, beta1=0.9, beta2=0.95, epsilon=1e-3, wd=0.0)
    telemetry.reset()
    mod = mx.mod.Module(models.sdar.get_symbol(CFG), context=mx.cpu())
    losses = []
    mod.fit(mx.io.NDArrayIter(data, weight, batch_size=BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam", optimizer_params=opt,
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()},
            batch_end_callback=lambda p: losses.append(
                float(mod.get_outputs()[0].asnumpy().mean())))
    assert mod._fused_step is not None and mod._fused_step.ran
    assert len(mod.get_outputs()) == 3 and len(losses) == 3
    snap = telemetry.snapshot()
    assert snap["module.recompute.blocks"]["value"] == 3 * 4
    assert snap["module.moe.selections_total"]["value"] == 3 * 2 * 80 * 3
    # each step's masked positions, a mean over its sequences
    masked = (weight[:, :LENGTH] > 0).sum(1).reshape(3, BATCH).mean(1).sum()
    assert snap["module.bd.masked_positions"]["value"] == pytest.approx(masked)
    assert snap["module.bd.noisy_positions"]["value"] == 3 * LENGTH
    # two attention nodes a step: on the CPU the XLA reference computes all
    # 40 x 40 scores of each, forward and backward; the mask lets
    # 2 x (4 x 4 x 5 x 5) + 20 x 4 = 480 through a sequence, once each way
    assert pk.bd_visible_pairs(40, 4) == 480
    assert snap["module.attn.pairs_computed"]["value"] \
        == 3 * BATCH * 2 * 2 * 40 * 40
    assert snap["module.attn.pairs_visible"]["value"] \
        == 3 * BATCH * 2 * 2 * 480

    p, m = dict(params), {n: jnp.zeros_like(a) for n, a in params.items()}
    v = dict(m)
    want = []
    for t in range(3):
        lo = t * BATCH
        loss, _, p, m, v = jax.jit(lambda *a: ref.adam_step(*a, CFG, opt))(
            p, m, v, t + 1.0, data[lo:lo + BATCH], weight[lo:lo + BATCH])
        want.append(float(loss))
    _close(losses[:1], want[:1], 1e-5)
    _close(losses, want, 1e-4)
    got = dict(zip(mod._fused_step.param_names, mod._fused_step._masters))
    for name in sorted(params):
        moved = np.asarray(p[name] - params[name], np.float64)
        gap = np.asarray(got[name] - params[name], np.float64) - moved
        assert np.linalg.norm(gap) <= 0.05 * np.linalg.norm(moved) + 1e-12, \
            name

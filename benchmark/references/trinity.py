"""Plain reference: one chip's share of Trinity-Mini (``model_type: afmoe``;
https://huggingface.co/arcee-ai/Trinity-Mini) with its next-token
cross-entropy, gradients and MXNet's Adam step, in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision.

Every block ``l`` is ``h = x + N2(Attn_l(N1(x))); y = h + N4(MLP_l(N3(h)))``
with the plain RMSNorm ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.
``Attn_l``: ``q = Wq u`` (heads of ``head_dim``), ``k = Wk u``, ``v = Wv u``
(K/V heads), ``g = Wg u``; q and k take a per-head RMSNorm; where
``layer_types[l]`` is ``"sliding_attention"`` rotate-half rotary positions go
on q and k (all dims) and key ``j`` is visible to query ``i`` iff ``0 <= i - j
< sliding_window``; where ``"full_attention"`` nothing positional is applied
and ``j <= i``; ``out = Wo (softmax(q k^T / sqrt(d)) v * sigmoid(g))``.  The
scores are computed against ALL keys and masked: no band, no tile.  ``MLP_l``
is a dense SwiGLU below ``num_dense_layers`` and otherwise ``sum_{e chosen}
w_e F_e(u) + F_shared(u)`` with ``s = sigmoid(W_r u)`` over all
``router_num_experts``, the chosen the top-k of ``s + b`` (``b`` the expert
bias: no gradient, no part in ``w``), ``w_e = route_scale * s_e / (sum over the
chosen of s + 1e-20)``; the experts are applied densely, one at a time (every
held expert to every token, weight zero where not chosen).  The embedding is
scaled by ``sqrt(hidden_size)``.

It imports nothing of the program and takes nothing the program made.  The
share: ``num_experts`` counts the experts HELD (``first_expert`` onwards)
while the router scores ``router_num_experts`` and normalises over the
``num_experts_per_tok`` it chose wherever those live; what the absent experts
would add is left out, as the program leaves it out.  The vocabulary is the
slice ``vocab_size`` gives.  ``layers_kept`` names the published layers the
blocks stand for (kinds read from the whole published ``layer_types``).

Departures from the published model, each on purpose: no dropout; no
auxiliary loss and no update of the expert bias (``load_balance_coeff`` is in
the config, what it scales is not: neither is guessed, the bias keeps its
zeros).  ``jax.checkpoint`` round each block, each expert, each 512 query rows
and each 2,048 rows of the head changes memory, not the mathematics.

``hooks = (operand, cotangent)`` lets the CONTROL recompute the same network
with every matrix product in a lower precision (references/lowprec.py);
``fault`` plants one of the mistakes the tests must catch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

FAULTS = ("window_left_out", "rotary_on_full_layers", "softmax_router",
          "route_scale_left_out", "normalised_over_held_only",
          "embedding_scale_left_out")
ATTN_BLOCK = 512        # query rows of attention rematerialised together
HEAD_BLOCK = 2048       # rows of the vocabulary head rematerialised together
WINDOW, FULL = "sliding_attention", "full_attention"


def layer_kinds(cfg):
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return [cfg["layer_types"][i] for i in kept]


def param_shapes(cfg):
    """{name: shape} of every parameter under the program's names
    (``mxnet_tpu/models/trinity.py``): matrices are [out, in] as
    ``FullyConnected`` keeps them, expert stacks [held, in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    held, mid = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = mid * cfg["num_shared_experts"]
    s = {"embed_weight": (v, h), "final_norm_gamma": (h,),
         "lm_head_weight": (v, h)}
    for l in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % l
        for n in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                  "post_mlp_norm"):
            s[p + n + "_gamma"] = (h,)
        s[p + "attn_q_proj_weight"] = s[p + "attn_gate_proj_weight"] \
            = (heads * d, h)
        s[p + "attn_k_proj_weight"] = s[p + "attn_v_proj_weight"] = (kv * d, h)
        s[p + "attn_q_norm_gamma"] = s[p + "attn_k_norm_gamma"] = (d,)
        s[p + "attn_o_proj_weight"] = (h, heads * d)
        if l < cfg["num_dense_layers"]:
            wide = cfg["intermediate_size"]
            s[p + "mlp_gate_proj_weight"] = s[p + "mlp_up_proj_weight"] \
                = (wide, h)
            s[p + "mlp_down_proj_weight"] = (h, wide)
            continue
        s[p + "moe_router_weight"] = (cfg["router_num_experts"], h)
        s[p + "moe_expert_bias"] = (cfg["router_num_experts"],)
        s[p + "moe_gate_weight"] = s[p + "moe_up_weight"] = (held, h, mid)
        s[p + "moe_down_weight"] = (held, mid, h)
        s[p + "shared_gate_proj_weight"] = (shared, h)
        s[p + "shared_up_proj_weight"] = (shared, h)
        s[p + "shared_down_proj_weight"] = (h, shared)
    return s


def _mm(x, w, hooks):
    """``x @ w`` as the hooks' precision reads the operands."""
    operand, cotangent = hooks
    return cotangent(jnp.matmul(operand(x), operand(w),
                                precision=lax.Precision.HIGHEST))


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def rotary(x, theta):
    """Rotate-half positions on every dim of [batch, seq, heads, d]."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def masked_attention(q, k, v, window, hooks):
    """softmax(q k^T / sqrt(d) + mask) v for q [batch, seq, kv heads, group,
    d] on k, v [batch, seq, kv heads, d]; key j is visible to query i iff j <=
    i and, where ``window`` is not 0, i - j < window.  Query rows go
    ``ATTN_BLOCK`` at a time, each block against all keys and
    rematerialised, so that no seq x seq array is ever whole."""
    operand, cotangent = hooks
    b, s, kv, group, d = q.shape
    pad = (-s) % ATTN_BLOCK
    blocks = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
        b, -1, ATTN_BLOCK, kv, group, d)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(item):
        qb, start = item
        scores = cotangent(jnp.einsum(
            "bqhgd,bkhd->bhgqk", operand(qb), operand(k),
            precision=lax.Precision.HIGHEST)) / jnp.sqrt(jnp.float32(d))
        gap = (start + jnp.arange(ATTN_BLOCK))[:, None] - cols[None, :]
        seen = gap >= 0
        if window:
            seen &= gap < window
        # a finite floor: a padded row past the window sees no key at all,
        # and -inf there would turn its (dropped) softmax into NaN
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return cotangent(jnp.einsum(
            "bhgqk,bkhd->bqhgd", operand(probs), operand(v),
            precision=lax.Precision.HIGHEST))

    out = lax.map(one, (jnp.moveaxis(blocks, 1, 0),
                        jnp.arange(blocks.shape[1]) * ATTN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, kv * group * d)[:, :s]


def gated_attention(x, p, cfg, kind, hooks, fault=None):
    b, s, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _mm(x, p["attn_q_proj_weight"].T, hooks).reshape(b, s, heads, d)
    k = _mm(x, p["attn_k_proj_weight"].T, hooks).reshape(b, s, kv, d)
    v = _mm(x, p["attn_v_proj_weight"].T, hooks).reshape(b, s, kv, d)
    gate = _mm(x, p["attn_gate_proj_weight"].T, hooks)
    q = rms_norm(q, p["attn_q_norm_gamma"], eps)
    k = rms_norm(k, p["attn_k_norm_gamma"], eps)
    window = cfg["sliding_window"] if kind == WINDOW else 0
    if kind == WINDOW or fault == "rotary_on_full_layers":
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    if fault == "window_left_out":
        window = 0
    a = masked_attention(q.reshape(b, s, kv, heads // kv, d), k, v, window,
                         hooks)
    return _mm(a * jax.nn.sigmoid(gate), p["attn_o_proj_weight"].T, hooks)


def _ffn(x, gate, up, down, hooks):
    return _mm(jax.nn.silu(_mm(x, gate, hooks)) * _mm(x, up, hooks), down,
               hooks)


def routed_weights(x, p, cfg, fault=None):
    """[tokens, held] weight of each held expert for each token (0 where it
    was not among the token's top-k), and the chosen ids."""
    held, first = cfg["num_experts"], cfg.get("first_expert", 0)
    logits = jnp.matmul(x, p["moe_router_weight"].T,
                        precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, -1) if fault == "softmax_router" \
        else jax.nn.sigmoid(logits)
    _, top_e = lax.top_k(scores + lax.stop_gradient(p["moe_expert_bias"]),
                         cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    local = top_e - first
    mine = (local >= 0) & (local < held)
    if cfg["route_norm"]:
        over = jnp.where(mine, top_s, 0.0) \
            if fault == "normalised_over_held_only" else top_s
        top_s = top_s / (jnp.sum(over, -1, keepdims=True) + 1e-20)
    if fault != "route_scale_left_out":
        top_s = top_s * cfg["route_scale"]
    hot = (local[..., None] == jnp.arange(held)) & mine[..., None]
    return jnp.sum(jnp.where(hot, top_s[..., None], 0.0), axis=1), top_e


def moe(x, p, cfg, hooks, fault=None):
    b, s, h = x.shape
    x = x.reshape(b * s, h)
    weights, _ = routed_weights(x, p, cfg, fault)

    @jax.checkpoint
    def expert(w_col, gate, up, down):
        return w_col[:, None] * _ffn(x, gate, up, down, hooks)

    def body(acc, item):
        return acc + expert(*item), None

    routed, _ = lax.scan(body, jnp.zeros_like(x),
                         (weights.T, p["moe_gate_weight"], p["moe_up_weight"],
                          p["moe_down_weight"]))
    shared = _ffn(x, p["shared_gate_proj_weight"].T,
                  p["shared_up_proj_weight"].T, p["shared_down_proj_weight"].T,
                  hooks)
    return (routed + shared).reshape(b, s, h)


def _layer(x, p, cfg, layer, kind, hooks, fault):
    eps = cfg["rms_norm_eps"]
    a = gated_attention(rms_norm(x, p["input_norm_gamma"], eps), p, cfg, kind,
                        hooks, fault)
    h = x + rms_norm(a, p["post_attn_norm_gamma"], eps)
    u = rms_norm(h, p["pre_mlp_norm_gamma"], eps)
    if layer < cfg["num_dense_layers"]:
        m = _ffn(u, p["mlp_gate_proj_weight"].T, p["mlp_up_proj_weight"].T,
                 p["mlp_down_proj_weight"].T, hooks)
    else:
        m = moe(u, p, cfg, hooks, fault)
    return h + rms_norm(m, p["post_mlp_norm_gamma"], eps)


def hidden(params, tokens, cfg, hooks, fault=None):
    """[batch, seq, hidden] after the final norm."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    if cfg.get("mup_enabled") and fault != "embedding_scale_left_out":
        x = x * jnp.sqrt(jnp.float32(cfg["hidden_size"]))
    for l, kind in enumerate(layer_kinds(cfg)):
        prefix = "layer%d_" % l
        sub = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, layer=l, kind=kind, hooks=hooks,
            fault=fault))(x, sub)
    return rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"])


def logits(params, tokens, cfg, hooks=None, fault=None):
    """[batch, seq, vocab] float32 for integer ``tokens`` [batch, seq]."""
    hooks = hooks or (lambda a: a, lambda a: a)
    return _mm(hidden(params, tokens, cfg, hooks, fault),
               params["lm_head_weight"].T, hooks)


def loss_fn(params, tokens, labels, cfg, hooks=None, fault=None):
    """Mean over every position of the next-token cross-entropy; the head
    and its softmax go ``HEAD_BLOCK`` rows at a time."""
    hooks = hooks or (lambda a: a, lambda a: a)
    x = hidden(params, tokens, cfg, hooks, fault)
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    pad = (-n) % HEAD_BLOCK
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, HEAD_BLOCK,
                                                     rows.shape[-1])
    ids = jnp.pad(labels.reshape(-1).astype(jnp.int32), (0, pad)).reshape(
        -1, HEAD_BLOCK)
    live = (jnp.arange(n + pad) < n).reshape(-1, HEAD_BLOCK)

    @jax.checkpoint
    def block(item):
        r, i, keep = item
        logp = jax.nn.log_softmax(_mm(r, params["lm_head_weight"].T, hooks),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, i[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(keep, picked, 0.0))

    return jnp.sum(lax.map(block, (rows, ids, live))) / n


def adam_step(params, mean, var, t, tokens, labels, cfg, opt, hooks=None,
              fault=None):
    """Step ``t`` (1-based) of MXNet's Adam on the mean loss: ``g += wd w;
    m = b1 m + (1-b1) g; v = b2 v + (1-b2) g^2; w -= lr sqrt(1-b2^t)/(1-b1^t)
    m / (sqrt(v) + eps)``.  Returns (loss, the gradient the optimizer got,
    parameters, m, v)."""
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels, cfg,
                                              hooks, fault)
    b1, b2 = opt["beta1"], opt["beta2"]
    lr = opt["learning_rate"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_m, new_v = {}, {}, {}
    for name, w in params.items():
        g = grads[name] + opt["wd"] * w
        new_m[name] = b1 * mean[name] + (1.0 - b1) * g
        new_v[name] = b2 * var[name] + (1.0 - b2) * jnp.square(g)
        new_p[name] = w - lr * new_m[name] / (jnp.sqrt(new_v[name])
                                              + opt["epsilon"])
    return loss, grads, new_p, new_m, new_v

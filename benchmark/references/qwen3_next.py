"""Plain reference: one chip's share of Qwen3-Next (``model_type:
qwen3_next``; https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct) with
its next-token cross-entropy, gradients and MXNet's Adam step, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.

Every layer ``l`` is ``h = x + Mixer_l(N(x)); y = h + MoE(N(h))`` with the
zero-centred RMSNorm ``N(x; w) = x / rms(x) (1 + w)``; the mixer is gated
softmax attention where ``(l + 1) % full_attention_interval == 0`` and Gated
DeltaNet otherwise.  The delta rule is computed TOKEN BY TOKEN with
``lax.scan`` (``S' = g_t S; S = S' + k_t (b_t (v_t - S'^T k_t))^T; o_t = S^T
q_t``), so that it shares nothing with the program's chunked form; the
experts are applied densely (every held expert to every token, weighted by
the renormalised top-k probability, zero where not chosen).

It imports nothing of the program and takes nothing the program made.  The
share: ``num_experts`` counts the experts HELD (``first_expert`` onwards)
while the router scores ``router_num_experts`` and renormalises over the
``num_experts_per_tok`` it chose wherever those live; what the absent
experts would add is left out, as the program leaves it out.  The vocabulary
is the slice ``vocab_size`` gives.

Departures from the published model, each on purpose: the multi-token-
prediction head (``described_as``: "MTP 1") is left out — no key of the
published config carries it; no dropout; no auxiliary load-balancing loss
(the config names no coefficient); ``in_proj_qkvz`` is laid out as the plain
concatenation [q | k | v | z] (the published checkpoint interleaves the same
rows by key head: a permutation of a seeded matrix).  ``jax.checkpoint``
round each layer, each expert and each 64 tokens of the recurrence changes
memory, not the mathematics.

``hooks = (operand, cotangent)`` lets the CONTROL recompute the same network
with every matrix product in a lower precision (references/lowprec.py);
``fault`` plants one of the mistakes the tests must catch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

FAULTS = ("decay_left_out", "attention_gate_left_out",
          "normalised_over_held_only", "absent_experts_added")
SCAN_BLOCK = 64         # tokens of the recurrence rematerialised together
ATTN_BLOCK = 512        # query rows of attention rematerialised together
HEAD_BLOCK = 2048       # rows of the vocabulary head rematerialised together


def is_attention(cfg, layer):
    return (layer + 1) % cfg["full_attention_interval"] == 0


def param_shapes(cfg):
    """{name: shape} of every trainable parameter, under the program's
    names (``mxnet_tpu/models/qwen3_next.py``): matrices are [out, in] as
    ``FullyConnected`` keeps them, expert stacks [held, in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    held, mid = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    s = {"embed_weight": (v, h), "final_norm_gamma": (h,),
         "lm_head_weight": (v, h)}
    for l in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % l
        s[p + "input_norm_gamma"] = s[p + "post_norm_gamma"] = (h,)
        if is_attention(cfg, l):
            s[p + "attn_q_proj_weight"] = (heads * 2 * d, h)
            s[p + "attn_k_proj_weight"] = (kv * d, h)
            s[p + "attn_v_proj_weight"] = (kv * d, h)
            s[p + "attn_q_norm_gamma"] = s[p + "attn_k_norm_gamma"] = (d,)
            s[p + "attn_o_proj_weight"] = (h, heads * d)
        else:
            s[p + "gdn_in_proj_qkvz_weight"] = (2 * hk * dk + 2 * hv * dv, h)
            s[p + "gdn_in_proj_ba_weight"] = (2 * hv, h)
            s[p + "gdn_conv_weight"] = (2 * hk * dk + hv * dv,
                                        cfg["linear_conv_kernel_dim"])
            s[p + "gdn_A_log"] = s[p + "gdn_dt_bias"] = (hv,)
            s[p + "gdn_norm_gamma"] = (dv,)
            s[p + "gdn_out_proj_weight"] = (h, hv * dv)
        s[p + "moe_router_weight"] = (cfg["router_num_experts"], h)
        s[p + "moe_gate_weight"] = s[p + "moe_up_weight"] = (held, h, mid)
        s[p + "moe_down_weight"] = (held, mid, h)
        s[p + "shared_gate_proj_weight"] = (shared, h)
        s[p + "shared_up_proj_weight"] = (shared, h)
        s[p + "shared_down_proj_weight"] = (h, shared)
        s[p + "shared_gate_weight"] = (1, h)
    return s


def _mm(x, w, hooks):
    """``x @ w`` as the hooks' precision reads the operands."""
    operand, cotangent = hooks
    return cotangent(jnp.matmul(operand(x), operand(w),
                                precision=lax.Precision.HIGHEST))


def rms_norm(x, w, eps, zero_centered=True):
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * (1.0 + w if zero_centered else w)


def rotary(x, rotary_dim, theta):
    """Rotate-half positions on the first ``rotary_dim`` dims of [batch,
    seq, heads, head_dim]."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                         / rotary_dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary_dim:]], axis=-1)


def causal_attention(q, k, v, hooks):
    """softmax(q k^T / sqrt(d) + causal) v for q [batch, seq, kv heads,
    group, d] on k, v [batch, seq, kv heads, d]: every K/V head serves its
    group of query heads.  Query rows go ``ATTN_BLOCK`` at a time, each
    block against all keys and rematerialised, so that no seq x seq array
    is ever whole."""
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
        rows = start + jnp.arange(ATTN_BLOCK)
        probs = jax.nn.softmax(jnp.where(rows[:, None] >= cols[None, :],
                                         scores, -jnp.inf), axis=-1)
        return cotangent(jnp.einsum(
            "bhgqk,bkhd->bqhgd", operand(probs), operand(v),
            precision=lax.Precision.HIGHEST))

    out = lax.map(one, (jnp.moveaxis(blocks, 1, 0),
                        jnp.arange(blocks.shape[1]) * ATTN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, kv * group * d)[:, :s]


def gated_attention(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = _mm(x, p["attn_q_proj_weight"].T, hooks).reshape(b, s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, heads * d)
    k = _mm(x, p["attn_k_proj_weight"].T, hooks).reshape(b, s, kv, d)
    v = _mm(x, p["attn_v_proj_weight"].T, hooks).reshape(b, s, kv, d)
    rd = int(d * cfg["partial_rotary_factor"])
    q = rotary(rms_norm(q, p["attn_q_norm_gamma"], eps), rd, cfg["rope_theta"])
    k = rotary(rms_norm(k, p["attn_k_norm_gamma"], eps), rd, cfg["rope_theta"])
    a = causal_attention(q.reshape(b, s, kv, heads // kv, d), k, v, hooks)
    a = a.reshape(b, s, heads * d)
    if fault != "attention_gate_left_out":
        a = a * jax.nn.sigmoid(gate)
    return _mm(a, p["attn_o_proj_weight"].T, hooks)


def delta_rule(q, k, v, decay, beta):
    """Token by token.  q, k: [batch, seq, heads, dk]; v: [batch, seq,
    heads, dv]; decay, beta: [batch, seq, heads].  State [batch, heads, dk,
    dv] from zero."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % SCAN_BLOCK
    seq_first = lambda x: jnp.pad(
        jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    xs = tuple(seq_first(x).reshape((-1, SCAN_BLOCK) + seq_first(x).shape[1:])
               for x in (q, k, v, decay, beta))

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * g_t[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t,
                          precision=lax.Precision.HIGHEST)
        state = state + k_t[..., :, None] \
            * (b_t[..., None] * (v_t - read))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def block(state, x):
        return lax.scan(token, state, x)

    # padded tokens come after the real ones: what they do to the state is
    # never read
    _, out = lax.scan(block, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(out.reshape((-1,) + out.shape[2:])[:s], 0, 1)


def gated_delta_net(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kd, vd = hk * dk, hv * dv
    qkvz = _mm(x, p["gdn_in_proj_qkvz_weight"].T, hooks)
    ba = _mm(x, p["gdn_in_proj_ba_weight"].T, hooks)
    mixed, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    # causal depth-wise convolution, no bias, then SiLU
    width = cfg["linear_conv_kernel_dim"]
    w = p["gdn_conv_weight"]
    padded = jnp.pad(mixed, ((0, 0), (width - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + s] * w[:, j]
                            for j in range(width)))
    q = mixed[..., :kd].reshape(b, s, hk, dk)
    k = mixed[..., kd:2 * kd].reshape(b, s, hk, dk)
    v = mixed[..., 2 * kd:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    decay = jnp.exp(-jnp.exp(p["gdn_A_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["gdn_dt_bias"]))
    if fault == "decay_left_out":
        decay = jnp.ones_like(decay)
    unit = lambda t: t * lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                                   + 1e-6)
    q = jnp.repeat(unit(q) / jnp.sqrt(jnp.float32(dk)), hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    o = delta_rule(q, k, v, decay, beta)
    o = rms_norm(o, p["gdn_norm_gamma"], cfg["rms_norm_eps"],
                 zero_centered=False)
    o = o.reshape(b, s, vd) * jax.nn.silu(z)
    return _mm(o, p["gdn_out_proj_weight"].T, hooks)


def _ffn(x, gate, up, down, hooks):
    return _mm(jax.nn.silu(_mm(x, gate, hooks)) * _mm(x, up, hooks), down,
               hooks)


def routed_weights(x, p, cfg, fault=None):
    """[tokens, held] weight of each held expert for each token (0 where
    it was not among the token's top-k), and the chosen ids."""
    held, first = cfg["num_experts"], cfg.get("first_expert", 0)
    probs = jax.nn.softmax(jnp.matmul(
        x, p["moe_router_weight"].T, precision=lax.Precision.HIGHEST), -1)
    top_p, top_e = lax.top_k(probs, cfg["num_experts_per_tok"])
    local = top_e - first
    mine = (local >= 0) & (local < held)
    if fault == "absent_experts_added":
        local, mine = top_e % held, jnp.ones_like(mine)
    if cfg["norm_topk_prob"]:
        if fault == "normalised_over_held_only":
            top_p = top_p / jnp.maximum(
                jnp.sum(jnp.where(mine, top_p, 0.0), -1, keepdims=True), 1e-30)
        else:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    hot = (local[..., None] == jnp.arange(held)) & mine[..., None]
    return jnp.sum(jnp.where(hot, top_p[..., None], 0.0), axis=1), top_e


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
    gate = jax.nn.sigmoid(jnp.matmul(x, p["shared_gate_weight"].T,
                                     precision=lax.Precision.HIGHEST))
    return (routed + gate * shared).reshape(b, s, h)


def _layer(x, p, cfg, layer, hooks, fault):
    eps = cfg["rms_norm_eps"]
    mixer = gated_attention if is_attention(cfg, layer) else gated_delta_net
    h = x + mixer(rms_norm(x, p["input_norm_gamma"], eps), p, cfg, hooks,
                  fault)
    return h + moe(rms_norm(h, p["post_norm_gamma"], eps), p, cfg, hooks,
                   fault)


def hidden(params, tokens, cfg, hooks, fault=None):
    """[batch, seq, hidden] after the final norm."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for l in range(cfg["num_hidden_layers"]):
        prefix = "layer%d_" % l
        sub = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, layer=l, hooks=hooks, fault=fault))(x, sub)
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

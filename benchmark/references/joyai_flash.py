"""Plain reference: one chip's share of JoyAI-LLM-Flash (``model_type:
joyai_llm_flash``; https://huggingface.co/jdopensource/JoyAI-LLM-Flash) with
both of its losses, gradients and MXNet's Adam step, in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision.

Every block is ``h = x + MLA(N(x)); y = h + FFN_l(N(h))`` with the plain
RMSNorm ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.

``MLA`` (DeepSeek-V2/V3's multi-head latent attention): ``c_q = N(W_dq u)``
(``q_lora_rank``), ``q = W_uq c_q`` as ``heads`` of ``[q_nope | q_rope]``
(``qk_nope_head_dim`` + ``qk_rope_head_dim``); ``[c_kv | k_r] = W_dkv u``
(``kv_lora_rank`` + ``qk_rope_head_dim``), ``[k_nope | v] = W_ukv N(c_kv)`` as
heads of ``qk_nope_head_dim + v_head_dim``.  Rotary positions go on ``q_rope``
of every head and on the one ``k_r``, written as PAIRS: frequency ``i`` turns
dims ``(2i, 2i+1)`` (``rope_interleave``; theta = ``rope_theta``, no scaling).
Head ``h``'s key is ``[k_nope_h | k_r]``; ``out = W_o concat_h softmax(q_h
k_h^T / sqrt(d_nope + d_rope)) v_h``, causal.  Here ``k_r`` IS copied to every
head and the scores are computed against ALL keys and masked: no shared part,
no tile.

``FFN_l`` is a dense SwiGLU below ``first_k_dense_replace`` and otherwise
``sum_{e chosen} w_e F_e(u) + F_shared(u)`` with ``s = sigmoid(W_r u)`` over all
``router_num_experts``, the chosen the top-k of ``s + b`` (``b`` the expert
bias: no gradient, no part in ``w``: ``noaux_tc``), ``w_e =
routed_scaling_factor * s_e / (sum over the chosen of s + 1e-20)``; the experts
are applied densely, one at a time (every held expert to every token, weight
zero where not chosen).

The multi-token-prediction module (DeepSeek-V3 section 2.2, depth 1): with
``h`` the main model's output after its final norm, ``h'_i = W_eh
[N(Emb(t_{i+1})) ; N(h_i)]``, one more block of the second kind, its own final
norm, the main model's embedding and output matrix; ``L_mtp`` is the mean
cross-entropy against ``t_{i+2}`` over the positions that have one (all but
the last).  ``loss = L_main + mtp_loss_weight * L_mtp``.

It imports nothing of the program and takes nothing the program made.  The
share: ``n_routed_experts`` counts the experts HELD (``first_expert`` onwards)
while the router scores ``router_num_experts`` and normalises over the
``num_experts_per_tok`` it chose wherever those live; what the absent experts
would add is left out, as the program leaves it out.  The vocabulary is the
slice ``vocab_size`` gives.

Departures from the published model, each on purpose: no dropout; no update of
the expert bias (it keeps its zeros) and no auxiliary sequence-balance loss;
HF's implementation of ``rope_interleave`` de-interleaves q_rope and k_r and
then rotates halves, which is the pairwise rotation followed by one fixed
permutation of the dims of BOTH, and a permutation common to q and k leaves
every score as it is: the pairs are rotated in place here.  The module's input
order under ``W_eh`` (embedding first), ``h`` taken after the final norm, and
the loss weight are the configuration file's ``assumed``.  ``jax.checkpoint``
round each block, each expert, each 256 query rows and each 2,048 rows of the
head changes memory, not the mathematics.

``hooks = (operand, cotangent)`` lets the CONTROL recompute the same network
with every matrix product in a lower precision (references/lowprec.py);
``fault`` plants one of the mistakes the tests must catch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

FAULTS = ("rotary_off_shared_key", "scale_by_value_width",
          "latent_norm_left_out", "mtp_term_left_out",
          "head_gradient_from_mtp_dropped")
ATTN_BLOCK = 256        # query rows of attention rematerialised together
HEAD_BLOCK = 2048       # rows of the vocabulary head rematerialised together


def _block_prefixes(cfg):
    """[(parameter prefix, whether its FFN is the dense one)] of the main
    model's blocks and, last, the prediction module's."""
    out = [("layer%d_" % l, l < cfg["first_k_dense_replace"])
           for l in range(cfg["num_hidden_layers"])]
    if cfg.get("num_nextn_predict_layers", 0):
        out.append(("mtp_", False))
    return out


def param_shapes(cfg):
    """{name: shape} of every parameter under the program's names
    (``mxnet_tpu/models/joyai_flash.py``): matrices are [out, in] as
    ``FullyConnected`` keeps them, expert stacks [held, in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, nope, rope, d_v = (cfg["num_attention_heads"],
                              cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, mid = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = mid * cfg["n_shared_experts"]
    s = {"embed_weight": (v, h), "final_norm_gamma": (h,),
         "lm_head_weight": (v, h)}
    for p, dense in _block_prefixes(cfg):
        s[p + "input_norm_gamma"] = s[p + "post_attn_norm_gamma"] = (h,)
        s[p + "attn_q_a_proj_weight"] = (q_rank, h)
        s[p + "attn_q_a_norm_gamma"] = (q_rank,)
        s[p + "attn_q_b_proj_weight"] = (heads * (nope + rope), q_rank)
        s[p + "attn_kv_a_proj_weight"] = (kv_rank + rope, h)
        s[p + "attn_kv_a_norm_gamma"] = (kv_rank,)
        s[p + "attn_kv_b_proj_weight"] = (heads * (nope + d_v), kv_rank)
        s[p + "attn_o_proj_weight"] = (h, heads * d_v)
        if dense:
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
    if cfg.get("num_nextn_predict_layers", 0):
        s["mtp_embed_norm_gamma"] = s["mtp_hidden_norm_gamma"] \
            = s["mtp_final_norm_gamma"] = (h,)
        s["mtp_eh_proj_weight"] = (h, 2 * h)
    return s


def _mm(x, w, hooks):
    """``x @ w`` as the hooks' precision reads the operands."""
    operand, cotangent = hooks
    return cotangent(jnp.matmul(operand(x), operand(w),
                                precision=lax.Precision.HIGHEST))


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def rotary_pairs(x, theta):
    """Rotary positions on every dim of [batch, seq, heads, d], pair by
    pair: ``(x[2i], x[2i+1])`` is turned by ``pos * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def masked_attention(q, k, v, scale, hooks):
    """softmax(q k^T * scale + causal mask) v for q, k [batch, seq, heads,
    d_qk] and v [batch, seq, heads, d_v].  Query rows go ``ATTN_BLOCK`` at a
    time, each block against all keys and rematerialised, so that no seq x
    seq array is ever whole."""
    operand, cotangent = hooks
    b, s, heads, d = q.shape
    pad = (-s) % ATTN_BLOCK
    blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, ATTN_BLOCK, heads, d)
    cols = jnp.arange(s)

    @jax.checkpoint
    def one(item):
        qb, start = item
        scores = cotangent(jnp.einsum(
            "bqhd,bkhd->bhqk", operand(qb), operand(k),
            precision=lax.Precision.HIGHEST)) * scale
        seen = (start + jnp.arange(ATTN_BLOCK))[:, None] >= cols[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return cotangent(jnp.einsum(
            "bhqk,bkhd->bqhd", operand(probs), operand(v),
            precision=lax.Precision.HIGHEST))

    out = lax.map(one, (jnp.moveaxis(blocks, 1, 0),
                        jnp.arange(blocks.shape[1]) * ATTN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, heads * v.shape[-1])[:, :s]


def latent_attention(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    heads, nope, rope, d_v = (cfg["num_attention_heads"],
                              cfg["qk_nope_head_dim"],
                              cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        float(cfg["rope_theta"])
    norm = (lambda t, w: t) if fault == "latent_norm_left_out" \
        else (lambda t, w: rms_norm(t, w, eps))
    c_q = norm(_mm(x, p["attn_q_a_proj_weight"].T, hooks),
               p["attn_q_a_norm_gamma"])
    q = _mm(c_q, p["attn_q_b_proj_weight"].T, hooks).reshape(
        b, s, heads, nope + rope)
    down = _mm(x, p["attn_kv_a_proj_weight"].T, hooks)
    c_kv, k_r = down[..., :rank], down[..., rank:].reshape(b, s, 1, rope)
    up = _mm(norm(c_kv, p["attn_kv_a_norm_gamma"]),
             p["attn_kv_b_proj_weight"].T, hooks).reshape(
                 b, s, heads, nope + d_v)
    q = jnp.concatenate([q[..., :nope], rotary_pairs(q[..., nope:], theta)],
                        axis=-1)
    if fault != "rotary_off_shared_key":
        k_r = rotary_pairs(k_r, theta)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_r, (b, s, heads, rope))], axis=-1)
    width = d_v if fault == "scale_by_value_width" else nope + rope
    a = masked_attention(q, k, up[..., nope:],
                         1.0 / jnp.sqrt(jnp.float32(width)), hooks)
    return _mm(a, p["attn_o_proj_weight"].T, hooks)


def _ffn(x, gate, up, down, hooks):
    return _mm(jax.nn.silu(_mm(x, gate, hooks)) * _mm(x, up, hooks), down,
               hooks)


def routed_weights(x, p, cfg):
    """[tokens, held] weight of each held expert for each token (0 where it
    was not among the token's top-k), and the chosen ids."""
    held, first = cfg["n_routed_experts"], cfg.get("first_expert", 0)
    scores = jax.nn.sigmoid(jnp.matmul(x, p["moe_router_weight"].T,
                                       precision=lax.Precision.HIGHEST))
    _, top_e = lax.top_k(scores + lax.stop_gradient(p["moe_expert_bias"]),
                         cfg["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if cfg["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    local = top_e - first
    hot = (local[..., None] == jnp.arange(held)) \
        & ((local >= 0) & (local < held))[..., None]
    return jnp.sum(jnp.where(hot, top_s[..., None], 0.0), axis=1), top_e


def moe(x, p, cfg, hooks):
    b, s, h = x.shape
    x = x.reshape(b * s, h)
    weights, _ = routed_weights(x, p, cfg)

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


def _layer(x, p, cfg, dense, hooks, fault):
    eps = cfg["rms_norm_eps"]
    h = x + latent_attention(rms_norm(x, p["input_norm_gamma"], eps), p, cfg,
                             hooks, fault)
    u = rms_norm(h, p["post_attn_norm_gamma"], eps)
    if dense:
        return h + _ffn(u, p["mlp_gate_proj_weight"].T,
                        p["mlp_up_proj_weight"].T,
                        p["mlp_down_proj_weight"].T, hooks)
    return h + moe(u, p, cfg, hooks)


def _block(x, params, prefix, cfg, dense, hooks, fault):
    sub = {k[len(prefix):]: v for k, v in params.items()
           if k.startswith(prefix)}
    return jax.checkpoint(functools.partial(
        _layer, cfg=cfg, dense=dense, hooks=hooks, fault=fault))(x, sub)


def hidden(params, tokens, cfg, hooks, fault=None):
    """[batch, seq, hidden] of the main model after its final norm."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for prefix, dense in _block_prefixes(cfg)[:cfg["num_hidden_layers"]]:
        x = _block(x, params, prefix, cfg, dense, hooks, fault)
    return rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"])


def mtp_hidden(params, main, labels, cfg, hooks, fault=None):
    """[batch, seq, hidden] of the prediction module after ITS final norm,
    from the main model's normed output and the next tokens."""
    eps = cfg["rms_norm_eps"]
    emb = params["embed_weight"][labels.astype(jnp.int32)]
    both = jnp.concatenate(
        [rms_norm(emb, params["mtp_embed_norm_gamma"], eps),
         rms_norm(main, params["mtp_hidden_norm_gamma"], eps)], axis=-1)
    x = _mm(both, params["mtp_eh_proj_weight"].T, hooks)
    x = _block(x, params, "mtp_", cfg, False, hooks, fault)
    return rms_norm(x, params["mtp_final_norm_gamma"], eps)


def logits(params, tokens, cfg, hooks=None, fault=None):
    """[batch, seq, vocab] float32 of the main head for integer ``tokens``
    [batch, seq]."""
    hooks = hooks or (lambda a: a, lambda a: a)
    return _mm(hidden(params, tokens, cfg, hooks, fault),
               params["lm_head_weight"].T, hooks)


def head_loss(x, head, targets, live, hooks):
    """Mean over the ``live`` positions of the cross-entropy of ``x`` [batch,
    seq, hidden] through ``head`` [vocab, hidden] against ``targets``; the
    head and its softmax go ``HEAD_BLOCK`` rows at a time."""
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    pad = (-n) % HEAD_BLOCK
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, HEAD_BLOCK,
                                                     rows.shape[-1])
    ids = jnp.pad(targets.reshape(-1).astype(jnp.int32), (0, pad)).reshape(
        -1, HEAD_BLOCK)
    keep = jnp.pad(live.reshape(-1), (0, pad)).reshape(-1, HEAD_BLOCK)

    @jax.checkpoint
    def block(item):
        r, i, k = item
        logp = jax.nn.log_softmax(_mm(r, head.T, hooks), axis=-1)
        picked = jnp.take_along_axis(logp, i[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(k, picked, 0.0))

    return jnp.sum(lax.map(block, (rows, ids, keep))) / jnp.sum(live)


def losses(params, tokens, labels, cfg, hooks=None, fault=None):
    """(L_main, L_mtp): the next-token cross-entropy over every position, and
    the module's against the token after the next over all but the last
    position (0 where the configuration has no module)."""
    hooks = hooks or (lambda a: a, lambda a: a)
    main = hidden(params, tokens, cfg, hooks, fault)
    every = jnp.ones(labels.shape, bool)
    l_main = head_loss(main, params["lm_head_weight"], labels, every, hooks)
    if not cfg.get("num_nextn_predict_layers", 0):
        return l_main, jnp.float32(0.0)
    head = params["lm_head_weight"]
    if fault == "head_gradient_from_mtp_dropped":
        head = lax.stop_gradient(head)
    after = jnp.concatenate([labels[:, 1:], jnp.zeros_like(labels[:, :1])], 1)
    live = every.at[:, -1].set(False)
    l_mtp = head_loss(mtp_hidden(params, main, labels, cfg, hooks, fault),
                      head, after, live, hooks)
    return l_main, l_mtp


def loss_fn(params, tokens, labels, cfg, hooks=None, fault=None):
    """``L_main + mtp_loss_weight * L_mtp``, the loss that is trained."""
    l_main, l_mtp = losses(params, tokens, labels, cfg, hooks, fault)
    if fault == "mtp_term_left_out" \
            or not cfg.get("num_nextn_predict_layers", 0):
        return l_main
    return l_main + cfg["mtp_loss_weight"] * l_mtp


def adam_step(params, mean, var, t, tokens, labels, cfg, opt, hooks=None,
              fault=None):
    """Step ``t`` (1-based) of MXNet's Adam on the loss: ``g += wd w;
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

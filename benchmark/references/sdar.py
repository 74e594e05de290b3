"""Plain reference: one chip's share of SDAR-30B-A3B (``model_type:
sdar_moe``; https://huggingface.co/JetLM/SDAR-30B-A3B-Chat) with its
block-diffusion loss, gradients and MXNet's Adam step, in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision.

Every block is Qwen3-MoE's: ``h = x + Attn(N1(x)); y = h + MoE(N2(h))`` with
the plain RMSNorm ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``.  ``Attn``: ``q
= Wq u`` (heads of ``head_dim``), ``k = Wk u``, ``v = Wv u`` (K/V heads); q
and k take a per-head RMSNorm, then rotate-half rotary positions on all dims
(theta ``rope_theta``), where the ``2 L`` rows are the noisy copy of the
sequence and its clean copy and BOTH are at positions ``0 .. L - 1``; ``out
= Wo softmax(q k^T / sqrt(d) + M) v`` with ``M`` the block-diffusion mask:
with ``blk(i) = (i mod L) div B``, key ``j`` is visible to query ``i`` iff
both are noisy and ``blk(j) = blk(i)``, or ``i`` is noisy, ``j`` clean and
``blk(j) < blk(i)``, or both are clean and ``blk(j) <= blk(i)``.  The scores
are computed against ALL keys and masked, ``ATTN_BLOCK`` query rows at a
time: no tile, no run.  ``MoE`` is ``sum_{e chosen} w_e F_e(u)`` with ``p =
softmax(W_r u)`` over all ``router_num_experts``, the chosen the top-k of
``p``, ``w_e = p_e / sum over the chosen of p`` (``norm_topk_prob``); the
experts are applied densely, one at a time (every held expert to every token,
weight zero where not chosen).  No shared expert.

The loss of a sequence is ``(1 / L) sum_i w_i CE(head(N(h[i])), x_0[i])``
over the noisy half's positions ``i``, where ``w`` is the first half of
``labels`` (``1 / t_b`` at a masked position, 0 elsewhere) and the target
``x_0[i]`` is the clean half of ``tokens``; the loss trained is the mean over
the batch.

It imports nothing of the program and takes nothing the program made.  The
share: ``num_experts`` counts the experts HELD (``first_expert`` onwards)
while the router scores ``router_num_experts`` and normalises over the
``num_experts_per_tok`` it chose wherever those live; what the absent experts
would add is left out, as the program leaves it out.  The vocabulary is the
slice ``vocab_size`` gives.

Departures from the published model, each on purpose: no dropout; no
auxiliary load-balancing loss (the config carries no coefficient for one);
``jax.checkpoint`` round each block, each expert, each ``ATTN_BLOCK`` query
rows and each ``HEAD_BLOCK`` rows of the head changes memory, not the
mathematics.

``hooks = (operand, cotangent)`` lets the CONTROL recompute the same network
with every matrix product in a lower precision (references/lowprec.py);
``fault`` plants one of the mistakes the tests must catch.  The two faults of
the loss's weight (``WEIGHT_FAULTS``) are functions of the labels alone, so
the unaltered reference on altered labels is the same fault.

``noise`` is the reference's own noising of clean ids from a seed's
generator, for the comparison with the pairs the program's noising made."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .trinity import _ffn, _mm, rms_norm

WEIGHT_FAULTS = {
    "weight_left_out": lambda w: (w > 0).astype(w.dtype),
    "unmasked_positions_counted": lambda w: (w + (w <= 0)).astype(w.dtype),
}
FAULTS = ("noisy_sees_own_clean_block", "positions_run_on") \
    + tuple(WEIGHT_FAULTS)
ATTN_BLOCK = 256        # query rows of attention rematerialised together
HEAD_BLOCK = 2048       # rows of the vocabulary head rematerialised together


def noise(ids, rng, block, mask_id, t_min):
    """``[x_t | x_0]`` and the loss weights, float32 [rows, 2 L], of clean ids
    [rows, L] under MDLM's linear schedule in blocks of ``block``: from
    ``rng``, first one ``t ~ U[t_min, 1)`` a block, row by row, then one
    uniform number a token; a token is masked where its number is below its
    block's ``t``, and weighs ``1 / t`` there."""
    rows, length = ids.shape
    t_block = rng.uniform(t_min, 1.0, size=(rows, -(-length // block)))
    draw = rng.random((rows, length))
    t = t_block[:, np.arange(length) // block]
    hit = draw < t
    noisy = np.where(hit, mask_id, ids)
    weight = np.concatenate([np.where(hit, 1.0 / t, 0.0),
                             np.zeros((rows, length))], axis=1)
    return (np.concatenate([noisy, ids], axis=1).astype(np.float32),
            weight.astype(np.float32))


def param_shapes(cfg):
    """{name: shape} of every parameter under the program's names
    (``mxnet_tpu/models/sdar.py``): matrices are [out, in] as
    ``FullyConnected`` keeps them, expert stacks [held, in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    held, mid = cfg["num_experts"], cfg["moe_intermediate_size"]
    s = {"embed_weight": (v, h), "final_norm_gamma": (h,),
         "lm_head_weight": (v, h)}
    for l in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % l
        s[p + "input_norm_gamma"] = s[p + "post_attn_norm_gamma"] = (h,)
        s[p + "attn_q_proj_weight"] = (heads * d, h)
        s[p + "attn_k_proj_weight"] = s[p + "attn_v_proj_weight"] = (kv * d, h)
        s[p + "attn_q_norm_gamma"] = s[p + "attn_k_norm_gamma"] = (d,)
        s[p + "attn_o_proj_weight"] = (h, heads * d)
        s[p + "moe_router_weight"] = (cfg["router_num_experts"], h)
        s[p + "moe_gate_weight"] = s[p + "moe_up_weight"] = (held, h, mid)
        s[p + "moe_down_weight"] = (held, mid, h)
    return s


def rotary(x, theta, fault=None):
    """Rotate-half positions on every dim of [batch, 2 L, heads, d], both
    halves from position 0."""
    s, d = x.shape[1], x.shape[-1]
    pos = jnp.arange(s, dtype=jnp.float32)
    if fault != "positions_run_on":
        pos = jnp.where(pos < s // 2, pos, pos - s // 2)
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def visible(rows, cols, half, block, fault=None):
    """The block-diffusion mask of query positions ``rows`` [n, 1] against
    key positions ``cols`` [1, m] of a sequence of ``2 * half``."""
    q_noisy, k_noisy = rows < half, cols < half
    qb = jnp.where(q_noisy, rows, rows - half) // block
    kb = jnp.where(k_noisy, cols, cols - half) // block
    offset = kb <= qb if fault == "noisy_sees_own_clean_block" else kb < qb
    return jnp.where(q_noisy, jnp.where(k_noisy, qb == kb, offset),
                     ~k_noisy & (kb <= qb))


def masked_attention(q, k, v, block, hooks, fault=None):
    """softmax(q k^T / sqrt(d) + M) v for q [batch, 2 L, kv heads, group, d]
    on k, v [batch, 2 L, kv heads, d] under the block-diffusion mask.  Query
    rows go ``ATTN_BLOCK`` at a time, each block against all keys and
    rematerialised, so that no (2 L) x (2 L) array is ever whole."""
    operand, cotangent = hooks
    b, s, kv, group, d = q.shape
    pad = (-s) % ATTN_BLOCK
    blocks = jnp.pad(q, ((0, 0), (0, pad)) + ((0, 0),) * 3).reshape(
        b, -1, ATTN_BLOCK, kv, group, d)
    cols = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(item):
        qb, start = item
        scores = cotangent(jnp.einsum(
            "bqhgd,bkhd->bhgqk", operand(qb), operand(k),
            precision=lax.Precision.HIGHEST)) / jnp.sqrt(jnp.float32(d))
        rows = (start + jnp.arange(ATTN_BLOCK))[:, None]
        seen = visible(rows, cols, s // 2, block, fault)
        # a finite floor: a padded row may see no key, and -inf there would
        # turn its (dropped) softmax into NaN
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return cotangent(jnp.einsum(
            "bhgqk,bkhd->bqhgd", operand(probs), operand(v),
            precision=lax.Precision.HIGHEST))

    out = lax.map(one, (jnp.moveaxis(blocks, 1, 0),
                        jnp.arange(blocks.shape[1]) * ATTN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, kv * group * d)[:, :s]


def attention(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _mm(x, p["attn_q_proj_weight"].T, hooks).reshape(b, s, heads, d)
    k = _mm(x, p["attn_k_proj_weight"].T, hooks).reshape(b, s, kv, d)
    v = _mm(x, p["attn_v_proj_weight"].T, hooks).reshape(b, s, kv, d)
    q = rotary(rms_norm(q, p["attn_q_norm_gamma"], eps), cfg["rope_theta"],
               fault)
    k = rotary(rms_norm(k, p["attn_k_norm_gamma"], eps), cfg["rope_theta"],
               fault)
    a = masked_attention(q.reshape(b, s, kv, heads // kv, d), k, v,
                         cfg["block_length"], hooks, fault)
    return _mm(a, p["attn_o_proj_weight"].T, hooks)


def routed_weights(x, p, cfg):
    """[tokens, held] weight of each held expert for each token (0 where it
    was not among the token's top-k)."""
    held, first = cfg["num_experts"], cfg.get("first_expert", 0)
    probs = jax.nn.softmax(jnp.matmul(x, p["moe_router_weight"].T,
                                      precision=lax.Precision.HIGHEST), -1)
    top_p, top_e = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    local = top_e - first
    hot = (local[..., None] == jnp.arange(held)) \
        & ((local >= 0) & (local < held))[..., None]
    return jnp.sum(jnp.where(hot, top_p[..., None], 0.0), axis=1)


def moe(x, p, cfg, hooks):
    b, s, h = x.shape
    x = x.reshape(b * s, h)
    weights = routed_weights(x, p, cfg)

    @jax.checkpoint
    def expert(w_col, gate, up, down):
        return w_col[:, None] * _ffn(x, gate, up, down, hooks)

    def body(acc, item):
        return acc + expert(*item), None

    out, _ = lax.scan(body, jnp.zeros_like(x),
                      (weights.T, p["moe_gate_weight"], p["moe_up_weight"],
                       p["moe_down_weight"]))
    return out.reshape(b, s, h)


def _layer(x, p, cfg, hooks, fault):
    eps = cfg["rms_norm_eps"]
    h = x + attention(rms_norm(x, p["input_norm_gamma"], eps), p, cfg, hooks,
                      fault)
    return h + moe(rms_norm(h, p["post_attn_norm_gamma"], eps), p, cfg,
                   hooks)


def hidden(params, tokens, cfg, hooks, fault=None):
    """[batch, 2 L, hidden] after the final norm."""
    x = params["embed_weight"][tokens.astype(jnp.int32)]
    for l in range(cfg["num_hidden_layers"]):
        prefix = "layer%d_" % l
        sub = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, hooks=hooks, fault=fault))(x, sub)
    return rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"])


def loss_fn(params, tokens, labels, cfg, hooks=None, fault=None):
    """Mean over the batch of each sequence's weighted cross-entropy over its
    noisy half (``tokens`` = [x_t | x_0], ``labels`` = the weights); the
    head and its softmax go ``HEAD_BLOCK`` rows at a time."""
    hooks = hooks or (lambda a: a, lambda a: a)
    b, s = tokens.shape
    half = s // 2
    x = hidden(params, tokens, cfg, hooks, fault)[:, :half]
    weight = labels[:, :half]
    if fault in WEIGHT_FAULTS:
        weight = WEIGHT_FAULTS[fault](weight)
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    pad = (-n) % HEAD_BLOCK
    rows = jnp.pad(rows, ((0, pad), (0, 0))).reshape(-1, HEAD_BLOCK,
                                                     rows.shape[-1])
    ids = jnp.pad(tokens[:, half:].reshape(-1).astype(jnp.int32),
                  (0, pad)).reshape(-1, HEAD_BLOCK)
    w = jnp.pad(weight.reshape(-1), (0, pad)).reshape(-1, HEAD_BLOCK)

    @jax.checkpoint
    def head(item):
        r, i, wi = item
        logp = jax.nn.log_softmax(_mm(r, params["lm_head_weight"].T, hooks),
                                  axis=-1)
        picked = jnp.take_along_axis(logp, i[:, None], axis=-1)[:, 0]
        return -jnp.sum(wi * picked)

    return jnp.sum(lax.map(head, (rows, ids, w))) / n


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

"""Plain reference: one chip's share of Granite 4.0-H Micro (``model_type:
granitemoehybrid``; https://huggingface.co/ibm-granite/granite-4.0-h-micro)
with its next-token cross-entropy, gradients and MXNet's Adam step, in
straightforward ``jax.numpy`` float32 at ``highest`` matmul precision.

Every block ``l`` is ``h = x + m Mixer_l(N(x)); y = h + m MLP(N(h))`` with
``m = residual_multiplier`` and the plain RMSNorm ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``.  Where ``layer_types[l]`` is ``"mamba"`` the
mixer is Mamba-2: ``[z | xBC | dt] = W_in u``; ``xBC = SiLU(b + sum_j w_j
xBC_{t-3+j})`` (causal, depth-wise, zeros before the sequence); ``[x | B |
C] = xBC``; ``delta = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
``h`` the state ``S [P, N]`` from zero takes ``S_t = exp(delta_t A_h)
S_{t-1} + delta_t x_t B_t^T`` and gives ``y_t = S_t C_t + D_h x_t``,
computed TOKEN BY TOKEN with ``lax.scan``, so that it shares nothing with the
program's chunked form (B and C of group ``g`` serve heads ``g r .. (g + 1)
r``); ``out = W_out (N(y * SiLU(z)) over the whole inner width)``.  Where
``"attention"``: GQA with no positional encoding, ``softmax(q k^T *
attention_multiplier + causal) v``, the scores computed against ALL keys
and masked.  ``MLP(u) = W_out (SiLU(u W_gate) * u W_up)``, ``[gate | up]``
one matrix.  The embedding is scaled by ``embedding_multiplier``; the head is
THE embedding (tied) and the logits are divided by ``logits_scaling``.

It imports nothing of the program and takes nothing the program made.  The
vocabulary is the slice ``vocab_size`` gives; ``layers_kept`` names the
published layers the blocks stand for (kinds read from the whole published
``layer_types``).

Departures from the published model, each on purpose: no dropout; no
``time_step_limit`` clamp (HF's default, (0, inf), clamps nothing).
``jax.checkpoint`` round each block, each 64 tokens of the recurrence, each
512 query rows and each 2,048 rows of the head changes memory, not the
mathematics.

``hooks = (operand, cotangent)`` lets the CONTROL recompute the same network
with every matrix product in a lower precision (references/lowprec.py);
``fault`` plants one of the mistakes the tests must catch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

FAULTS = ("skip_left_out", "gate_after_norm", "conv_bias_left_out",
          "residual_multiplier_left_out", "attention_scaled_by_sqrt_d")
SCAN_BLOCK = 64         # tokens of the recurrence rematerialised together
ATTN_BLOCK = 512        # query rows of attention rematerialised together
HEAD_BLOCK = 2048       # rows of the vocabulary head rematerialised together
MAMBA, ATTENTION = "mamba", "attention"


def layer_kinds(cfg):
    kept = cfg.get("layers_kept") or range(cfg["num_hidden_layers"])
    return [cfg["layer_types"][i] for i in kept]


def _head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // \
        cfg["num_attention_heads"]


def param_shapes(cfg):
    """{name: shape} of every parameter under the program's names
    (``mxnet_tpu/models/granite_hybrid.py``): matrices are [out, in] as
    ``FullyConnected`` keeps them."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    _head_dim(cfg))
    mh, mp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    inner = mh * mp
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    wide = cfg["shared_intermediate_size"]
    s = {"embed_weight": (v, h), "final_norm_gamma": (h,)}
    for l, kind in enumerate(layer_kinds(cfg)):
        p = "layer%d_" % l
        s[p + "input_norm_gamma"] = s[p + "post_norm_gamma"] = (h,)
        if kind == MAMBA:
            s[p + "mamba_in_proj_weight"] = (inner + conv + mh, h)
            s[p + "mamba_conv_weight"] = (conv, cfg["mamba_d_conv"])
            s[p + "mamba_conv_bias"] = (conv,)
            s[p + "mamba_A_log"] = s[p + "mamba_dt_bias"] = (mh,)
            s[p + "mamba_D"] = (mh,)
            s[p + "mamba_norm_gamma"] = (inner,)
            s[p + "mamba_out_proj_weight"] = (h, inner)
        else:
            s[p + "attn_q_proj_weight"] = (heads * d, h)
            s[p + "attn_k_proj_weight"] = s[p + "attn_v_proj_weight"] \
                = (kv * d, h)
            s[p + "attn_o_proj_weight"] = (h, heads * d)
        s[p + "mlp_input_linear_weight"] = (2 * wide, h)
        s[p + "mlp_output_linear_weight"] = (h, wide)
    return s


def _mm(x, w, hooks):
    """``x @ w`` as the hooks' precision reads the operands."""
    operand, cotangent = hooks
    return cotangent(jnp.matmul(operand(x), operand(w),
                                precision=lax.Precision.HIGHEST))


def rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def state_space(x, B, C, delta, A):
    """Token by token.  x: [batch, seq, heads, P]; B, C: [batch, seq,
    heads, N] (each head's group's); delta: [batch, seq, heads]; A:
    [heads].  State [batch, heads, P, N] from zero; returns y without the
    skip."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % SCAN_BLOCK
    seq_first = lambda t: jnp.pad(
        jnp.moveaxis(t, 1, 0), ((0, pad),) + ((0, 0),) * (t.ndim - 1))
    xs = tuple(seq_first(t).reshape((-1, SCAN_BLOCK) + seq_first(t).shape[1:])
               for t in (x, B, C, delta))

    def token(state, item):
        x_t, b_t, c_t, d_t = item
        state = state * jnp.exp(d_t * A)[..., None, None] \
            + (d_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def block(state, item):
        return lax.scan(token, state, item)

    # padded tokens come after the real ones: what they do to the state is
    # never read
    _, out = lax.scan(block, jnp.zeros((b, h, p, n), jnp.float32), xs)
    return jnp.moveaxis(out.reshape((-1,) + out.shape[2:])[:s], 0, 1)


def mamba(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    heads, width = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    state, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    inner = heads * width
    conv = inner + 2 * groups * state
    proj = _mm(x, p["mamba_in_proj_weight"].T, hooks)
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + conv],
                  proj[..., inner + conv:])
    taps = cfg["mamba_d_conv"]
    w = p["mamba_conv_weight"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = sum(padded[:, j:j + s] * w[:, j] for j in range(taps))
    if fault != "conv_bias_left_out":
        xbc = xbc + p["mamba_conv_bias"]
    xbc = jax.nn.silu(xbc)
    xh = xbc[..., :inner].reshape(b, s, heads, width)
    per_head = lambda t: jnp.repeat(t.reshape(b, s, groups, state),
                                    heads // groups, axis=2)
    B = per_head(xbc[..., inner:inner + groups * state])
    C = per_head(xbc[..., inner + groups * state:])
    delta = jax.nn.softplus(dt + p["mamba_dt_bias"])
    y = state_space(xh, B, C, delta, -jnp.exp(p["mamba_A_log"]))
    if fault != "skip_left_out":
        y = y + p["mamba_D"][:, None] * xh
    y = y.reshape(b, s, inner)
    eps = cfg["rms_norm_eps"]
    if fault == "gate_after_norm":
        y = rms_norm(y, p["mamba_norm_gamma"], eps) * jax.nn.silu(z)
    else:
        y = rms_norm(y * jax.nn.silu(z), p["mamba_norm_gamma"], eps)
    return _mm(y, p["mamba_out_proj_weight"].T, hooks)


def causal_attention(q, k, v, scale, hooks):
    """softmax(q k^T * scale + causal) v for q [batch, seq, kv heads, group,
    d] on k, v [batch, seq, kv heads, d].  Query rows go ``ATTN_BLOCK`` at a
    time, each block against all keys and rematerialised, so that no seq x
    seq array is ever whole."""
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
            precision=lax.Precision.HIGHEST)) * scale
        rows = start + jnp.arange(ATTN_BLOCK)
        probs = jax.nn.softmax(jnp.where(rows[:, None] >= cols[None, :],
                                         scores, -jnp.inf), axis=-1)
        return cotangent(jnp.einsum(
            "bhgqk,bkhd->bqhgd", operand(probs), operand(v),
            precision=lax.Precision.HIGHEST))

    out = lax.map(one, (jnp.moveaxis(blocks, 1, 0),
                        jnp.arange(blocks.shape[1]) * ATTN_BLOCK))
    return jnp.moveaxis(out, 0, 1).reshape(b, -1, kv * group * d)[:, :s]


def attention(x, p, cfg, hooks, fault=None):
    b, s, _ = x.shape
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    _head_dim(cfg))
    q = _mm(x, p["attn_q_proj_weight"].T, hooks).reshape(b, s, kv,
                                                          heads // kv, d)
    k = _mm(x, p["attn_k_proj_weight"].T, hooks).reshape(b, s, kv, d)
    v = _mm(x, p["attn_v_proj_weight"].T, hooks).reshape(b, s, kv, d)
    scale = d ** -0.5 if fault == "attention_scaled_by_sqrt_d" \
        else cfg["attention_multiplier"]
    a = causal_attention(q, k, v, scale, hooks)
    return _mm(a, p["attn_o_proj_weight"].T, hooks)


def mlp(x, p, cfg, hooks):
    wide = cfg["shared_intermediate_size"]
    both = _mm(x, p["mlp_input_linear_weight"].T, hooks)
    return _mm(jax.nn.silu(both[..., :wide]) * both[..., wide:],
               p["mlp_output_linear_weight"].T, hooks)


def _layer(x, p, cfg, kind, hooks, fault):
    eps = cfg["rms_norm_eps"]
    m = 1.0 if fault == "residual_multiplier_left_out" \
        else cfg["residual_multiplier"]
    mixer = mamba if kind == MAMBA else attention
    h = x + m * mixer(rms_norm(x, p["input_norm_gamma"], eps), p, cfg, hooks,
                      fault)
    return h + m * mlp(rms_norm(h, p["post_norm_gamma"], eps), p, cfg, hooks)


def hidden(params, tokens, cfg, hooks, fault=None):
    """[batch, seq, hidden] after the final norm."""
    x = params["embed_weight"][tokens.astype(jnp.int32)] \
        * cfg["embedding_multiplier"]
    for l, kind in enumerate(layer_kinds(cfg)):
        prefix = "layer%d_" % l
        sub = {k[len(prefix):]: v for k, v in params.items()
               if k.startswith(prefix)}
        x = jax.checkpoint(functools.partial(
            _layer, cfg=cfg, kind=kind, hooks=hooks, fault=fault))(x, sub)
    return rms_norm(x, params["final_norm_gamma"], cfg["rms_norm_eps"])


def _head(rows, params, cfg, hooks):
    return _mm(rows, params["embed_weight"].T, hooks) / cfg["logits_scaling"]


def logits(params, tokens, cfg, hooks=None, fault=None):
    """[batch, seq, vocab] float32 for integer ``tokens`` [batch, seq]."""
    hooks = hooks or (lambda a: a, lambda a: a)
    return _head(hidden(params, tokens, cfg, hooks, fault), params, cfg,
                 hooks)


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
        logp = jax.nn.log_softmax(_head(r, params, cfg, hooks), axis=-1)
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

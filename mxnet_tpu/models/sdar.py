"""SDAR (``model_type: sdar_moe``; JetLM SDAR-30B-A3B) as a Symbol trained
with its block-diffusion objective (docs/sdar.md).

The block is Qwen3-MoE's, ``num_hidden_layers`` of them alike, with plain
RMSNorm (weights 1, not zero-centred)::

    h = x + W_o Attn(RoPE(N_q(W_q N1(x))), RoPE(N_k(W_k N1(x))), W_v N1(x))
    y = h + sum_{e in top-k(softmax(W_r N2(h)))} p_e / sum_top-k p * F_e(N2(h))

``N_q`` and ``N_k`` are RMSNorms over each head's ``head_dim``, before the
rotate-half rotary positions on all of its dims; ``F_e`` is a SwiGLU expert
of ``moe_intermediate_size``; no shared expert.

The objective (BD3-LM's, Arriola et al. 2025): a clean sequence ``x_0`` of
``L`` tokens in blocks of ``B``, each block's tokens replaced by the mask id
with a probability ``t_b ~ U[t_min, 1]`` of its own (``noise``), gives
``x_t``; the model reads the ``2 L`` positions ``[x_t | x_0]``, both halves
at positions ``0 .. L - 1``, under the block-diffusion mask (a noisy query
sees its own block's noisy keys and the clean keys of the blocks before it; a
clean query the clean keys of its block and those before), and the loss is
``(1 / L) sum_b sum_{i in b, masked} (1 / t_b) CE(head(N(h_L[i])),
x_0[i])`` over the noisy half alone: the clean half's logits are never
formed.

Inputs: ``data`` [batch, 2 L] is ``[x_t | x_0]``, and ``softmax_label``
[batch, 2 L] carries each position's loss weight, ``1 / t_b`` at a masked
noisy position and 0 elsewhere (the targets are ``data``'s clean half).
``cfg`` holds the published config's keys; ``num_experts`` counts the
experts HELD by this chip (``first_expert`` onwards) of the
``router_num_experts`` the router scores, ``vocab_size`` is the slice of the
vocabulary held, ``block_length`` is ``B``.  Outputs: the loss, one number
a sequence; the expert layers' selection counts; and the masked and noisy
positions of each sequence for the counters ``module.bd.masked_positions`` /
``module.bd.noisy_positions``.  Each half of a block is one
``__mirror_stage__``.
"""
from __future__ import annotations

import numpy as np

from .. import symbol as sym
from ._lm import LMBuilder

BD_COUNTERS = ("module.bd.masked_positions", "module.bd.noisy_positions")


def noise(ids, rng, block_length, mask_id, t_min):
    """``(data, weight)``, each float32 [rows, 2 L], from clean token ids
    [rows, L] and a numpy ``Generator``: every block of ``block_length``
    tokens of a row draws ``t ~ U[t_min, 1]``, and each of its tokens becomes
    ``mask_id`` with probability ``t``.  ``data`` is ``[x_t | x_0]``;
    ``weight`` is ``1 / t`` at the masked positions of the noisy half and 0
    everywhere else (MDLM's linear schedule, whose ELBO weighs a masked
    token by ``1 / t``)."""
    ids = np.asarray(ids)
    rows, length = ids.shape
    blocks = -(-length // int(block_length))
    t = rng.uniform(float(t_min), 1.0, size=(rows, blocks))
    t = np.repeat(t, int(block_length), axis=1)[:, :length]
    masked = rng.random((rows, length)) < t
    data = np.concatenate([np.where(masked, int(mask_id), ids), ids], axis=1)
    weight = np.zeros((rows, 2 * length), np.float32)
    weight[:, :length] = np.where(masked, 1.0 / t, 0.0)
    return data.astype(np.float32), weight


class _Builder(LMBuilder):
    def norm(self, x, name):
        return super().norm(x, name, zero_centered=False)

    def heads(self, x, name, count, normed=True):
        """The projection ``name`` of ``x`` as [batch, seq, count, head_dim],
        each head RMS-normed over its own dims (Qwen3's QK-norm) unless not
        ``normed``."""
        d = int(self.cfg["head_dim"])
        out = sym.Reshape(self.dense(x, name, count * d),
                          shape=(0, 0, count, d))
        return self.norm(out, name.replace("_proj", "_norm")) if normed \
            else out

    def attention(self, x, p):
        cfg = self.cfg
        heads, kv = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
        # both halves of the sequence take positions 0 .. L - 1
        rope = dict(rotary_dim=int(cfg["head_dim"]),
                    base=float(cfg["rope_theta"]), segments=2)
        q = sym.rotary_embedding(self.heads(x, p + "q_proj", heads), **rope)
        k = sym.rotary_embedding(self.heads(x, p + "k_proj", kv), **rope)
        v = self.heads(x, p + "v_proj", kv, normed=False)
        a = sym.scaled_dot_product_attention(
            q, k, v, block_diffusion=int(cfg["block_length"]),
            name=p + "sdpa")
        return self.dense(
            sym.Reshape(a, shape=(0, 0, heads * int(cfg["head_dim"]))),
            p + "o_proj", cfg["hidden_size"])

    def block(self, x, layer, recompute):
        """(the block's output, its expert layer's counts).  Each half is one
        mirror stage."""
        p = "layer%d_" % layer
        with self.stage(p + "attn", recompute):
            h = x + self.attention(self.norm(x, p + "input_norm"), p + "attn_")
        with self.stage(p + "mlp", recompute):
            routed = self.routed_experts(
                self.norm(h, p + "post_attn_norm"), p,
                score_func="softmax",
                norm_topk_prob=bool(self.cfg["norm_topk_prob"]))
            return h + sym.reshape_like(routed[0], h), routed[1]

    def diffusion_loss(self, x, data):
        """(the loss [batch], its masked and noisy positions [batch] each)
        from the final norm's output ``x`` over both halves: the head reads
        the noisy half, against ``data``'s clean half, weighted by the noisy
        half of ``softmax_label``."""
        weight = sym.split(self.label(), num_outputs=2, axis=1)[0]
        with self.named("mx:head"):
            logits = self.dense(sym.split(x, num_outputs=2, axis=1)[0],
                                "lm_head", self.cfg["vocab_size"])
            loss = sym.sequence_cross_entropy(
                logits, sym.split(data, num_outputs=2, axis=1)[1], weight,
                use_weight=True, name="ce")
        return loss, sym.sum(weight > 0, axis=1), sym.sum(
            sym.ones_like(weight), axis=1)


def get_symbol(cfg, dtype="float32", recompute=True):
    """``Group([loss, expert selection counts, masked and noisy positions])``
    over ``data`` [batch, 2 L] (``[x_t | x_0]``, whole ids) and
    ``softmax_label`` [batch, 2 L] (each position's loss weight)."""
    if cfg.get("mlp_only_layers") or int(cfg.get("decoder_sparse_step",
                                                 1)) != 1:
        raise ValueError("sdar: every layer is an expert layer here "
                         "(decoder_sparse_step 1, no mlp_only_layers)")
    build = _Builder(cfg, dtype)
    data = sym.Variable("data")
    x = build.embed(data)
    counts = []
    for layer in range(int(cfg["num_hidden_layers"])):
        x, c = build.block(x, layer, recompute)
        counts.append(c)
    loss, masked, noisy = build.diffusion_loss(build.norm(x, "final_norm"),
                                               data)
    return sym.Group([sym.MakeLoss(loss, name="loss"),
                      build.moe_counts(counts),
                      build.counter_rows((masked, noisy), BD_COUNTERS,
                                         "bd_counts")])

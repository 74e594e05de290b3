"""Qwen3-Next as a Symbol (``model_type: qwen3_next``; docs/qwen3_next.md).

``num_hidden_layers`` blocks ``h = x + Mixer(N(x)); y = h + MoE(N(h))``: the
mixer is gated GQA attention in every ``full_attention_interval``-th block
and Gated DeltaNet in the others, every block ends in a top-k expert layer
with one shared expert, all norms are zero-centred RMSNorm.  The symbol's
first output is the next-token cross-entropy, one number a sequence (the
mean over its positions, float32) under ``MakeLoss``; the second, without
gradient, the per-expert selection counts of every block, which
``Module.update_metric`` hands to the ``module.moe.*`` counters.

``cfg`` holds the published config's keys.  ``num_experts`` counts the
experts HELD by this chip (``first_expert`` onwards) of the
``router_num_experts`` the router scores; ``vocab_size`` is the slice of the
vocabulary held.  The nodes of each half of a block (mixer, expert layer)
carry one ``__mirror_stage__``, so the executor recomputes that half in the
backward pass (``recompute=False``: none does).
"""
from __future__ import annotations

from .. import symbol as sym
from ._lm import LMBuilder


def is_attention(cfg, layer):
    return (layer + 1) % int(cfg["full_attention_interval"]) == 0


class _Builder(LMBuilder):
    def attention(self, x, p):
        cfg = self.cfg
        heads, kv, d = (int(cfg["num_attention_heads"]),
                        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
        rope = dict(rotary_dim=int(d * float(cfg["partial_rotary_factor"])),
                    base=float(cfg["rope_theta"]))
        qg = sym.Reshape(self.dense(x, p + "q_proj", heads * 2 * d),
                         shape=(0, 0, heads, 2 * d))
        q = sym.slice_axis(qg, axis=3, begin=0, end=d)
        gate = sym.Reshape(sym.slice_axis(qg, axis=3, begin=d, end=2 * d),
                           shape=(0, 0, heads * d))
        k = sym.Reshape(self.dense(x, p + "k_proj", kv * d),
                        shape=(0, 0, kv, d))
        v = sym.Reshape(self.dense(x, p + "v_proj", kv * d),
                        shape=(0, 0, kv, d))
        q = sym.rotary_embedding(self.norm(q, p + "q_norm"), **rope)
        k = sym.rotary_embedding(self.norm(k, p + "k_norm"), **rope)
        a = sym.scaled_dot_product_attention(q, k, v, causal=True,
                                             name=p + "sdpa")
        a = sym.Reshape(a, shape=(0, 0, heads * d)) * sym.sigmoid(gate)
        return self.dense(a, p + "o_proj", cfg["hidden_size"])

    def delta_net(self, x, p):
        cfg = self.cfg
        hk, hv = (int(cfg["linear_num_key_heads"]),
                  int(cfg["linear_num_value_heads"]))
        dk, dv = (int(cfg["linear_key_head_dim"]),
                  int(cfg["linear_value_head_dim"]))
        kd, vd = hk * dk, hv * dv
        qkvz = self.dense(x, p + "in_proj_qkvz", 2 * kd + 2 * vd)
        ba = self.dense(x, p + "in_proj_ba", 2 * hv)
        mixed = sym.causal_conv1d(
            sym.slice_axis(qkvz, axis=2, begin=0, end=2 * kd + vd),
            weight=self.param(p + "conv_weight"),
            kernel=int(cfg["linear_conv_kernel_dim"]), activation="silu",
            name=p + "conv")
        z = sym.slice_axis(qkvz, axis=2, begin=2 * kd + vd,
                           end=2 * kd + 2 * vd)
        heads = lambda s, lo, hi, n, d: sym.Reshape(
            sym.slice_axis(s, axis=2, begin=lo, end=hi), shape=(0, 0, n, d))
        o = sym.gated_delta_rule(
            heads(mixed, 0, kd, hk, dk), heads(mixed, kd, 2 * kd, hk, dk),
            heads(mixed, 2 * kd, 2 * kd + vd, hv, dv),
            sym.slice_axis(ba, axis=2, begin=hv, end=2 * hv),
            sym.slice_axis(ba, axis=2, begin=0, end=hv),
            A_log=self.param(p + "A_log"), dt_bias=self.param(p + "dt_bias"),
            name=p + "rule")
        o = self.norm(o, p + "norm", zero_centered=False)
        o = sym.SwiGLU(z, sym.Reshape(o, shape=(0, 0, vd)))
        return self.dense(o, p + "out_proj", cfg["hidden_size"])

    def moe(self, x, p):
        """(the layer's output, its per-expert selection counts)."""
        cfg = self.cfg
        routed = self.routed_experts(
            x, p, norm_topk_prob=bool(cfg["norm_topk_prob"]))
        with self.named("mx:moe:shared"):
            shared = self.swiglu_mlp(
                x, p + "shared_", int(cfg["shared_expert_intermediate_size"]))
            shared = sym.broadcast_mul(
                sym.sigmoid(self.dense(x, p + "shared_gate", 1)), shared)
        return sym.reshape_like(routed[0], x) + shared, routed[1]

    def block(self, x, layer, recompute):
        """Each half of a block is one mirror stage: the backward pass keeps
        the block's input and ``h`` and recomputes either half alone."""
        p = "layer%d_" % layer
        stage = lambda half: self.stage(p + half, recompute)
        with stage("mixer"):
            mix = self.attention if is_attention(self.cfg, layer) \
                else self.delta_net
            h = x + mix(self.norm(x, p + "input_norm"),
                        p + ("attn_" if is_attention(self.cfg, layer)
                             else "gdn_"))
        with stage("moe"):
            out, counts = self.moe(self.norm(h, p + "post_norm"), p)
            return h + out, counts


def get_symbol(cfg, dtype="float32", recompute=True):
    """``Group([loss, expert selection counts])`` over ``data`` [batch, seq]
    token ids and ``softmax_label`` [batch, seq] next-token targets."""
    build = _Builder(cfg, dtype)
    x = build.embed(sym.Variable("data"))
    counts = []
    for layer in range(int(cfg["num_hidden_layers"])):
        x, c = build.block(x, layer, recompute)
        counts.append(c)
    return build.outputs(build.norm(x, "final_norm"), counts)

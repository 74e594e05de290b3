"""Granite 4.0-H (``model_type: granitemoehybrid`` without experts; IBM
Granite-4.0-H-Micro) as a Symbol (docs/granite_hybrid.md).

``num_hidden_layers`` blocks, each half scaled by ``residual_multiplier``::

    h = x + m Mixer_l(N(x));    y = h + m MLP(N(h))

with plain RMSNorm (weights 1, not zero-centred).  ``Mixer_l`` is, where
``layer_types[l]`` is ``"mamba"``, Mamba-2: ``[z | xBC | dt] = W_in u``,
``xBC = SiLU(conv1d(xBC) + b)`` (causal, depth-wise, ``mamba_d_conv``
taps), ``[x | B | C] = xBC``, the selective state space of ``lm_ops._ssd``
over ``mamba_n_heads`` heads of ``mamba_d_head`` with state
``mamba_d_state`` (``mamba_n_groups`` B/C groups), then ``W_out
RMSNorm(y * SiLU(z))`` over the whole inner width; where ``"attention"``,
GQA attention with no positional encoding at all and the softmax scale
``attention_multiplier``.  ``MLP`` is dense SwiGLU of
``shared_intermediate_size``, ``[gate | up]`` in one matrix as published.
The embedding's output is scaled by ``embedding_multiplier``; the head reads
THE embedding (``tie_word_embeddings``) and divides the logits by
``logits_scaling``.  The symbol's one output is the next-token
cross-entropy, one number a sequence, under ``MakeLoss``.

``cfg`` holds the published config's keys; ``vocab_size`` is the slice of
the vocabulary held and ``layers_kept``, where given, names the published
layers the ``num_hidden_layers`` blocks stand for (their kinds read from
``layer_types`` at those indices; else the first ``num_hidden_layers``).
Each half of a block is one ``__mirror_stage__``.
"""
from __future__ import annotations

from .. import symbol as sym
from ._lm import LMBuilder

MAMBA, ATTENTION = "mamba", "attention"
# tokens of the state-space recurrence's chunks: the op's choice, not the
# model's (``mamba_chunk_size`` is the published kernels'); 64 keeps a
# chunk's [64, 64] decayed matrices of eight heads and their stacked product
# in VMEM, and the states the backward keeps at 134 MB a layer in bfloat16
SSD_CHUNK = 64


def layer_kinds(cfg):
    """``layer_types`` of the blocks built, in order."""
    n = int(cfg["num_hidden_layers"])
    kept = cfg.get("layers_kept") or range(n)
    kinds = [cfg["layer_types"][int(i)] for i in kept]
    if len(kinds) != n or set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError("granite_hybrid: %d layers but kinds %s"
                         % (n, kinds))
    return kinds


class _Builder(LMBuilder):
    def norm(self, x, name):
        return super().norm(x, name, zero_centered=False)

    def attention(self, x, p):
        cfg = self.cfg
        heads, kv = (int(cfg["num_attention_heads"]),
                     int(cfg["num_key_value_heads"]))
        d = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
        split = lambda t, n: sym.Reshape(t, shape=(0, 0, n, d))
        a = sym.scaled_dot_product_attention(
            split(self.dense(x, p + "q_proj", heads * d), heads),
            split(self.dense(x, p + "k_proj", kv * d), kv),
            split(self.dense(x, p + "v_proj", kv * d), kv), causal=True,
            scale=float(cfg["attention_multiplier"]), name=p + "sdpa")
        return self.dense(sym.Reshape(a, shape=(0, 0, heads * d)),
                          p + "o_proj", cfg["hidden_size"])

    def mamba(self, x, p):
        cfg = self.cfg
        heads, width = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        state, groups = int(cfg["mamba_d_state"]), int(cfg["mamba_n_groups"])
        inner = heads * width
        conv = inner + 2 * groups * state
        proj = self.dense(x, p + "in_proj", inner + conv + heads)
        cut = lambda t, lo, hi: sym.slice_axis(t, axis=2, begin=lo, end=hi)
        xbc = sym.causal_conv1d(
            cut(proj, inner, inner + conv),
            weight=self.param(p + "conv_weight"),
            bias=self.param(p + "conv_bias"), use_bias=True,
            kernel=int(cfg["mamba_d_conv"]), activation="silu",
            name=p + "conv")
        split = lambda lo, hi, n, d: sym.Reshape(cut(xbc, lo, hi),
                                                 shape=(0, 0, n, d))
        y = sym.ssd(split(0, inner, heads, width),
                    split(inner, inner + groups * state, groups, state),
                    split(inner + groups * state, conv, groups, state),
                    cut(proj, inner + conv, inner + conv + heads),
                    A_log=self.param(p + "A_log"),
                    dt_bias=self.param(p + "dt_bias"),
                    D=self.param(p + "D"), chunk=SSD_CHUNK, name=p + "ssd")
        y = sym.SwiGLU(cut(proj, 0, inner),
                       sym.Reshape(y, shape=(0, 0, inner)))
        return self.dense(self.norm(y, p + "norm"), p + "out_proj",
                          cfg["hidden_size"])

    def mlp(self, x, p):
        """``W_out (SiLU(x W_gate) * x W_up)``, ``[gate | up]`` one
        matrix."""
        wide = int(self.cfg["shared_intermediate_size"])
        both = self.dense(x, p + "input_linear", 2 * wide)
        return self.dense(
            sym.SwiGLU(sym.slice_axis(both, axis=2, begin=0, end=wide),
                       sym.slice_axis(both, axis=2, begin=wide,
                                      end=2 * wide)),
            p + "output_linear", self.cfg["hidden_size"])

    def block(self, x, layer, kind, recompute):
        """Each half of a block is one mirror stage."""
        p = "layer%d_" % layer
        scale = float(self.cfg["residual_multiplier"])
        with self.stage(p + "mixer", recompute):
            u = self.norm(x, p + "input_norm")
            mix = self.mamba(u, p + "mamba_") if kind == MAMBA \
                else self.attention(u, p + "attn_")
            h = x + mix * scale
        with self.stage(p + "mlp", recompute):
            with self.named("mx:mlp"):
                out = self.mlp(self.norm(h, p + "post_norm"), p + "mlp_")
            return h + out * scale


def get_symbol(cfg, dtype="float32", recompute=True):
    """``Group([loss])`` over ``data`` [batch, seq] token ids and
    ``softmax_label`` [batch, seq] next-token targets."""
    build = _Builder(cfg, dtype)
    x = build.embed(sym.Variable("data")) * float(cfg["embedding_multiplier"])
    for layer, kind in enumerate(layer_kinds(cfg)):
        x = build.block(x, layer, kind, recompute)
    return build.outputs(build.norm(x, "final_norm"), None,
                         tied=bool(cfg["tie_word_embeddings"]),
                         divisor=float(cfg["logits_scaling"]))

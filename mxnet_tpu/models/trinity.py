"""Trinity (``model_type: afmoe``; Arcee Trinity-Mini / Nano) as a Symbol
(docs/trinity.md).

``num_hidden_layers`` blocks, each half normed before AND after::

    h = x + N2(Attn_l(N1(x)));    y = h + N4(MLP_l(N3(h)))

with plain RMSNorm (weights 1, not zero-centred).  ``Attn_l`` is gated GQA
attention with a per-head RMSNorm on q and k: where ``layer_types[l]`` is
``"sliding_attention"`` q and k take rotary positions and a query sees the
``sliding_window`` keys up to its own; where ``"full_attention"`` there is no
positional encoding at all and a query sees every key up to its own.
``MLP_l`` is a dense SwiGLU of ``intermediate_size`` in the first
``num_dense_layers`` blocks and, in the rest, a sigmoid-scored top-k expert
layer (``route_scale`` times the chosen scores normalised over the chosen;
the expert bias takes part in the choice alone) plus one ungated shared
expert.  The embedding is scaled by ``sqrt(hidden_size)`` under
``mup_enabled``.  Outputs as ``qwen3_next.get_symbol``: the next-token
cross-entropy, one number a sequence, and the expert layers' selection
counts (the dense blocks have no row).

``cfg`` holds the published config's keys; ``num_experts`` counts the experts
HELD by this chip (``first_expert`` onwards) of the ``router_num_experts`` the
router scores, ``vocab_size`` is the slice of the vocabulary held, and
``layers_kept``, where given, names the published layers the
``num_hidden_layers`` blocks stand for (their kinds are read from
``layer_types`` at those indices; else the first ``num_hidden_layers``).
Each half of a block is one ``__mirror_stage__``.
"""
from __future__ import annotations

from .. import symbol as sym
from ._lm import LMBuilder

WINDOW, FULL = "sliding_attention", "full_attention"


def layer_kinds(cfg):
    """``layer_types`` of the blocks built, in order."""
    n = int(cfg["num_hidden_layers"])
    kept = cfg.get("layers_kept") or range(n)
    kinds = [cfg["layer_types"][int(i)] for i in kept]
    if len(kinds) != n or set(kinds) - {WINDOW, FULL}:
        raise ValueError("trinity: %d layers but kinds %s" % (n, kinds))
    return kinds


class _Builder(LMBuilder):
    def norm(self, x, name):
        return super().norm(x, name, zero_centered=False)

    def attention(self, x, p, kind):
        cfg = self.cfg
        heads, kv, d = (int(cfg["num_attention_heads"]),
                        int(cfg["num_key_value_heads"]), int(cfg["head_dim"]))
        split = lambda t, n: sym.Reshape(t, shape=(0, 0, n, d))
        q = self.norm(split(self.dense(x, p + "q_proj", heads * d), heads),
                      p + "q_norm")
        k = self.norm(split(self.dense(x, p + "k_proj", kv * d), kv),
                      p + "k_norm")
        v = split(self.dense(x, p + "v_proj", kv * d), kv)
        gate = self.dense(x, p + "gate_proj", heads * d)
        window = 0
        if kind == WINDOW:
            rope = dict(rotary_dim=d, base=float(cfg["rope_theta"]))
            q = sym.rotary_embedding(q, **rope)
            k = sym.rotary_embedding(k, **rope)
            window = int(cfg["sliding_window"])
        a = sym.scaled_dot_product_attention(q, k, v, causal=True,
                                             window=window, name=p + "sdpa")
        a = sym.Reshape(a, shape=(0, 0, heads * d)) * sym.sigmoid(gate)
        return self.dense(a, p + "o_proj", cfg["hidden_size"])

    def moe(self, x, p):
        """(the layer's output, its per-expert selection counts)."""
        cfg = self.cfg
        routed = self.routed_experts(
            x, p, score_func=str(cfg["score_func"]),
            norm_topk_prob=bool(cfg["route_norm"]),
            route_scale=float(cfg["route_scale"]), use_expert_bias=True)
        width = int(cfg["moe_intermediate_size"]) \
            * int(cfg["num_shared_experts"])
        return (sym.reshape_like(routed[0], x)
                + self.shared_expert(x, p, width), routed[1])

    def block(self, x, layer, kind, recompute):
        """(the block's output, its counts or None).  Each half is one
        mirror stage."""
        p = "layer%d_" % layer
        with self.stage(p + "attn", recompute):
            h = x + self.norm(
                self.attention(self.norm(x, p + "input_norm"), p + "attn_",
                               kind), p + "post_attn_norm")
        with self.stage(p + "mlp", recompute):
            u = self.norm(h, p + "pre_mlp_norm")
            if layer < int(self.cfg.get("num_dense_layers", 0)):
                with self.named("mx:mlp"):
                    out, counts = self.swiglu_mlp(
                        u, p + "mlp_", int(self.cfg["intermediate_size"])), None
            else:
                out, counts = self.moe(u, p)
            return h + self.norm(out, p + "post_mlp_norm"), counts


def get_symbol(cfg, dtype="float32", recompute=True):
    """``Group([loss, expert selection counts])`` over ``data`` [batch, seq]
    token ids and ``softmax_label`` [batch, seq] next-token targets."""
    build = _Builder(cfg, dtype)
    x = build.embed(sym.Variable("data"))
    if cfg.get("mup_enabled"):
        x = x * float(cfg["hidden_size"]) ** 0.5
    counts = []
    for layer, kind in enumerate(layer_kinds(cfg)):
        x, c = build.block(x, layer, kind, recompute)
        if c is not None:
            counts.append(c)
    return build.outputs(build.norm(x, "final_norm"), counts)

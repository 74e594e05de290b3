"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``; DeepSeek-V3's block at
its own sizes) as a Symbol (docs/joyai_flash.md).

``num_hidden_layers`` pre-norm blocks with plain RMSNorm (weights 1, not
zero-centred)::

    h = x + MLA(N1(x));    y = h + FFN_l(N2(h))

``MLA`` is multi-head LATENT attention: queries and keys/values are projected
down, normed, and projected up again, ``c_q = N(W_dq u)``, ``q = W_uq c_q``
(heads of ``qk_nope_head_dim + qk_rope_head_dim``), ``[c_kv | k_r] = W_dkv
u``, ``[k_nope | v] = W_ukv N(c_kv)`` (heads of ``qk_nope_head_dim +
v_head_dim``); rotary positions (interleaved pairs under ``rope_interleave``)
go on the last ``qk_rope_head_dim`` columns of every head's q and on the ONE
``k_r``, which all heads share as the last columns of their key.  Scores are
``q . [k_nope | k_r] / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal;
values are ``v_head_dim`` wide, so the keys are wider than the values, and the
shared key part is handed to the attention node as it is, never copied a head.
``FFN_l`` is a dense SwiGLU of ``intermediate_size`` in the first
``first_k_dense_replace`` blocks and, in the rest, ``n_shared_experts``
ungated shared experts plus a sigmoid-scored top-k expert layer
(``routed_scaling_factor`` times the chosen scores normalised over the
chosen; the expert bias takes part in the choice alone: ``noaux_tc``).

``num_nextn_predict_layers`` (0 or 1) multi-token-prediction modules
(DeepSeek-V3 section 2.2): ``h'_i = W_eh [N(Emb(t_{i+1})) ; N(h_i)]`` with
``h`` the main model's normed output, one more whole block, its own final
norm, and the MAIN model's embedding and output matrix, read by both; its
cross-entropy is against ``t_{i+2}``.  With ``data`` = t_0.. and
``softmax_label`` = t_1.. the module embeds ``softmax_label`` and its targets
are ``softmax_label`` one position on.  The loss trained is ``L_main +
mtp_loss_weight * L_mtp``.

``cfg`` holds the published config's keys; ``n_routed_experts`` counts the
experts HELD by this chip (``first_expert`` onwards) of the
``router_num_experts`` the router scores, ``vocab_size`` is the slice of the
vocabulary held.  Outputs: the loss trained, one number a sequence; the
expert layers' selection counts (the module's layer last); and, with a
module, the two parts of the loss for the counters ``module.lm.loss_main`` /
``module.lm.loss_mtp``.  Each half of a block is one ``__mirror_stage__``.
"""
from __future__ import annotations

from .. import symbol as sym
from ._lm import LMBuilder

LOSS_COUNTERS = ("module.lm.loss_main", "module.lm.loss_mtp")


class _Builder(LMBuilder):
    def __init__(self, cfg, dtype):
        # ``routed_experts`` and ``outputs`` read the held count as
        # ``num_experts``
        super().__init__(dict(cfg, num_experts=cfg["n_routed_experts"]),
                         dtype)

    def norm(self, x, name):
        return super().norm(x, name, zero_centered=False)

    def attention(self, x, p):
        cfg = self.cfg
        heads, nope, rope, d_v = (int(cfg[k]) for k in (
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim"))
        latent = int(cfg["kv_lora_rank"])
        turn = dict(rotary_dim=rope, base=float(cfg["rope_theta"]),
                    interleaved=bool(cfg.get("rope_interleave")))
        cut = lambda t, axis, lo, hi: sym.slice_axis(  # noqa: E731
            t, axis=axis, begin=lo, end=hi)
        with self.named("mx:mla"):
            c_q = self.norm(self.dense(x, p + "q_a_proj", cfg["q_lora_rank"]),
                            p + "q_a_norm")
            q = sym.Reshape(
                self.dense(c_q, p + "q_b_proj", heads * (nope + rope)),
                shape=(0, 0, heads, nope + rope))
            q = sym.rotary_embedding(q, offset=nope, **turn)
            down = self.dense(x, p + "kv_a_proj", latent + rope)
            k_rope = sym.Reshape(sym.rotary_embedding(sym.Reshape(
                cut(down, 2, latent, latent + rope), shape=(0, 0, 1, rope)),
                **turn), shape=(0, 0, rope))
            up = sym.Reshape(
                self.dense(self.norm(cut(down, 2, 0, latent),
                                     p + "kv_a_norm"),
                           p + "kv_b_proj", heads * (nope + d_v)),
                shape=(0, 0, heads, nope + d_v))
            k_nope, v = cut(up, 3, 0, nope), cut(up, 3, nope, nope + d_v)
        a = sym.scaled_dot_product_attention(
            q, k_nope, v, key_shared=k_rope, causal=True,
            use_shared_key=True, name=p + "sdpa")
        return self.dense(sym.Reshape(a, shape=(0, 0, heads * d_v)),
                          p + "o_proj", cfg["hidden_size"])

    def moe(self, x, p):
        """(the layer's output, its per-expert selection counts)."""
        cfg = self.cfg
        routed = self.routed_experts(
            x, p, score_func=str(cfg["scoring_func"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            route_scale=float(cfg["routed_scaling_factor"]),
            use_expert_bias=True)
        width = int(cfg["moe_intermediate_size"]) * int(cfg["n_shared_experts"])
        return (sym.reshape_like(routed[0], x)
                + self.shared_expert(x, p, width), routed[1])

    def block(self, x, p, dense, recompute):
        """(the block's output, its counts or None).  Each half is one
        mirror stage."""
        with self.stage(p + "attn", recompute):
            h = x + self.attention(self.norm(x, p + "input_norm"), p + "attn_")
        with self.stage(p + "mlp", recompute):
            u = self.norm(h, p + "post_attn_norm")
            if dense:
                with self.named("mx:mlp"):
                    out, counts = self.swiglu_mlp(
                        u, p + "mlp_", int(self.cfg["intermediate_size"])), None
            else:
                out, counts = self.moe(u, p)
            return h + out, counts

    def mtp(self, x, recompute):
        """(the module's loss [batch], its expert layer's counts) from the
        main model's normed output ``x``."""
        with self.named("mx:mtp"):
            both = sym.concat(
                self.norm(self.embed(self.label(), "mtp_embed"),
                          "mtp_embed_norm"),
                self.norm(x, "mtp_hidden_norm"), dim=2)
            h = self.dense(both, "mtp_eh_proj", self.cfg["hidden_size"])
            h, counts = self.block(h, "mtp_", False, recompute)
            return self.token_loss(self.norm(h, "mtp_final_norm"), "mtp_",
                                   shift=1), counts


def get_symbol(cfg, dtype="float32", recompute=True):
    """``Group([loss, expert selection counts, loss parts])`` over ``data``
    [batch, seq] token ids and ``softmax_label`` [batch, seq] next-token
    targets (no third output where ``num_nextn_predict_layers`` is 0)."""
    modules = int(cfg.get("num_nextn_predict_layers", 0))
    if modules > 1:
        raise ValueError("joyai_flash: %d prediction modules; one is built"
                         % modules)
    build = _Builder(cfg, dtype)
    x = build.embed(sym.Variable("data"), "embed")
    counts = []
    for layer in range(int(cfg["num_hidden_layers"])):
        x, c = build.block(x, "layer%d_" % layer,
                           layer < int(cfg["first_k_dense_replace"]),
                           recompute)
        if c is not None:
            counts.append(c)
    x = build.norm(x, "final_norm")
    second = None
    if modules:
        loss, c = build.mtp(x, recompute)
        counts.append(c)
        second = (float(cfg["mtp_loss_weight"]), loss, LOSS_COUNTERS)
    return build.outputs(x, counts, second)

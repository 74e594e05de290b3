"""What the decoder language models share (``qwen3_next.py``, ``trinity.py``):
parameters in the storage dtype, bias-free projections over ``[batch, seq,
hidden]``, RMSNorm, a SwiGLU MLP, the expert layer's call, the mirror stage
of half a block, and the tail that turns the last hidden state into the
symbol's two outputs."""
from __future__ import annotations

from .. import symbol as sym
from ..executor import MIRROR_STAGE, NAMED_SCOPE


class LMBuilder:
    def __init__(self, cfg, dtype):
        self.cfg = cfg
        self.dtype = dtype
        self.eps = float(cfg["rms_norm_eps"])

    def param(self, name):
        return sym.var(name, dtype=self.dtype)

    def dense(self, x, name, width):
        return sym.FullyConnected(x, weight=self.param(name + "_weight"),
                                  num_hidden=int(width), no_bias=True,
                                  flatten=False, name=name)

    def norm(self, x, name, zero_centered=True):
        return sym.RMSNorm(x, gamma=self.param(name + "_gamma"), eps=self.eps,
                           zero_centered=zero_centered, name=name)

    def swiglu_mlp(self, x, p, width):
        """``W_down (SiLU(W_gate x) * W_up x)`` under the names ``p`` +
        ``gate_proj`` / ``up_proj`` / ``down_proj``."""
        return self.dense(
            sym.SwiGLU(self.dense(x, p + "gate_proj", width),
                       self.dense(x, p + "up_proj", width)),
            p + "down_proj", self.cfg["hidden_size"])

    def routed_experts(self, x, p, **routing):
        """The ``moe_experts`` node of layer prefix ``p`` over the tokens of
        ``x`` [batch, seq, hidden]: ``num_experts`` of the config counts the
        experts HELD from ``first_expert`` on, the router scores
        ``router_num_experts``.  Returns its two outputs, the first still
        [tokens, hidden]."""
        cfg = self.cfg
        flat = sym.Reshape(x, shape=(-3, 0))
        inputs = dict(router_weight=self.param(p + "moe_router_weight"),
                      gate_weight=self.param(p + "moe_gate_weight"),
                      up_weight=self.param(p + "moe_up_weight"),
                      down_weight=self.param(p + "moe_down_weight"))
        if routing.get("use_expert_bias"):
            inputs["expert_bias"] = self.param(p + "moe_expert_bias")
        return sym.moe_experts(
            flat, num_experts=int(cfg["router_num_experts"]),
            num_hidden=int(cfg["moe_intermediate_size"]),
            experts_held=int(cfg["num_experts"]),
            first_expert=int(cfg.get("first_expert", 0)),
            top_k=int(cfg["num_experts_per_tok"]), name=p + "moe",
            **inputs, **routing)

    @staticmethod
    def stage(name, recompute):
        """The scope of one mirror stage: the backward pass keeps what
        enters it and recomputes the rest (none where not ``recompute``)."""
        return sym.AttrScope(**({MIRROR_STAGE: name} if recompute else {}))

    @staticmethod
    def named(scope):
        """The nodes built inside are lowered under ``jax.named_scope``."""
        return sym.AttrScope(**{NAMED_SCOPE: scope})

    def outputs(self, x, counts):
        """``Group([loss, expert selection counts])`` from the last block's
        output: final norm, untied head, the next-token cross-entropy as one
        mean a sequence under ``MakeLoss``, and the layers' counts stacked
        without gradient and marked for ``Module.update_metric``."""
        cfg = self.cfg
        logits = self.dense(self.norm(x, "final_norm"), "lm_head",
                            cfg["vocab_size"])
        loss = sym.MakeLoss(sym.sequence_cross_entropy(
            logits, sym.Variable("softmax_label"), name="ce"), name="loss")
        counts = sym.BlockGrad(sym.stack(*counts, axis=0), name="moe_counts")
        counts._set_attr(__moe_counts__="%d,%d" % (
            int(cfg.get("first_expert", 0)), int(cfg["num_experts"])))
        return sym.Group([loss, counts])

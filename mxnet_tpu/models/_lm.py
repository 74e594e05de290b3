"""What the decoder language models share (``qwen3_next.py``, ``trinity.py``,
``joyai_flash.py``, ``sdar.py``, ``granite_hybrid.py``): parameters in the
storage dtype (one
variable a name, so that two nodes may read one parameter and its gradient is
the sum of both uses), bias-free projections over ``[batch, seq, hidden]``,
RMSNorm, a SwiGLU MLP, the expert layer's call, the mirror stage of half a
block, and the tail that turns the last hidden state (and, where a model has
one, a second loss head on the same embedding and output matrix) into the
symbol's outputs."""
from __future__ import annotations

from .. import symbol as sym
from ..executor import MIRROR_STAGE, NAMED_SCOPE


class LMBuilder:
    def __init__(self, cfg, dtype):
        self.cfg = cfg
        self.dtype = dtype
        self.eps = float(cfg["rms_norm_eps"])
        self._params = {}

    def param(self, name):
        """The one variable of this name: asked for twice, it is one
        parameter read by two nodes."""
        if name not in self._params:
            self._params[name] = sym.var(name, dtype=self.dtype)
        return self._params[name]

    def label(self):
        """``softmax_label``, one variable whoever reads it."""
        return self._params.setdefault("softmax_label",
                                       sym.Variable("softmax_label"))

    def dense(self, x, name, width, weight=None):
        """``x W^T`` as the node ``name``, with its own matrix ``name`` +
        ``_weight`` or, where ``weight`` names another node's, that one."""
        return sym.FullyConnected(
            x, weight=self.param((weight or name) + "_weight"),
            num_hidden=int(width), no_bias=True, flatten=False, name=name)

    def embed(self, ids, name="embed"):
        """The rows of THE embedding (``embed_weight``, whichever node
        asks) for the token ids ``ids``."""
        with self.named("mx:embed"):
            return sym.Embedding(ids, weight=self.param("embed_weight"),
                                 input_dim=int(self.cfg["vocab_size"]),
                                 output_dim=int(self.cfg["hidden_size"]),
                                 name=name)

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

    def shared_expert(self, x, p, width):
        """The expert every token takes, beside the routed ones: a SwiGLU
        MLP under ``p`` + ``shared_``."""
        with self.named("mx:moe:shared"):
            return self.swiglu_mlp(x, p + "shared_", width)

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
        """The nodes built inside are lowered under ``jax.named_scope``
        (inside another such scope, under ``outer/scope``)."""
        outer = sym.AttrScope.current().get(None).get(NAMED_SCOPE)
        return sym.AttrScope(
            **{NAMED_SCOPE: outer + "/" + scope if outer else scope})

    def token_loss(self, x, prefix="", shift=0, tied=False, divisor=1.0):
        """The cross-entropy, one mean a sequence, of the normed hidden
        state ``x`` through THE output matrix (``lm_head_weight``, whichever
        node asks; ``embed_weight`` where ``tied``) against
        ``softmax_label`` ``shift`` positions on: 0 is the next token, 1 the
        one after it.  The logits are divided by ``divisor`` where it is not
        1."""
        with self.named("mx:head"):
            logits = self.dense(x, prefix + "lm_head",
                                self.cfg["vocab_size"],
                                weight="embed" if tied else "lm_head")
            if float(divisor) != 1.0:
                logits = logits / float(divisor)
            return sym.sequence_cross_entropy(
                logits, self.label(), shift=int(shift),
                name=prefix + "ce")

    def outputs(self, x, counts, second=None, tied=False, divisor=1.0):
        """``Group([loss, expert selection counts])`` from the last block's
        output through the final norm, ``x``: the head (``token_loss``:
        untied unless ``tied``, logits over ``divisor``), the next-token
        cross-entropy as one mean a sequence under ``MakeLoss``, and the
        layers' counts stacked without gradient and marked for
        ``Module.update_metric``; ``counts`` None (a model without experts):
        ``Group([loss])``.

        ``second = (weight, loss, counter names)`` adds a second head's loss
        [batch] (``token_loss``) to the one that is trained, ``main + weight *
        loss``, and a third output: the two parts stacked without gradient,
        which ``update_metric`` adds to the two counters named."""
        main = self.token_loss(x, tied=tied, divisor=divisor)
        total = main if second is None else main + float(second[0]) * second[1]
        loss = sym.MakeLoss(total, name="loss")
        if counts is None:
            return sym.Group([loss])
        counts = self.moe_counts(counts)
        if second is None:
            return sym.Group([loss, counts])
        return sym.Group([loss, counts, self.counter_rows(
            (main, second[1]), second[2], "loss_parts")])

    def moe_counts(self, counts):
        """The expert layers' selection counts stacked without gradient and
        marked for ``Module.update_metric`` (first expert, experts held)."""
        out = sym.BlockGrad(sym.stack(*counts, axis=0), name="moe_counts")
        cfg = self.cfg
        out._set_attr(__moe_counts__="%d,%d" % (
            int(cfg.get("first_expert", 0)), int(cfg["num_experts"])))
        return out

    @staticmethod
    def counter_rows(rows, names, name):
        """``rows`` (each [batch]) stacked without gradient as the node
        ``name``, which ``update_metric`` adds, each row's mean over the
        batch, to the counter of the same place in ``names``."""
        out = sym.BlockGrad(sym.stack(*rows, axis=0), name=name)
        out._set_attr(__counters__=",".join(names))
        return out

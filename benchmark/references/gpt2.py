"""Plain reference: the GPT-2 decoder-only transformer (Radford et al.
2019; sizes from openai-community/gpt2 ``config.json``) as one full causal
forward pass over a whole sequence, in straightforward ``jax.numpy``
float32 at ``highest`` matmul precision.  No cache, no pages, no batching.

Departures from the published model, both the served model's own
(``gluon/model_zoo/transformer.py:TransformerLM``): the vocabulary head is
untied from the embedding and has a bias, and GELU is the exact (erf) form.

It imports nothing of the program; the weights come from the benchmark's
generator under the names of the decoder's published parameter schema
(``embed``, ``pos``, ``l{i}.wq`` ..., ``lnf_g``, ``head_w``).  ``operand``
lets the CONTROL recompute the same pass in a lower precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-5


def param_shapes(cfg):
    e, f, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    shapes = {"embed": (v, e), "pos": (cfg["n_positions"], e)}
    for i in range(cfg["n_layer"]):
        p = "l%d." % i
        for w in ("wq", "wk", "wv", "wo"):
            shapes[p + w] = (e, e)
        for b in ("bq", "bk", "bv", "bo", "ln1_g", "ln1_b", "ln2_g",
                  "ln2_b", "b2"):
            shapes[p + b] = (e,)
        shapes[p + "w1"], shapes[p + "b1"] = (f, e), (f,)
        shapes[p + "w2"] = (e, f)
    shapes.update({"lnf_g": (e,), "lnf_b": (e,), "head_w": (v, e),
                   "head_b": (v,)})
    return shapes


def _ln(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def logits(params, tokens, cfg, first, operand=None):
    """Logits [len(tokens) - first, vocab] of positions ``first``.. of one
    sequence ``tokens`` [T] under a causal mask."""
    q8 = operand or (lambda a: a)

    def mm(x, w):           # x @ w.T, the (out, in) weight convention
        return jnp.dot(q8(x), q8(w).T, precision=lax.Precision.HIGHEST)

    t = tokens.shape[0]
    heads = cfg["n_head"]
    hd = cfg["n_embd"] // heads
    h = params["embed"][tokens] + params["pos"][:t]
    causal = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["n_layer"]):
        p = "l%d." % i
        x = _ln(h, params[p + "ln1_g"], params[p + "ln1_b"])
        q = (mm(x, params[p + "wq"]) + params[p + "bq"]).reshape(t, heads, hd)
        k = (mm(x, params[p + "wk"]) + params[p + "bk"]).reshape(t, heads, hd)
        v = (mm(x, params[p + "wv"]) + params[p + "bv"]).reshape(t, heads, hd)
        s = jnp.einsum("qhd,khd->hqk", q8(q), q8(k),
                       precision=lax.Precision.HIGHEST) / jnp.sqrt(
                           jnp.float32(hd))
        s = jnp.where(causal[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q8(w), q8(v),
                       precision=lax.Precision.HIGHEST).reshape(t, -1)
        h = h + mm(o, params[p + "wo"]) + params[p + "bo"]
        y = _ln(h, params[p + "ln2_g"], params[p + "ln2_b"])
        f = mm(y, params[p + "w1"]) + params[p + "b1"]
        f = 0.5 * f * (1.0 + lax.erf(f / jnp.sqrt(jnp.float32(2.0))))
        h = h + mm(f, params[p + "w2"]) + params[p + "b2"]
    hf = _ln(h[first:], params["lnf_g"], params["lnf_b"])
    return mm(hf, params["head_w"]) + params["head_b"]

"""The comparisons that decide ``correct``: arithmetic only, shared by the
runs, the calibration tool and the tests."""
from __future__ import annotations

import numpy as np

# a leaf whose reference gradient is under this share of the median leaf's
# is nought to rounding (a key's bias under softmax, a frozen scale): it
# moves by round-off alone and is left out of the UPDATE comparison
DEAD_LEAF = 1e-3


def leaf_norms(tree, names):
    """float64 vector of the L2 norms of ``tree[name]``, in ``names`` order
    (one device computation, one fetch)."""
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(t[n].astype(jnp.float32))))
         for n in names]))(tree)
    return np.asarray(norms, np.float64)


def norm_gaps(got, ref, live=None):
    """Each leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (0 for a leaf that is left out)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    live = np.ones(len(ref), bool) if live is None else np.asarray(live)
    scale = np.maximum(ref, np.median(ref[live]))
    gaps = np.where(live, np.abs(got - ref) / scale, 0.0)
    return np.where(np.isfinite(gaps), gaps, np.inf)


def worst_leaves(names, got, ref, live=None, top=4):
    """'name got/ref' of the leaves with the widest gaps, for the notes."""
    gaps = norm_gaps(got, ref, live)
    return "; ".join("%s %.4g/%.4g" % (names[i], got[i], ref[i])
                     for i in np.argsort(-gaps)[:top])


def training_numbers(losses, ref_losses, grad_norms, ref_grad_norms,
                     update_norms, ref_update_norms):
    """{name: value} of what a training cell compares: the worst relative
    gap of the first steps' losses, the worst leaf's gap of the first
    gradient's norm, and of the parameters' change over those steps (leaves
    whose reference gradient is nought to rounding left out)."""
    losses = np.asarray(losses, np.float64)
    ref_losses = np.asarray(ref_losses, np.float64)
    loss_gaps = np.abs(losses - ref_losses) / np.abs(ref_losses)
    loss_gaps = np.where(np.isfinite(loss_gaps), loss_gaps, np.inf)
    ref_g = np.asarray(ref_grad_norms, np.float64)
    live = ref_g >= DEAD_LEAF * np.median(ref_g)
    g_gaps = norm_gaps(grad_norms, ref_g)
    u_gaps = norm_gaps(update_norms, ref_update_norms, live)[live]
    numbers = {
        "loss_gap": float(loss_gaps.max()),
        "loss1_gap": float(loss_gaps[0]),
        "grad_norm_gap": float(g_gaps.max()),
        "grad_norm_gap_p90": float(np.quantile(g_gaps, 0.9)),
        "grad_norm_gap_median": float(np.median(g_gaps)),
        "update_norm_gap": float(u_gaps.max()),
        "update_norm_gap_p90": float(np.quantile(u_gaps, 0.9)),
        "update_norm_gap_median": float(np.median(u_gaps)),
        "total_grad_norm_gap": float(abs(
            np.sqrt(np.sum(np.square(grad_norms)))
            / np.sqrt(np.sum(np.square(ref_g))) - 1.0)),
        "total_update_norm_gap": float(abs(
            np.sqrt(np.sum(np.square(update_norms)))
            / np.sqrt(np.sum(np.square(ref_update_norms))) - 1.0)),
    }
    return numbers, {"grad_leaf": int(np.argmax(g_gaps)),
                     "update_leaf": int(np.argmax(
                         norm_gaps(update_norms, ref_update_norms, live))),
                     "dead_leaves": int((~live).sum())}


def logit_gaps(ref_logits, tokens):
    """For each position, how far the given token's logit lies below the
    reference's best: ``max(ref) - ref[token]`` (0 where it IS the best)."""
    ref_logits = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens, np.int64)
    return ref_logits.max(axis=1) - ref_logits[np.arange(len(tokens)), tokens]

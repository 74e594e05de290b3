"""``moe_experts`` moves only the rows this chip holds (docs/kernels.md, "The
expert layer's rows"): the sorted choices are worked through in rounds of a
static capacity, as many rounds as the live choices reach, so a layer over
its capacity still computes every held choice.  Values, the routing counts
and every gradient against a float32 dense loop over the held experts, at
loads from none held to all held; the capacity from shapes alone; no array
of tokens x top-k rows left in the traced program at the cells' shapes; and
the counters that say how often a second round runs."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.observability import instrument, telemetry
from mxnet_tpu.ops import lm_ops

import test_qwen3_next

# 512 tokens x top-8 over 64 experts of which 8 are held: 4,096 choices, a
# uniform router would hold 512 of them, so a round is 1,024 rows: four rounds
N, K, E, HELD, FIRST, H, I = 512, 8, 64, 8, 16, 64, 8
CAP = 1024
# held choices a token: the load, in rounds of CAP
LOADS = {"none-held": 0, "under": 1, "exactly-one-round": 2, "two-rounds": 3,
         "three-rounds": 5, "every-choice-held": 8}
# the language-model cells: tokens, hidden, experts, held, top-k, expert width
CELLS = {"qwen3next-train-s8k-b2": (16384, 2048, 512, 32, 10, 512, 20480),
         "trinity-mini-train-s8k-b1": (8192, 2048, 128, 16, 8, 1024, 16384),
         "joyai-flash-train-s8k-b1": (8192, 2048, 256, 16, 8, 768, 8192)}


def _normal(seed, shape, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).normal(0, scale, shape),
                       jnp.float32)


def _close(got, want, tol=1e-4):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(1.0, np.abs(want).max()))


def _layer(held_per_token, seed=0):
    """(tokens, weights): the router is the identity, so a token's logits
    are its own first ``E`` features, and those are set so that it chooses
    exactly ``held_per_token`` of the held experts."""
    r = np.random.RandomState(seed)
    x = r.normal(0, 0.3, (N, H))
    held = np.arange(FIRST, FIRST + HELD)
    others = np.setdiff1d(np.arange(E), held)
    for t in range(N):
        chosen = np.concatenate([
            r.choice(held, held_per_token, replace=False),
            r.choice(others, K - held_per_token, replace=False)])
        x[t, chosen] += 4.0
    w = dict(router=jnp.eye(E, H, dtype=jnp.float32),
             gate=_normal(seed + 1, (HELD, H, I), 0.3),
             up=_normal(seed + 2, (HELD, H, I), 0.3),
             down=_normal(seed + 3, (HELD, I, H), 0.3))
    return jnp.asarray(x, jnp.float32), w


def _op(x, w, score_func, **changed):
    kw = dict(num_experts=E, num_hidden=I, experts_held=HELD,
              first_expert=FIRST, top_k=K, norm_topk_prob=True,
              score_func=score_func, route_scale=1.5)
    kw.update(changed)
    return lm_ops._moe_experts(x, w["router"], w["gate"], w["up"], w["down"],
                               **kw)


def _dense(x, w, score_func):
    """The same sum by a loop over the held experts, every token through
    every one of them, in float32: (y, counts)."""
    logits = x @ w["router"].T
    probs = jax.nn.sigmoid(logits) if score_func == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)
    top_p = 1.5 * top_p / (jnp.sum(top_p, -1, keepdims=True)
                           + (1e-20 if score_func == "sigmoid" else 0.0))
    y = jnp.zeros_like(x)
    for j in range(HELD):
        p = jnp.sum(jnp.where(top_e == FIRST + j, top_p, 0.0), axis=-1)
        mid = jax.nn.silu(x @ w["gate"][j]) * (x @ w["up"][j])
        y = y + p[:, None] * (mid @ w["down"][j])
    counts = np.bincount(np.asarray(top_e).ravel(), minlength=E)
    return y, counts


def _value_and_grads(fn, x, w):
    target = _normal(9, (N, H))
    return jax.value_and_grad(
        lambda x, w: jnp.sum(fn(x, w) * target), argnums=(0, 1))(x, w)


# -- the capacity ---------------------------------------------------------------------

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_capacity_at_the_cells(cell):
    n, _, experts, held, k, _, want = CELLS[cell]
    assert lm_ops.moe_capacity(n * k, held, experts) == want


@pytest.mark.parametrize("choices, held, experts, want", [
    (4096, 8, 64, 1024),        # twice the expected 512, a whole row tile
    (4096, 0, 64, 4096),        # held 0: every expert is held
    (4096, 64, 64, 4096),       # an unsharded layer: what it did before
    (72, 4, 16, 72),            # fewer choices than a row tile: all of them
    (4096, 48, 64, 4096),       # twice the share is more than there is
    (40960, 8, 64, 10240),      # ten tiles exactly
    (40968, 8, 64, 11264),      # and one row more: the next tile
])
def test_capacity_from_shapes_alone(choices, held, experts, want):
    assert lm_ops.moe_capacity(choices, held, experts) == want


def test_this_file_s_layer_takes_four_rounds():
    assert lm_ops.moe_capacity(N * K, HELD, E) == CAP
    assert [lm_ops.moe_rounds(live, CAP) for live in
            (0, 1, CAP, CAP + 1, 3 * CAP, N * K)] == [0, 1, 1, 2, 3, 4]


# -- every held choice, whatever the load ------------------------------------------

@pytest.mark.parametrize("score_func", ["softmax", "sigmoid"])
@pytest.mark.parametrize("load", sorted(LOADS))
def test_value_counts_and_gradients_against_the_dense_loop(load, score_func):
    x, w = _layer(LOADS[load])
    y, counts = _op(x, w, score_func)
    want, want_counts = _dense(x, w, score_func)
    live = int(want_counts[FIRST:FIRST + HELD].sum())
    assert live == N * LOADS[load]
    assert lm_ops.moe_rounds(live, CAP) == -(-live // CAP)
    _close(y, want)
    assert counts.dtype == jnp.float32
    assert np.array_equal(np.asarray(counts), want_counts)
    got = _value_and_grads(lambda x, w: _op(x, w, score_func)[0], x, w)
    ref = _value_and_grads(lambda x, w: _dense(x, w, score_func)[0], x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        _close(a, b)
    if live:
        assert float(jnp.abs(got[1][1]["router"]).max()) > 0


@pytest.mark.parametrize("load", ["under", "three-rounds"])
def test_differentiates_under_checkpoint(load):
    """The mirror stages recompute this op: the same gradients through
    ``jax.checkpoint``, jitted."""
    x, w = _layer(LOADS[load], seed=5)
    fn = jax.checkpoint(lambda x, w: _op(x, w, "sigmoid")[0])
    got = jax.jit(lambda x, w: _value_and_grads(fn, x, w))(x, w)
    ref = _value_and_grads(lambda x, w: _dense(x, w, "sigmoid")[0], x, w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        _close(a, b)


@pytest.mark.parametrize("load", ["under", "two-rounds"])
def test_bfloat16_rows_sum_in_float32(load):
    """The configuration's precision: bfloat16 rows and products, each
    token's choices added in float32 and cast once."""
    x, w = _layer(LOADS[load], seed=7)
    half = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), t)
    y, counts = _op(half(x), dict(half(w), router=w["router"]), "softmax")
    assert y.dtype == jnp.bfloat16
    want, want_counts = _dense(half(x).astype(jnp.float32), jax.tree_util.
                               tree_map(lambda a: half(a).astype(jnp.float32),
                                        w), "softmax")
    assert np.array_equal(np.asarray(counts), want_counts)
    _close(y.astype(jnp.float32), want, 2e-2)


def test_rows_past_the_live_ones_may_hold_anything_in_every_round(
        monkeypatch):
    """What the grouped product leaves unwritten (NaN here, as the TPU may)
    past a round's groups reaches neither the output nor a gradient: in the
    last round taken the live rows end inside the window."""
    x, w = _layer(LOADS["two-rounds"], seed=3)
    run = lambda: _value_and_grads(lambda x, w: _op(x, w, "softmax")[0], x, w)
    want = run()
    monkeypatch.setattr(lm_ops.lax, "ragged_dot",
                        test_qwen3_next._leaves_rows_unwritten(
                            jax.lax.ragged_dot))
    for a, b in zip(jax.tree_util.tree_leaves(run()),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-6)


def test_an_unsharded_layer_is_one_round_and_no_loop():
    x, w = _layer(LOADS["under"], seed=11)
    whole = dict(w, **{n: jnp.concatenate([w[n]] * (E // HELD))
                       for n in ("gate", "up", "down")})
    for held in (0, E):
        text = str(jax.make_jaxpr(
            lambda x, w: _op(x, w, "softmax", experts_held=held,
                             first_expert=0))(x, whole))
        assert "while" not in text and "cond" not in text
    assert "while" in str(jax.make_jaxpr(
        lambda x, w: _op(x, w, "softmax"))(x, w))


# -- no array of tokens x top-k rows ------------------------------------------------

def _shapes_in(jaxpr, seen):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            seen.add(tuple(getattr(v.aval, "shape", ())))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes_in(sub, seen)
    return seen


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_no_array_of_all_the_choices_rows_at_the_cell_s_shape(cell):
    """Forward, recomputed and backward of the expert layer at the cell's
    shape, traced and not run: every array of rows of the hidden or the
    expert width has the capacity's rows, none tokens x top-k."""
    n, h, experts, held, k, width, cap = CELLS[cell]
    aval = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    layer = jax.checkpoint(lambda x, r, g, u, d: lm_ops._moe_experts(
        x, r, g, u, d, num_experts=experts, num_hidden=width,
        experts_held=held, first_expert=held, top_k=k)[0])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(layer(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3, 4)))(
        aval(n, h), aval(experts, h), aval(held, h, width),
        aval(held, h, width), aval(held, width, h))
    shapes = _shapes_in(jaxpr.jaxpr, set())
    assert (cap, h) in shapes and (cap, width) in shapes
    assert not {s for s in shapes
                if len(s) > 1 and s[0] == n * k and s[-1] in (h, width)}
    assert not {s for s in shapes if s[:2] == (n, k) and s[2:] in ((h,), (width,))}


# -- the counters ---------------------------------------------------------------------

def _counts(*held_choices):
    """[layers, E] selection counts with the given held choices a layer,
    spread over the held experts, and the rest of the 4,096 elsewhere."""
    rows = np.zeros((len(held_choices), E))
    for row, live in zip(rows, held_choices):
        row[FIRST:FIRST + HELD] = live // HELD
        row[FIRST] += live % HELD
        row[0] = N * K - live
    return rows


@pytest.mark.parametrize("held_choices, moved, overflow", [
    ((700,), CAP, 0),                   # under the capacity: one round
    ((CAP,), CAP, 0),                   # at it: still one
    ((CAP + 1,), 2 * CAP, 1),           # one row over: a second round
    ((0,), 0, 0),                       # nothing held: no round runs
    ((N * K,), 4 * CAP, 3),             # every choice: all four
    ((700, CAP, CAP + 1, 2500), 7 * CAP, 3),    # layers add up
])
def test_counters_say_how_many_rounds_ran(held_choices, moved, overflow):
    names = ["module.moe." + n for n in (
        "rows_live", "rows_moved", "overflow_rounds", "selections_held",
        "selections_total")]
    read = lambda: [telemetry.snapshot().get(n, {}).get("value", 0.0)
                    for n in names]
    before = read()
    instrument.note_moe_counts(_counts(*held_choices), first_expert=FIRST,
                               experts_held=HELD)
    live, rows, extra, held, total = (
        b - a for a, b in zip(before, read()))
    assert (live, rows, extra) == (sum(held_choices), moved, overflow)
    assert held == live and total == len(held_choices) * N * K

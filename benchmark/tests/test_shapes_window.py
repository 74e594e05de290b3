"""``shapes_window`` against hand counts, against ``shapes_lm`` where no
window hides a key, and the reader of a kernel's share of its roofline on a
canned reduction."""
import pytest

from benchmark import shapes_lm, shapes_window
from benchmark.readers import kernel_compute_roofline

CFG = dict(
    hidden_size=32, vocab_size=50, num_hidden_layers=3, num_dense_layers=1,
    layer_types=["sliding_attention", "sliding_attention", "full_attention"],
    sliding_window=4, intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, rope_theta=10000, rms_norm_eps=1e-5,
    num_experts=4, router_num_experts=16, first_expert=4,
    num_experts_per_tok=3, route_norm=True, route_scale=2.826,
    score_func="sigmoid", moe_intermediate_size=16, num_shared_experts=1,
    mup_enabled=True)
SHAPES = dict(data=(2, 10), softmax_label=(2, 10))


def test_visible_pairs_by_hand():
    # 10 queries, window 4: 1 + 2 + 3 + 4 and then 4 each
    assert shapes_window.visible_pairs(10, 10, True, 4) == 10 + 6 * 4
    assert shapes_window.visible_pairs(10, 10, True, 0) == 55
    assert shapes_window.visible_pairs(10, 10, True, 10) == 55
    assert shapes_window.visible_pairs(10, 10, True, 1) == 10
    assert shapes_window.visible_pairs(10, 7, False) == 70
    assert shapes_window.attention_forward_flops(2, 10, 10, 4, 16, True, 4) \
        == 4 * 2 * 4 * 16 * 34


def _symbol(**changed):
    from mxnet_tpu import models
    return models.trinity.get_symbol(dict(CFG, **changed))


def test_train_flops_by_hand_and_against_shapes_lm():
    tokens = 20
    attn = 2 * tokens * 32 * (2 * 64 + 2 * 32 + 64)     # q, gate, k, v, o
    dense = 2 * tokens * 3 * 32 * 48
    experts = 2 * tokens * 32 * 16 + tokens * 3 * 4 / 16 * 6 * 32 * 16 \
        + 2 * tokens * 3 * 32 * 16                      # router, held, shared
    head = 2 * tokens * 32 * 50
    pairs = 4 * 2 * 4 * 16 * (2 * 34 + 55)
    want = 3.0 * (3 * attn + dense + 2 * experts + head + pairs)
    assert shapes_window.train_flops(_symbol(), CFG, **SHAPES) \
        == pytest.approx(want)
    # a window that hides no key: the count shapes_lm makes
    wide = _symbol(sliding_window=10)
    assert shapes_window.train_flops(wide, CFG, **SHAPES) \
        == pytest.approx(shapes_lm.train_flops(wide, CFG, **SHAPES))
    assert shapes_lm.train_flops(_symbol(), CFG, **SHAPES) > want


def test_flash_forward_work_counts_the_recomputed_call():
    q, kv = 2 * 10 * 4 * 16, 2 * 10 * 2 * 16
    once = {"flops": 4.0 * 2 * 4 * 16 * (2 * 34 + 55),
            "bytes": 3 * 2.0 * (2 * q + 2 * kv)}
    got = shapes_window.flash_forward_work(_symbol(), 2, **SHAPES)
    assert got == {k: pytest.approx(2 * v) for k, v in once.items()}
    from mxnet_tpu import models
    plain = models.trinity.get_symbol(CFG, recompute=False)
    assert shapes_window.flash_forward_work(plain, 2, **SHAPES) \
        == {k: pytest.approx(v) for k, v in once.items()}


def test_the_reader_takes_the_binding_side_of_the_roofline():
    obs = {"device_kind": "TPU v5 lite",
           "trace": {"op_seconds": {"flash_attn_fwd.3 custom-call": 0.004,
                                    "flash_attn_fwd.7 custom-call": 0.006,
                                    "fusion.1 fusion": 1.0}},
           "kernel_work": {"flash_attn_fwd": {"flops": 197e12 * 0.005,
                                              "bytes": 819e9 * 0.001}}}
    read = kernel_compute_roofline.read
    assert read(obs, "flash_attn_fwd") == pytest.approx(50.0)
    obs["kernel_work"]["flash_attn_fwd"]["bytes"] = 819e9 * 0.008
    assert read(obs, "flash_attn_fwd") == pytest.approx(80.0)
    # nothing to read: no such work handed, no such operation, no trace
    assert read(dict(obs, kernel_work={}), "flash_attn_fwd") is None
    assert read(dict(obs, kernel_work=None), "flash_attn_fwd") is None
    assert read(obs, "no_such_kernel") is None
    assert read(dict(obs, trace=None), "flash_attn_fwd") is None
    obs["trace"]["op_seconds"] = {"fusion.1 fusion": 1.0}
    assert read(obs, "flash_attn_fwd") is None

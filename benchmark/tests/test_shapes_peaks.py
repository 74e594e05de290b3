"""Shape functions against hand counts at one size each, and the peak
table."""
import pytest

from benchmark import peaks, shapes


def test_peak_table_names_its_source_and_refuses_unknown_kinds():
    row = peaks.peak("TPU v5 lite")
    assert row["flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert "v5e" in row["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("cpu")


def test_conv_and_dense_flops_by_hand():
    # 3x3 conv, 16 -> 32 channels, output 8x8, batch 2:
    # 2 * (2*32*8*8) * (16*3*3) = 2 * 4096 * 144
    assert shapes.conv_forward_flops((2, 32, 8, 8), 16, (3, 3)) \
        == 2 * 4096 * 144
    assert shapes.dense_forward_flops(4, 10, 7) == 2 * 4 * 10 * 7


def test_train_flops_read_off_a_symbol_by_hand():
    import mxnet_tpu as mx
    data = mx.sym.var("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             no_bias=True, name="c")
    net = mx.sym.BatchNorm(net, name="bn")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max",
                         name="p")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=5, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    kw = dict(data=(2, 3, 8, 8), softmax_label=(2,))
    conv = 2 * (2 * 4 * 8 * 8) * (3 * 3 * 3)
    fc = 2 * 2 * (4 * 4 * 4) * 5
    assert shapes.symbol_train_flops(net, **kw) == 3 * (conv + fc)
    # BN reads its [2,4,8,8] input three times (bf16: 2 bytes) plus four
    # float32 rows of C; max pooling backward reads x and dy, writes dx
    bn = 3 * (2 * 4 * 8 * 8) * 2 + 4 * 4 * 4
    pool = (2 * (2 * 4 * 8 * 8) + (2 * 4 * 4 * 4)) * 2
    assert shapes.bn_pool_kernel_bytes(net, 2, **kw) == bn + pool
    assert shapes.bn_pool_kernel_bytes(net, 2, lambda s: False, **kw) == pool


def test_resnet50_forward_is_the_published_4_gmac():
    from mxnet_tpu import models
    sym = models.resnet.get_symbol(num_classes=1000, num_layers=50,
                                   image_shape="3,224,224", dtype="bfloat16")
    per_sample = shapes.symbol_train_flops(
        sym, data=(1, 3, 224, 224), softmax_label=(1,)) / 3 / 2
    assert 4.0e9 < per_sample < 4.2e9       # multiply-adds, forward


GPT = dict(n_embd=8, n_inner=32, n_layer=2, vocab_size=50)


def test_lm_flops_and_bytes_by_hand():
    # per layer: 4 projections 8x8 and two 8x32 products, 2 ops a
    # multiply-add, plus scores and weighted sum over 5 positions
    layer = 2 * (4 * 64 + 2 * 256) + 4 * 5 * 8
    assert shapes.lm_token_flops(GPT, 5, False) == 2 * layer
    assert shapes.lm_token_flops(GPT, 5, True) == 2 * layer + 2 * 8 * 50
    per_layer = 4 * 64 + 4 * 8 + 2 * 256 + 32 + 8 + 4 * 8
    weights = 4 * (2 * per_layer + 2 * 8 + 50 * 8 + 50)
    assert shapes.lm_weight_bytes(GPT) == weights
    # two streams at contexts 5 and 3, four slots
    kv = 2 * 2 * 8 * 4 * (8 + 2)
    assert shapes.lm_iteration_bytes(GPT, [5, 3], 4) \
        == weights + kv + 2 * 8 * 4 * 2 + 4 * 50 * 4

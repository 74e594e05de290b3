"""Every Pallas kernel cross-lowered for the TPU, on the CPU, in seconds.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the Pallas
TPU lowering (BlockSpec legality, the Mosaic MLIR emission) without a chip,
so a block shape the TPU lowering refuses never reaches one again.  Shapes
are the ones the chip runs: every BatchNorm and Pooling input of the
ResNet-50 train step at batch 32, read off the symbol itself, and flash
attention at head_dim 128 and at the language-model cell's own shape
(8,192 tokens, 16 query heads over 2 K/V heads of 256), forward and
gradient; the delta-rule scan and chunk-local kernels
(``ops/gdn_kernels.py``) at that cell's shape too; the state-space scan
kernels and the flash kernels at head width 64 at the Granite cell's.

The lowering cannot see Mosaic's own compile (layout inference, unaligned
slices).  ``-m slow`` adds it: libtpu compiles for a named v5e topology
with no chip attached, so every case also runs the full TPU compile here.
Whether a compiled kernel computes the right numbers is ``make chip``
(tests/test_pallas.py and the compiled mode of tests/test_pallas_kernels.py
on the chip).
"""
import json

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import models
from mxnet_tpu.base import shape_attr
from mxnet_tpu.ops import gdn_kernels, lm_ops
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.nn import _pool_core

BATCH = 32


def _aval(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _resnet50_kernel_inputs():
    """(BatchNorm input shapes, Pooling (input shape, attrs)) of the
    chip_smoke train symbol."""
    sym = models.resnet.get_symbol(num_classes=1000, num_layers=50,
                                   image_shape="3,224,224",
                                   dtype="bfloat16")
    internals = sym.get_internals()
    _, out_shapes, _ = internals.infer_shape(data=(BATCH, 3, 224, 224),
                                             softmax_label=(BATCH,))
    shape_of = dict(zip(internals.list_outputs(), out_shapes))
    nodes = json.loads(sym.tojson())["nodes"]
    bn, pools = set(), set()
    for node in nodes:
        if node["op"] not in ("BatchNorm", "Pooling"):
            continue
        src = nodes[node["inputs"][0][0]]
        shape = tuple(shape_of[src["name"] + ("_output" if src["op"] != "null"
                                              else "")])
        if node["op"] == "BatchNorm":
            bn.add(shape)
        else:
            pools.add((shape, json.dumps(node["attrs"], sort_keys=True)))
    return sorted(bn), sorted(pools)


BN_SHAPES, POOLS = _resnet50_kernel_inputs()
FLASH_CASES = [
    # (q shape, dtype, causal, K/V heads); tests/test_pallas.py shapes ...
    ((2, 256, 2, 128), "float32", False, 2),
    ((1, 256, 2, 128), "float32", True, 2),
    ((1, 512, 1, 128), "float32", True, 1),
    # ... an odd length (block padding), one real one, and the attention
    # layer of `qwen3next-train-s8k-b2`: 16 query heads over 2 K/V heads
    ((1, 100, 2, 128), "bfloat16", True, 2),
    ((4, 4096, 16, 128), "bfloat16", True, 16),
    ((2, 8192, 16, 256), "bfloat16", True, 2),
    # a fifth entry is a causal window: a short one over tiles that do not
    # divide it, and the two kinds of layer of `trinity-mini-train-s8k-b1`,
    # 32 query heads over 4 K/V heads with and without its 2,048 window
    ((1, 512, 4, 128), "float32", True, 2, 100),
    ((1, 8192, 32, 128), "bfloat16", True, 4, 2048),
    ((1, 8192, 32, 128), "bfloat16", True, 4),
    # head width 64 (a block whose last dim is the whole head): the attention
    # layer of `granite-h-train-s8k-b1`, 32 query heads over 8 K/V heads
    ((1, 8192, 32, 64), "bfloat16", True, 8),
]
# score width != value width: (q shape, dtype, K/V heads, the heads' own key
# width, the value width); what is left of q's width is a key part that all
# heads share.  A small one, a plain wide-key one, and the latent attention of
# `joyai-flash-train-s8k-b1`: 32 heads of 192 = 128 + 64 shared on values of 128
FLASH_TWO_WIDTH_CASES = [
    ((1, 256, 4, 192), "float32", 2, 128, 128),
    ((1, 512, 4, 256), "bfloat16", 2, 256, 128),
    ((1, 8192, 32, 192), "bfloat16", 32, 128, 128),
]
# the block-diffusion mask: (q shape, dtype, K/V heads, block); a short one
# whose tiles lie across the two halves, and `sdar-train-bd4-s8k-b1`'s
# 16,384 positions (8,192 noisy + 8,192 clean), 32 query heads over 4
FLASH_BD_CASES = [
    ((1, 96, 2, 128), "float32", 1, 4),
    ((1, 16384, 32, 128), "bfloat16", 4, 4),
]


def pool_configs():
    """(input shape, ``_pool_core`` static config) per Pooling node."""
    out = []
    for shape, attrs in POOLS:
        attrs = json.loads(attrs)
        if attrs.get("global_pool") == "True":
            kernel, stride, pad = shape[2:], (1, 1), (0, 0)
        else:
            kernel, stride, pad = (shape_attr(attrs[k])
                                   for k in ("kernel", "stride", "pad"))
        out.append((shape, (attrs["pool_type"], kernel, stride, pad,
                            "valid", True)))
    return out


def _cases():
    """(id, fn, avals) for every kernel program the chip must take."""
    out = []
    for shape in BN_SHAPES:
        x = _aval(shape, "bfloat16")
        out.append(("bn-sums-%s" % (shape,),
                    lambda a: pk.bn_channel_sums(a), (x,)))         # Σx, Σx²
        out.append(("bn-pair-%s" % (shape,),
                    lambda a, b: pk.bn_channel_sums(a, b), (x, x)))  # Σdy, Σdy·x
    for shape, cfg in pool_configs():
        core = _pool_core(*cfg, "pallas")
        out.append(("pool-%s-bwd-%s" % (cfg[0], shape),
                    jax.grad(lambda v, core=core: jnp.sum(
                        core(v).astype(jnp.float32) ** 2)),
                    (_aval(shape, "bfloat16"),)))
    for shape, dtype, causal, kv_heads, *window in FLASH_CASES:
        window = window[0] if window else 0
        x = _aval(shape, dtype)
        kv = _aval(shape[:2] + (kv_heads,) + shape[3:], dtype)

        def fwd(q, k, v, n=None, causal=causal, window=window):
            return pk.flash_attention(q, k, v, causal=causal,
                                      use_pallas=True, kv_lens=n,
                                      window=window)

        tag = "%s%s-%s-%s%s" % (shape, "" if kv_heads == shape[2]
                                else "kv%d" % kv_heads, dtype,
                                "causal" if causal else "full",
                                "-w%d" % window if window else "")
        out.append(("flash-fwd-" + tag, fwd, (x, kv, kv)))
        out.append(("flash-grad-" + tag,
                    jax.grad(lambda q, k, v, fwd=fwd: jnp.sum(
                        fwd(q, k, v).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2)), (x, kv, kv)))
        # the padding-mask operand (scalar prefetch)
        out.append(("flash-lens-" + tag, fwd,
                    (x, kv, kv, _aval(shape[:1], "int32"))))
    for shape, dtype, kv_heads, d_k, d_v in FLASH_TWO_WIDTH_CASES:
        avals = [_aval(shape, dtype)] + [
            _aval(shape[:2] + (kv_heads, w), dtype) for w in (d_k, d_v)]
        if shape[3] > d_k:
            avals.append(_aval(shape[:2] + (shape[3] - d_k,), dtype))

        def fwd(q, k, v, ks=None):
            return pk.flash_attention(q, k, v, causal=True, use_pallas=True,
                                      k_shared=ks)

        tag = "%skv%d-k%d-v%d-%s" % (shape, kv_heads, d_k, d_v, dtype)
        out.append(("flash-fwd-" + tag, fwd, tuple(avals)))
        out.append(("flash-grad-" + tag,
                    jax.grad(lambda *a, fwd=fwd: jnp.sum(
                        fwd(*a).astype(jnp.float32) ** 2),
                        argnums=tuple(range(len(avals)))), tuple(avals)))
    for shape, dtype, kv_heads, block in FLASH_BD_CASES:
        x = _aval(shape, dtype)
        kv = _aval(shape[:2] + (kv_heads,) + shape[3:], dtype)

        def fwd(q, k, v, block=block):
            return pk.flash_attention(q, k, v, use_pallas=True,
                                      block_diffusion=block)

        tag = "%skv%d-%s-bd%d" % (shape, kv_heads, dtype, block)
        out.append(("flash-fwd-" + tag, fwd, (x, kv, kv)))
        out.append(("flash-grad-" + tag,
                    jax.grad(lambda q, k, v, fwd=fwd: jnp.sum(
                        fwd(q, k, v).astype(jnp.float32) ** 2),
                        argnums=(0, 1, 2)), (x, kv, kv)))
    # the delta-rule scan kernels at the language-model cell's own shape:
    # 2 x 8,192 tokens, 16 key heads of 128, 2 value heads of 128 each
    gdr = lm_ops._make_gdr(64, "pallas")
    heads = _aval((2, 16, 8192, 128), "bfloat16")
    values = _aval((2, 16, 2, 8192, 128), "bfloat16")
    gates = _aval((2, 16, 2, 8192), "float32")
    gdn = (heads, heads, values, gates, gates)
    out.append(("gdn-fwd", gdr, gdn))
    out.append(("gdn-grad",
                jax.grad(lambda *a: jnp.sum(gdr(*a).astype(jnp.float32) ** 2),
                         argnums=(0, 1, 2, 3, 4)), gdn))
    # the two chunk-local kernels alone at that shape: 128 chunks of 64
    chunks = _aval((2, 16, 128, 64, 128), "bfloat16")
    per_chunk = _aval((2, 16, 2, 128, 64, 128), "bfloat16")
    decays = _aval((2, 16, 2, 128, 64), "float32")
    squares = _aval((2, 16, 2, 128, 64, 64), "bfloat16")
    inverses = _aval((32, 128, 64, 128), "float32")
    local = (chunks, chunks, per_chunk, decays, decays)
    out.append(("gdn-local-fwd", lambda *a: gdn_kernels.local_fwd(
        *a, keep_inverse=True), local))
    out.append(("gdn-local-bwd", lambda q, k, v, g, b, inv, *d:
                gdn_kernels.local_bwd(q, k, v, g, b, inv, d),
                local + (inverses, per_chunk, squares, squares, decays,
                         decays)))
    # the state-space scan kernels (no correction) at `granite-h-train-s8k-b1`'s
    # shape: 8,192 tokens, one group of state 128, 64 value heads of 64
    ssd = lm_ops._make_gdr(64, "pallas", False)
    ssm = (_aval((1, 1, 8192, 128), "bfloat16"),) * 2 + (
        _aval((1, 1, 64, 8192, 64), "bfloat16"),
        _aval((1, 1, 64, 8192), "float32"))
    plain = lambda q, k, v, g: ssd(q, k, v, g, None)    # no beta
    out.append(("ssd-fwd", plain, ssm))
    out.append(("ssd-grad",
                jax.grad(lambda *a: jnp.sum(plain(*a).astype(jnp.float32)
                                            ** 2), argnums=(0, 1, 2, 3)), ssm))
    return out


CASES = _cases()


def test_resnet50_bn_shapes_are_kernel_eligible():
    assert all(pk.bn_sums_eligible(shape) for shape in BN_SHAPES)


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_lowers_for_tpu(case):
    _, fn, avals = case
    jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))


POOL_GRAD_CASES = [c for c in CASES if c[0].startswith("pool-")]


@pytest.mark.parametrize("case", POOL_GRAD_CASES,
                         ids=[c[0] for c in POOL_GRAD_CASES])
def test_only_max_pooling_lowers_to_a_kernel(case):
    """With the pool flag resolving to the compiled kernel, the gradient
    at ResNet-50's max pool holds one Mosaic call and the one at its
    global average pool holds none: that one is XLA's own (PR 26)."""
    name, fn, avals = case
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == int(name.startswith("pool-max"))


@pytest.fixture(scope="module")
def v5e_device():
    """A compile-only v5e device: libtpu compiles for a named topology
    without a chip attached, Mosaic included."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as exc:  # noqa: BLE001 — no libtpu, or it wants a chip
        pytest.skip("no compile-only TPU topology here: %s" % (exc,))
    return topo.devices[0]


@pytest.mark.slow
@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_mosaic_compiles_for_v5e(case, v5e_device):
    """The whole TPU compile, Mosaic's passes included, on the CPU: what a
    kernel change should pass before it spends chip time."""
    from jax.sharding import SingleDeviceSharding
    _, fn, avals = case
    on_chip = SingleDeviceSharding(v5e_device)
    jax.jit(fn, in_shardings=(on_chip,) * len(avals)).trace(*avals) \
        .lower(lowering_platforms=("tpu",)).compile()


def test_partitioned_trace_keeps_mosaic_kernels_out(monkeypatch):
    """A jit XLA partitions by itself (shardings over a mesh) cannot hold
    a Mosaic kernel — the lowering refuses it outside a shard_map.  The
    trace scope the dp fused step and ShardedModule open resolves the
    kernel flags to off there, so the 4-chip step lowers; and a program
    bound for the cpu traces no compiled kernel on a TPU host."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.ops.nn import _pooling

    monkeypatch.setattr(pk, "on_tpu", lambda: True)
    assert pk.kernel_mode("bn") == "pallas"
    assert dict(pk.kernel_signature("cpu"))["bn"] == "off"
    with pk.trace_scope(platform="cpu"):
        assert pk.kernel_mode("bn") == "off"
    with pk.trace_scope(partitioned=True):
        assert pk.kernel_mode("bn") == "off"
        monkeypatch.setenv("MXNET_TPU_PALLAS_BN", "1")
        assert pk.kernel_mode("bn") == "pallas"   # explicit: let it raise
    dp = NamedSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), P("dp"))

    def pool_grad(x):
        return jax.grad(lambda v: jnp.sum(_pooling(
            v, pool_type="max", kernel=(3, 3), stride=(2, 2),
            pad=(1, 1)) ** 2))(x)

    def scoped(x):
        with pk.trace_scope(partitioned=True):
            return pool_grad(x)

    x = _aval((4, 8, 16, 16), "float32")
    jax.jit(scoped, in_shardings=dp).trace(x).lower(
        lowering_platforms=("tpu",))
    with pytest.raises(NotImplementedError, match="partitioned"):
        jax.jit(pool_grad, in_shardings=dp).trace(x).lower(
            lowering_platforms=("tpu",))


@pytest.mark.slow
def test_compiled_kernels_carry_their_scope_and_pass(v5e_device):
    """The compiled text of a TPU program names each kernel's call by the
    kernel, and its ``op_name`` carries the op's ``mx:`` scope and the pass:
    what ``FusedTrainStep.op_scopes`` reads (docs/observability.md §1)."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.observability import instrument
    from mxnet_tpu.ops import attention, lm_ops

    def loss(q, k, v, qd, vd, a, log, bias):
        with pk.trace_scope(platform="tpu"):
            o = attention._sdpa(q, k, v, causal=True, window=128)
            d = lm_ops._gated_delta_rule(qd, qd, vd, a, a, log, bias)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(d.astype(jnp.float32))

    avals = [_aval((1, 512, 2, 128), "bfloat16")] * 3 \
        + [_aval((1, 256, 2, 128), "bfloat16")] * 2 \
        + [_aval((1, 256, 2), "bfloat16")] + [_aval((2,), "float32")] * 2
    on_chip = SingleDeviceSharding(v5e_device)
    text = jax.jit(jax.grad(loss, argnums=tuple(range(8))),
                   in_shardings=(on_chip,) * 8).trace(*avals).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    table = instrument.scopes_of_hlo(text)
    of = lambda kernel: {(r["mechanism"], r["detail"], r["pass"])
                         for n, r in table.items() if n.startswith(kernel)}
    assert of("flash_attn_fwd") == {("mx:attn", "mx:attn:window", "forward")}
    for kernel in ("flash_attn_bwd_dq", "flash_attn_bwd_dkv"):
        assert of(kernel) == {("mx:attn", "mx:attn:window", "backward")}
    assert of("gdn_scan_fwd") == {("mx:gdn", "mx:gdn:scan", "forward")}
    assert of("gdn_scan_bwd") == {("mx:gdn", "mx:gdn:scan", "backward")}
    assert of("gdn_local_bwd") == {("mx:gdn", "mx:gdn:local", "backward")}
    # the backward rule calls ``gdn_local_fwd`` too, to recompute the chunks
    # (where a mirror stage recomputes the forward, XLA merges the two)
    assert of("gdn_local_fwd") == {("mx:gdn", "mx:gdn:local", "forward"),
                                   ("mx:gdn", "mx:gdn:local", "backward")}


@pytest.mark.slow
def test_compiled_expert_layer_moves_no_array_of_all_the_choices(v5e_device):
    """The expert layer of `qwen3next-train-s8k-b2` (16,384 tokens x top-10
    over 512 experts, 32 held), forward and backward under
    ``jax.checkpoint``, compiled for the v5e: no gather, scatter or select,
    alone or in a fusion, makes an array of tokens x top-k rows of the
    hidden or the expert width: the rows moved are a round's 20,480; and
    both loops over the rounds are there."""
    import re
    from jax.sharding import SingleDeviceSharding
    n, h, experts, held, k, width = 16384, 2048, 512, 32, 10, 512
    cap = lm_ops.moe_capacity(n * k, held, experts)
    assert cap == 20480
    layer = jax.checkpoint(lambda x, r, g, u, d: lm_ops._moe_experts(
        x, r, g, u, d, num_experts=experts, num_hidden=width,
        experts_held=held, first_expert=held, top_k=k)[0])
    avals = [_aval(s, "bfloat16") for s in (
        (n, h), (experts, h), (held, h, width), (held, h, width),
        (held, width, h))]
    text = jax.jit(
        jax.grad(lambda *a: jnp.sum(layer(*a).astype(jnp.float32) ** 2),
                 argnums=(0, 1, 2, 3, 4)),
        in_shardings=(SingleDeviceSharding(v5e_device),) * 5).trace(*avals) \
        .lower(lowering_platforms=("tpu",)).compile().as_text()
    assert not re.search(r"\[%d,(%d|%d)\]" % (n * k, h, width), text)
    assert not re.search(r"\[%d,%d,(%d|%d)\]" % (n, k, h, width), text)
    assert re.search(r"(bf16|f32)\[%d,%d\]\S* gather\(" % (cap, h), text)
    assert re.search(r"f32\[%d,%d\]\S* scatter\(" % (n, h), text)
    assert text.count(" while(") == 2

"""Device time by mechanism (docs/observability.md §1): every op of a step
program is traced under an ``mx:`` scope, ``FusedTrainStep.op_scopes()`` is
the table from compiled instruction to scope and pass, ``Module.fit`` hands
it to ``instrument`` under an open profiler session and not without, and
``instrument.device_seconds_by_scope`` is a partition of a trace's seconds.
The toy language models of the other test files and a toy ResNet block,
compiled on the CPU."""
import collections
import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import executor_cache, models
from mxnet_tpu.module import fused_step
from mxnet_tpu.observability import instrument, tracing
from mxnet_tpu.ops import lm_ops

import test_joyai_flash
import test_qwen3_next
import test_trinity

LMS = {"qwen3next": (models.qwen3_next, test_qwen3_next),
       "trinity": (models.trinity, test_trinity),
       "joyai": (models.joyai_flash, test_joyai_flash)}
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\s([\w\-]+)\(")
# opcodes that name a value and run nothing
FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}


def _fit_lm(which, recompute=True):
    family, toy = LMS[which]
    params = toy._params(toy.CFG, seed=70, scale=0.2)
    xs, ys = toy._tokens(2)
    mod = mx.mod.Module(family.get_symbol(toy.CFG, recompute=recompute),
                        context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=toy.BATCH), num_epoch=1,
            eval_metric="loss", optimizer="adam",
            optimizer_params=dict(learning_rate=1e-2, epsilon=1e-3),
            arg_params={n: mx.nd.NDArray(a) for n, a in params.items()})
    assert mod._fused_step is not None and mod._fused_step.ran
    return mod


def _resnet_block():
    data = mx.sym.Variable("data")
    x = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           no_bias=True, name="conv0")
    x = mx.sym.Activation(mx.sym.BatchNorm(x, name="bn0"), act_type="relu")
    y = mx.sym.Convolution(x, num_filter=8, kernel=(3, 3), pad=(1, 1),
                           no_bias=True, name="conv1")
    x = x + mx.sym.BatchNorm(y, name="bn1")
    x = mx.sym.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max")
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _fit_resnet(batches=1):
    r = np.random.RandomState(0)
    xs = r.normal(size=(8 * batches, 3, 8, 8)).astype(np.float32)
    ys = r.randint(0, 4, (8 * batches,)).astype(np.float32)
    mod = mx.mod.Module(_resnet_block(), context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(xs, ys, batch_size=8), num_epoch=1,
            optimizer="sgd", initializer=mx.initializer.Xavier(),
            optimizer_params=dict(learning_rate=0.1, momentum=0.9))
    assert mod._fused_step is not None and mod._fused_step.ran
    return mod


@pytest.fixture(scope="module")
def steps():
    """{model: its fused step after one fit}, each compiled once."""
    made = {}

    def get(which):
        if which not in made:
            made[which] = (_fit_resnet() if which == "resnet"
                           else _fit_lm(which))._fused_step
        return made[which]
    return get


def _seen(table):
    """{(mechanism, detail): the passes it appears with}."""
    seen = collections.defaultdict(set)
    for row in table.values():
        seen[row["mechanism"], row["detail"]].add(row["pass"])
    return seen


def _opcodes(text):
    return [m.group(2) for m in map(INSTRUCTION.match, text.splitlines())
            if m]


# -- the rule: token, mechanism, detail, pass -----------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(_step)/jvp(mx:mlp)/dot_general",
     ("mx:mlp", "mx:mlp", "mx:mlp", "forward")),
    ("jit(_step)/transpose(jvp(mx:mtp/mx:mla))/mx:attn/mx:attn:full/jit(run)"
     "/flash_attn_bwd_dq/pallas_call",
     ("mx:mtp/mx:mla/mx:attn/mx:attn:full", "mx:attn", "mx:attn:full",
      "backward")),
    ("jit(_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/"
     "mx:op:moe_experts/mx:moe/mx:moe:gather/gather",
     ("mx:op:moe_experts/mx:moe/mx:moe:gather", "mx:moe", "mx:moe:gather",
      "recomputed")),
    ("jit(_step)/transpose(jvp(jvp()))/checkpoint/mx:op:gated_delta_rule/"
     "mx:gdn/mx:gdn:scan/jit(run)/gdn_scan_bwd/pallas_call",
     ("mx:op:gated_delta_rule/mx:gdn/mx:gdn:scan", "mx:gdn", "mx:gdn:scan",
      "backward")),
    ("jit(_step)/mx:update/sub", ("mx:update",) * 3 + ("forward",)),
    ("jit(_step)/jvp(mx:op:Convolution)/conv_general_dilated",
     ("mx:op:Convolution", "mx:op", "mx:op:Convolution", "forward")),
    ("jit(_step)/jvp(mx:mtp)/mx:head/dot_general",
     ("mx:mtp/mx:head", "mx:head", "mx:head", "forward")),
    ("ragged-dot-none", ("", None, None, "forward")),
    ("", ("", None, None, "forward")),
])
def test_scope_of_op_name(op_name, want):
    row = instrument.scope_of_op_name(op_name)
    assert (row["path"], row["mechanism"], row["detail"], row["pass"]) == want


def test_an_instruction_without_a_scope_takes_its_readers_else_its_source():
    text = """HloModule m
%fused (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0)
  ROOT %n = f32[2]{0} negate(%p), metadata={op_name="jit(f)/jvp(mx:a)/neg"}
}
ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2]{0} parameter(0)
  %copy.1 = f32[2]{0} copy(%x)
  %call.2 = f32[2]{0} custom-call(%copy.1), metadata={op_name="made-by-xla"}
  %fusion.3 = f32[2]{0} fusion(%call.2), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/transpose(jvp(mx:b:c))/mul"}
  %copy.4 = f32[2]{0} copy(%fusion.3)
  ROOT %tuple.5 = (f32[2]{0}) tuple(%copy.4)
}
"""
    table = instrument.scopes_of_hlo(text)
    assert set(table) == {"p", "n", "x", "copy.1", "call.2", "fusion.3",
                          "copy.4", "tuple.5"}
    own = table["fusion.3"]
    assert (own["mechanism"], own["detail"], own["pass"]) \
        == ("mx:b", "mx:b:c", "backward") and "own" not in own
    # read by the fusion, through one another
    for name in ("x", "copy.1", "call.2"):
        assert table[name] == dict(own, own=False), name
    # read by nothing that has a scope: what they read
    for name in ("copy.4", "tuple.5"):
        assert table[name] == dict(own, own=False), name
    # a computation of its own: the fusion's inside is the root's
    assert table["p"]["mechanism"] == table["n"]["mechanism"] == "mx:a"


def test_a_loop_s_event_spans_its_body_s_and_is_left_out():
    """A ``while`` is marked as holding other computations, and its seconds
    (which a trace reports beside its body's own events) are summed
    nowhere: the rows are the body's."""
    text = """HloModule m
%body (p: (s32[], f32[2])) -> (s32[], f32[2]) {
  %p = (s32[], f32[2]{0}) parameter(0)
  %g = f32[2]{0} get-tuple-element(%p), index=1
  %n = f32[2]{0} negate(%g), metadata={op_name="jit(f)/mx:moe/while/body/mx:moe:gather/neg"}
  ROOT %t = (s32[], f32[2]{0}) tuple(%p, %n)
}
ENTRY %main (x: (s32[], f32[2])) -> (s32[], f32[2]) {
  %x = (s32[], f32[2]{0}) parameter(0)
  %while.1 = (s32[], f32[2]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(f)/mx:moe/while"}
  ROOT %copy.2 = (s32[], f32[2]{0}) copy(%while.1)
}
"""
    table = instrument.scopes_of_hlo(text)
    assert table["while.1"]["holds"] and table["while.1"]["detail"] == "mx:moe"
    assert not any(row.get("holds") for name, row in table.items()
                   if name != "while.1")
    rows = instrument.device_seconds_by_scope(
        {"while.1 while": 3.0, "n negate": 2.5, "copy.2 copy": 0.25}, table)
    by = {(r["detail"], r["pass"]): r["seconds"] for r in rows}
    assert by == {("mx:moe:gather", "forward"): 2.5,
                  ("mx:moe", "forward"): 0.25}


# -- every op of a step program -------------------------------------------------------

EXPECTED = {
    "qwen3next": [("mx:attn", "mx:attn:full"), ("mx:gdn", "mx:gdn"),
                  ("mx:gdn", "mx:gdn:local"), ("mx:gdn", "mx:gdn:scan"),
                  ("mx:moe", "mx:moe:route"), ("mx:moe", "mx:moe:shared"),
                  ("mx:op", "mx:op:RMSNorm"),
                  ("mx:op", "mx:op:FullyConnected")],
    # a norm reads the expert layer's output here, so its backward needs it
    # and the stage runs the rounds again; the other two add it to the
    # residual stream and recompute the routing alone
    "trinity": [("mx:attn", "mx:attn:window"), ("mx:attn", "mx:attn:full"),
                ("mx:mlp", "mx:mlp"), ("mx:moe", "mx:moe:gather"),
                ("mx:moe", "mx:moe:experts"), ("mx:moe", "mx:moe:scatter"),
                ("mx:moe", "mx:moe:shared")],
    "joyai": [("mx:attn", "mx:attn:full"), ("mx:mla", "mx:mla"),
              ("mx:mlp", "mx:mlp"), ("mx:mtp", "mx:mtp"),
              ("mx:moe", "mx:moe:route"), ("mx:moe", "mx:moe:shared")],
}
# the rounds of the expert layer: the backward runs each round's forward
# itself and pulls it back (``lm_ops._moe_held``), all of it ``backward``
ROUNDS = [("mx:moe", "mx:moe:gather"), ("mx:moe", "mx:moe:experts"),
          ("mx:moe", "mx:moe:scatter")]
# outside every mirror stage: never recomputed
UNMIRRORED = [("mx:head", "mx:head"), ("mx:embed", "mx:embed")]


@pytest.mark.parametrize("which", ["qwen3next", "trinity", "joyai", "resnet"])
def test_every_instruction_maps_and_few_to_none(which, steps):
    step = steps(which)
    text, table = step.compiled_hlo(), step.op_scopes()
    found = [m for m in map(INSTRUCTION.match, text.splitlines()) if m]
    assert len(found) > 100 and {m.group(1) for m in found} == set(table)
    assert all(set(row) - {"own", "holds"}
               == {"path", "mechanism", "detail", "pass"}
               and row["pass"] in ("forward", "recomputed", "backward")
               for row in table.values())
    runs = [m.group(1) for m in found if m.group(2) not in FREE]
    unscoped = [n for n in runs if table[n]["mechanism"] is None]
    assert len(unscoped) < 0.05 * len(runs), (len(unscoped), len(runs))
    # on the instructions' own names alone, no neighbour asked
    unnamed = [n for n in runs if not table[n].get("own", True)]
    assert len(unnamed) < 0.25 * len(runs), (len(unnamed), len(runs))


@pytest.mark.parametrize("which", sorted(EXPECTED))
def test_each_mechanism_forward_and_backward_recomputed_in_stages(which,
                                                                  steps):
    step = steps(which)
    assert step.prog.mirror_stages > 0
    seen = _seen(step.op_scopes())
    for key in EXPECTED[which]:
        assert seen[key] >= {"forward", "backward", "recomputed"}, \
            (key, seen[key])
    for key in ROUNDS:
        assert seen[key] >= {"forward", "backward"}, (key, seen[key])
    for key in UNMIRRORED:
        assert seen[key] == {"forward", "backward"}, (key, seen[key])
    assert seen["mx:update", "mx:update"] == {"forward"}


def test_second_head_nests_under_the_prediction_module(steps):
    paths = {row["path"] for row in steps("joyai").op_scopes().values()
             if row["mechanism"] == "mx:head"}
    assert "mx:head" in paths and "mx:mtp/mx:head" in paths
    embeds = {row["path"] for row in steps("joyai").op_scopes().values()
              if row["mechanism"] == "mx:embed"}
    assert embeds == {"mx:embed", "mx:mtp/mx:embed"}


def test_no_mirror_stage_nothing_recomputed():
    table = _fit_lm("trinity", recompute=False)._fused_step.op_scopes()
    assert "recomputed" not in {row["pass"] for row in table.values()}
    assert {"forward", "backward"} <= _seen(table)["mx:moe", "mx:moe:gather"]


def test_resnet_block_ops_by_name_and_the_update(steps):
    step = steps("resnet")
    assert step.prog.mirror_stages == 0
    seen = _seen(step.op_scopes())
    for op in ("Convolution", "BatchNorm", "Pooling", "FullyConnected",
               "SoftmaxOutput"):
        assert seen["mx:op", "mx:op:" + op] >= {"forward", "backward"}, op
    assert all("recomputed" not in passes for passes in seen.values())
    # SGD with momentum on six parameters: the optimizer's ops and no other
    text, table = step.compiled_hlo(), step.op_scopes()
    update = collections.Counter(
        m.group(2) for m in map(INSTRUCTION.match, text.splitlines())
        if m and table[m.group(1)]["mechanism"] == "mx:update"
        and table[m.group(1)].get("own", True))
    assert update and not set(update) & {"convolution", "dot", "reduce-window"}
    assert update["multiply"] + update["fusion"] >= 6


def test_backward_kernels_carry_their_scope_and_pass():
    """The delta rule's kernels through the interpreter, whose ops keep the
    kernel's name on their path: what ``gdn_scan_bwd`` runs is
    ``mx:gdn:scan`` backward, what ``gdn_local_bwd`` runs is
    ``mx:gdn:local`` backward, and ``gdn_local_fwd`` is ``mx:gdn:local``
    forward (the backward rule calls it too, to recompute the chunks: XLA
    may merge that call with the forward's, one name surviving)."""
    r = np.random.RandomState(0)
    q = jnp.asarray(r.normal(size=(1, 2, 128, 128)), jnp.float32)
    v = jnp.asarray(r.normal(size=(1, 2, 1, 128, 128)), jnp.float32)
    g = -jnp.abs(jnp.asarray(r.normal(size=(1, 2, 1, 128)), jnp.float32))

    def f(q, k, v, g, beta):
        with jax.named_scope("mx:gdn"):
            return jnp.sum(lm_ops._make_gdr(64, "interpret")(q, k, v, g,
                                                             beta))

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4))).lower(
        q, q, v, g, jax.nn.sigmoid(g)).compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")]
    rows = {n: instrument.scope_of_op_name(n) for n in names}
    of = lambda kernel: {(r["detail"], r["pass"]) for n, r in rows.items()
                         if "/%s/" % kernel in n}
    assert of("gdn_scan_bwd") == {("mx:gdn:scan", "backward")}
    assert of("gdn_scan_fwd") == {("mx:gdn:scan", "forward")}
    assert of("gdn_local_bwd") == {("mx:gdn:local", "backward")}
    assert ("mx:gdn:local", "forward") in of("gdn_local_fwd")
    assert of("gdn_local_fwd") <= {("mx:gdn:local", "forward"),
                                   ("mx:gdn:local", "backward")}


def test_flash_backward_kernels_carry_their_scope_and_pass(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS_ATTN", "1")
    from mxnet_tpu.ops import attention
    r = np.random.RandomState(1)
    q = jnp.asarray(r.normal(size=(1, 256, 2, 128)), jnp.float32)

    def f(q, k, v):
        return jnp.sum(attention._sdpa(q, k, v, causal=True))

    text = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(q, q, q) \
        .compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if n.startswith("jit(")]
    rows = {n: instrument.scope_of_op_name(n) for n in names}
    for kernel in ("flash_attn_bwd_dq", "flash_attn_bwd_dkv"):
        got = {(r["detail"], r["pass"]) for n, r in rows.items()
               if "/%s/" % kernel in n}
        assert got == {("mx:attn:full", "backward")}, (kernel, got)
    assert {(r["detail"], r["pass"]) for n, r in rows.items()
            if "/flash_attn_fwd/" in n} == {("mx:attn:full", "forward")}


# -- scopes are metadata --------------------------------------------------------------

@pytest.mark.parametrize("which", ["qwen3next", "resnet"])
def test_the_program_is_the_same_with_the_scopes_stripped(which, steps,
                                                          monkeypatch):
    with_scopes = _opcodes(steps(which).compiled_hlo())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    step = (_fit_resnet() if which == "resnet"
            else _fit_lm(which))._fused_step
    assert not any(row["mechanism"] for row in step.op_scopes().values())
    stripped = _opcodes(step.compiled_hlo())
    assert len(stripped) == len(with_scopes)
    assert collections.Counter(stripped) == collections.Counter(with_scopes)


def test_the_resnet_step_holds_nothing_of_the_expert_layer(steps,
                                                          monkeypatch):
    """The cells that bypass the expert layer: no instruction of the ResNet
    step lies under ``mx:moe``, and its program is the same, opcode for
    opcode, with the expert layer's code taken away."""
    with_experts = _opcodes(steps("resnet").compiled_hlo())
    assert not any(row["mechanism"] == "mx:moe"
                   for row in steps("resnet").op_scopes().values())

    def gone(*args, **kwargs):
        raise AssertionError("the ResNet step reached moe_experts")
    for name in ("_moe_experts", "_moe_held", "_moe_round", "moe_capacity"):
        monkeypatch.setattr(lm_ops, name, gone)
    without = _opcodes(_fit_resnet()._fused_step.compiled_hlo())
    assert without == with_experts


def test_the_rounds_row_moves_keep_their_scopes_inside_the_loops():
    """A layer of four rounds (tests/test_moe_rows.py's), forward and
    backward under ``jax.checkpoint``: the forward's and the backward's
    ``while`` bodies hold the row moves under ``mx:moe:gather`` and
    ``mx:moe:scatter``, the backward's with the transposes (a scatter-add of
    the rows' gradient under ``gather``, a gather of the output's gradient
    under ``scatter``), all ``backward``: no round is ``recomputed``."""
    import test_moe_rows as rows
    x, w = rows._layer(3)
    layer = jax.checkpoint(lambda x, w: lm_ops._moe_experts(
        x, w["router"], w["gate"], w["up"], w["down"], num_experts=rows.E,
        num_hidden=rows.I, experts_held=rows.HELD, first_expert=rows.FIRST,
        top_k=rows.K)[0])
    text = jax.jit(jax.grad(lambda x, w: jnp.sum(layer(x, w) ** 2),
                            argnums=(0, 1))).lower(x, w).compile().as_text()
    assert text.count(" while(") == 2
    names = [n for n in re.findall(r'op_name="([^"]*)"', text)
             if "/while/body/" in n and "mx:moe:" in n]
    seen = collections.defaultdict(set)
    for n in names:
        row = instrument.scope_of_op_name(n)
        seen[row["detail"], row["pass"]].add(n.rsplit("/", 1)[-1])
    assert "gather" in seen["mx:moe:gather", "forward"]
    assert "scatter-add" in seen["mx:moe:scatter", "forward"]
    assert {"gather", "scatter-add"} <= seen["mx:moe:gather", "backward"]
    # the output's gradient is gathered; the round's own sum is dead there
    assert "gather" in seen["mx:moe:scatter", "backward"]
    assert "scatter-add" not in seen["mx:moe:scatter", "backward"]
    assert seen["mx:moe:experts", "forward"] \
        and seen["mx:moe:experts", "backward"]
    assert not any(p == "recomputed" for _, p in seen)


def test_compiled_hlo_is_compiled_once(steps, monkeypatch):
    step = steps("resnet")
    first = step.compiled_hlo()
    monkeypatch.setattr(step, "_step_jit", None)    # a second lower() raises
    assert step.compiled_hlo() is first and step.op_scopes() is \
        step.op_scopes()
    assert fused_step.collective_counts(first)["all-reduce"] == 0


# -- the capture at the end of fit -------------------------------------------------

@contextlib.contextmanager
def _profiler_session(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _traces_of(fit):
    before = executor_cache.trace_counts()
    fit()
    after = executor_cache.trace_counts()
    return {k: after[k] - before.get(k, 0) for k in after}


def test_captured_under_an_open_session_and_not_without(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(instrument, "_op_scopes", {})
    assert not tracing.device_trace_open()
    plain = _traces_of(lambda: _fit_resnet(batches=2))
    assert instrument.device_op_scopes() == {}          # no session, no table
    with _profiler_session(tmp_path):
        assert tracing.device_trace_open()
        kept = []
        traced = _traces_of(lambda: kept.append(_fit_resnet(batches=2)))
        mod = kept.pop()
        table = instrument.device_op_scopes()
        assert table == mod._fused_step.op_scopes() and len(table) > 100
        del mod                     # the table outlives the module
    assert not tracing.device_trace_open()
    assert traced == plain          # the capture traces nothing again
    assert instrument.device_op_scopes() == table


def test_not_captured_with_telemetry_off(tmp_path, monkeypatch):
    monkeypatch.setattr(instrument, "_op_scopes", {})
    monkeypatch.setenv("MXNET_TPU_TELEMETRY", "0")
    with _profiler_session(tmp_path):
        _fit_resnet()
    assert instrument.device_op_scopes() == {}


def test_a_later_capture_of_a_label_replaces_the_earlier(monkeypatch):
    monkeypatch.setattr(instrument, "_op_scopes", {})
    row = instrument.scope_of_op_name("jit(f)/mx:a/add")
    instrument.capture_device_op_scopes("p", {"add.1": row})
    instrument.capture_device_op_scopes("q", {"mul.2": row})
    instrument.capture_device_op_scopes("p", {"add.3": row})
    instrument.capture_device_op_scopes("r", None)      # nothing to keep
    assert set(instrument.device_op_scopes()) == {"mul.2", "add.3"}


# -- the partition -------------------------------------------------------------------

def test_device_seconds_by_scope_is_a_partition():
    row = instrument.scope_of_op_name
    scopes = {"fusion.1": row("jit(s)/jvp(mx:attn)/mx:attn:full/dot"),
              "fusion.2": row("jit(s)/transpose(jvp(mx:attn))/mx:attn:full/d"),
              "fusion.3": row("jit(s)/jvp(mx:attn)/mx:attn:full/exp"),
              "copy.4": row("")}
    op_seconds = {"fusion.1 fusion": 0.25, "%fusion.2 = f32[2] fusion(": 0.5,
                  "fusion.3": 1.0, "copy.4 copy": 0.125,
                  "fusion.9 fusion": 2.0}       # another program's
    rows = instrument.device_seconds_by_scope(op_seconds, scopes)
    assert sum(r["seconds"] for r in rows) == sum(op_seconds.values())
    by = {(r["mechanism"], r["detail"], r["pass"]): r["seconds"]
          for r in rows}
    assert by == {("mx:attn", "mx:attn:full", "forward"): 1.25,
                  ("mx:attn", "mx:attn:full", "backward"): 0.5,
                  (None, None, "forward"): 0.125,
                  (None, None, None): 2.0}


def test_a_traced_fit_reads_device_time_by_mechanism(tmp_path, monkeypatch):
    """The operator's five lines (docs/observability.md §1): a traced fit,
    the trace's HLO events by name, ``device_seconds_by_scope``."""
    monkeypatch.setattr(instrument, "_op_scopes", {})
    with _profiler_session(tmp_path):
        _fit_resnet(batches=3)
    found = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    seconds = collections.Counter()
    for plane in jax.profiler.ProfileData.from_file(str(found[0])).planes:
        for line in plane.lines:
            for e in line.events:
                if any(k == "hlo_op" for k, _ in e.stats):
                    seconds[e.name] += e.duration_ns * 1e-9
    rows = instrument.device_seconds_by_scope(seconds)
    assert abs(sum(r["seconds"] for r in rows) - sum(seconds.values())) < 1e-9
    named = sum(r["seconds"] for r in rows if r["mechanism"] is not None)
    assert named > 0.5 * sum(seconds.values())
    assert any(r["detail"] == "mx:op:Convolution" and r["pass"] == "backward"
               for r in rows)

"""Executor: binds a Symbol to a device and runs it as ONE XLA computation.

TPU-native rebuild of src/executor/graph_executor.{h,cc} (1.9k LoC) +
python/mxnet/executor.py.  The reference's Init pipeline (InitFullGraph ->
PlaceDevice -> PlanMemory -> AttachOpExecs -> InitCachedOps -> per-node
engine pushes in RunOps) collapses to: build a pure python evaluator over
the graph, `jax.jit` it whole, and let XLA do memory planning, fusion and
scheduling — the north-star design from BASELINE.json.  Backward is the
jitted vjp of the same computation (gradient pass == jax.vjp instead of
nnvm::pass::Gradient), sharing the forward's RNG keys so dropout masks
match between forward and backward.
"""
from __future__ import annotations

import os
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, np_dtype
from .context import current_context
from .log import module_logger as _module_logger
from .observability import memprof as _memprof
from .ops.registry import get_op
from .ndarray import NDArray, zeros as nd_zeros
from .ndarray.ndarray import _Handle
from . import executor_cache
from . import random as _random


# MXNet's mirroring attribute (``MXNET_BACKWARD_DO_MIRROR``'s per-node form):
# nodes that share a value of it are recomputed together in the backward pass
MIRROR_STAGE = "__mirror_stage__"
# nodes that carry this attribute are lowered under ``jax.named_scope`` of its
# value: a model's builder names a group of plain nodes (a dense MLP) for the
# device trace, as the ops that are one node name themselves (``mx:attn``).
# Every other node is lowered under ``mx:op:<its op>``, so that each op of a
# compiled program says which mechanism it belongs to
# (``FusedTrainStep.op_scopes``, docs/observability.md)
NAMED_SCOPE = "__named_scope__"
OP_SCOPE = "mx:op:"


def _to_device(arr, dev):
    """Move `arr` to `dev` unless already there (single shared impl for
    every cross-device placement site in this file)."""
    return arr if arr.devices() == {dev} else jax.device_put(arr, dev)


@contextmanager
def _oom_guard(what):
    """OOM black box over one program dispatch: RESOURCE_EXHAUSTED
    writes the augmented flight dump (per-program memory table, buffer
    census, allocator peaks) before the error propagates; every other
    exception passes through untouched (observability/memprof.py)."""
    try:
        yield
    except Exception as exc:
        _memprof.maybe_record_oom(what, exc)
        raise



class _Program:
    """Compiled form of a symbol graph: closures + metadata."""

    def __init__(self, symbol):
        self.symbol = symbol
        self.order = symbol._topo()
        symbol._mark_aux(self.order)
        self.arg_names = [n.name for n in self.order if n.is_var and not n._is_aux]
        self.aux_names = [n.name for n in self.order if n.is_var and n._is_aux]
        self.var_nodes = {n.name: n for n in self.order if n.is_var}
        self.entries = list(symbol._entries)
        # nodes needing RNG keys, in topo order
        self.rng_nodes = [n for n in self.order
                          if not n.is_var and get_op(n.op_name).needs_rng]
        # init-op nodes (zeros/ones/... with a `shape` attr) whose literal
        # shape has unknown (0) dims — e.g. RNN begin_state zeros with
        # batch 0 — take their real shape from graph inference at bind
        # (the reference allocates by inferred shape via PlanMemory)
        self._shape_overrides = {}
        self._unit_order = None

    def finalize_shapes(self, known_shapes):
        """Resolve 0-dim init-op shapes from inference given bound arg
        shapes ({name: shape})."""
        needs = [n for n in self.order
                 if not n.is_var and "shape" in get_op(n.op_name).params
                 and n.attrs.get("shape")
                 and any(int(d) == 0 for d in
                         get_op(n.op_name).normalize_attrs(n.attrs)
                         .get("shape") or ())]
        if not needs:
            return
        shapes, _ = self.symbol._infer(dict(known_shapes), {})
        for n in needs:
            s = shapes.get((n, 0))
            if s is not None and all(int(d) != 0 for d in s):
                self._shape_overrides[n] = tuple(int(d) for d in s)
            else:
                # fail at bind with an actionable message instead of a
                # ZeroDivisionError deep inside the jitted graph
                raise MXNetError(
                    "cannot resolve unknown dims of init op %r (shape %s) "
                    "from bound argument shapes %s; pass full shapes to "
                    "bind/simple_bind" % (
                        n.op_name, n.attrs.get("shape"), dict(known_shapes)))

    def evaluate(self, arg_map, aux_map, keys, train, tap=None):
        """Evaluate the graph given {name: jax.Array} maps.  Returns
        (outputs, new_aux_map).  Pure — safe to jit/vjp.

        Nodes that carry one ``__mirror_stage__`` (MXNet's mirroring
        attribute, set by a model's builder on the nodes of a block) are
        evaluated together under ``jax.checkpoint``: a backward pass
        keeps what enters the stage and recomputes the rest.  A graph
        that sets no such attribute is evaluated node by node as ever."""
        env = {}
        new_aux = dict(aux_map)
        key_of = dict(zip(self.rng_nodes, keys))
        for node in self.order:
            if node.is_var:
                if node.name in arg_map:
                    env[(node, 0)] = arg_map[node.name]
                elif node.name in aux_map:
                    env[(node, 0)] = aux_map[node.name]
                else:
                    raise MXNetError("unbound variable %r" % node.name)

        def run(node, env, new_aux):
            op = get_op(node.op_name)
            attrs = op.normalize_attrs(node.attrs)
            if op.key_var_num_args and not attrs.get(op.key_var_num_args):
                attrs[op.key_var_num_args] = len(node.inputs)
            if node in self._shape_overrides:
                attrs["shape"] = self._shape_overrides[node]
            if op.takes_train_flag:
                attrs["_train"] = train
            ins = [env[e] for e in node.inputs]
            if op.needs_rng:
                ins = [key_of[node]] + ins
            with jax.named_scope(node.attrs.get(NAMED_SCOPE)
                                 or OP_SCOPE + node.op_name):
                out = op.impl(*ins, **attrs)
            if not isinstance(out, tuple):
                out = (out,)
            n_vis = node.num_outputs()
            for i in range(n_vis):
                env[(node, i)] = out[i]
            # state outputs fold back into aux values (BatchNorm moving stats)
            for extra, in_idx in zip(out[n_vis:], op.mutate_map):
                src_node, _ = node.inputs[in_idx]
                if src_node.is_var and src_node.name in new_aux:
                    new_aux[src_node.name] = extra
            if tap is not None:
                for i in range(n_vis):
                    tap(node, i, out[i])

        for unit in self._units():
            if isinstance(unit, _Stage) and tap is None and train:
                self._run_stage(unit, run, env, new_aux)
            else:
                for node in getattr(unit, "nodes", (unit,)):
                    run(node, env, new_aux)
        outputs = [env[e] for e in self.entries]
        return outputs, new_aux

    def _units(self):
        """The op nodes in an order of evaluation, each mirror stage
        gathered into one ``_Stage`` placed where its inputs are known."""
        if self._unit_order is not None:
            return self._unit_order
        ops = [n for n in self.order if not n.is_var]
        stage_of = {n: n.attrs.get(MIRROR_STAGE) for n in ops}
        if not any(stage_of.values()):
            self._unit_order = ops
            return ops
        stages = {}
        for n in ops:
            if stage_of[n]:
                stages.setdefault(stage_of[n], _Stage(stage_of[n])) \
                    .nodes.append(n)
        unit_of = {n: stages[stage_of[n]] if stage_of[n] else n for n in ops}
        units = list(dict.fromkeys(unit_of[n] for n in ops))
        needs = {u: {unit_of[src] for n in getattr(u, "nodes", (u,))
                     for src, _ in n.inputs if not src.is_var} - {u}
                 for u in units}
        order, done = [], set()
        while len(order) < len(units):
            ready = [u for u in units if u not in done and needs[u] <= done]
            if not ready:
                raise MXNetError(
                    "mirror stages %s feed each other: a stage's nodes must "
                    "need nothing that is computed from the stage itself"
                    % sorted(s.name for s in units
                             if isinstance(s, _Stage) and s not in done))
            order.append(ready[0])
            done.add(ready[0])
        read_by = {}
        for n in ops:
            for e in n.inputs:
                read_by.setdefault(e, []).append(n)
        for stage in stages.values():
            inside = set(stage.nodes)
            stage.inputs = list(dict.fromkeys(
                e for n in stage.nodes for e in n.inputs
                if e[0] not in inside))
            stage.outputs = [(n, i) for n in stage.nodes
                             for i in range(n.num_outputs())
                             if any(c not in inside
                                    for c in read_by.get((n, i), ()))
                             or (n, i) in self.entries]
            stage.aux = [src.name for n in stage.nodes
                         for in_idx in get_op(n.op_name).mutate_map
                         for src in [n.inputs[in_idx][0]] if src.is_var]
        self._unit_order = order
        return order

    @property
    def mirror_stages(self):
        """How many blocks a training pass of this graph recomputes."""
        return sum(isinstance(u, _Stage) for u in self._units())

    def attention_pairs(self, shapes, dtypes):
        """(computed, visible) (query, key) pairs of one training pass over
        this graph's attention nodes at the bound argument ``shapes`` and
        ``dtypes`` ({name: ...}), summed over nodes and heads apart:
        ``ops.pallas_kernels.attention_pairs`` of each, resolved under the
        caller's ``trace_scope``.  (0, 0) for a graph without attention."""
        nodes = [n for n in self.order if not n.is_var and n.op_name in (
            "scaled_dot_product_attention", "multi_head_attention")]
        if not nodes:
            return 0, 0
        from .ops import pallas_kernels
        at, kinds = self.symbol._infer(dict(shapes), dict(dtypes))
        computed = visible = 0
        for n in nodes:
            attrs = get_op(n.op_name).normalize_attrs(n.attrs)
            q, k = at[n.inputs[0]], at[n.inputs[1]]
            if n.op_name == "multi_head_attention":
                heads = int(attrs["num_heads"])
                width = int(attrs["num_hidden"]) or int(q[-1])
                q, k = ((int(s[0]), int(s[1]), heads, width // heads)
                        for s in (q, k))
            widths = {}
            if n.op_name == "scaled_dot_product_attention":
                widths["v_width"] = int(at[n.inputs[2]][-1])
                if attrs.get("use_shared_key"):
                    widths["shared_width"] = int(at[n.inputs[-1]][-1])
                widths["block_diffusion"] = int(
                    attrs.get("block_diffusion") or 0)
            c, v = pallas_kernels.attention_pairs(
                q, k, kinds[n.inputs[0]], bool(attrs["causal"]),
                int(attrs.get("window") or 0), **widths)
            computed, visible = computed + c, visible + v
        return computed, visible

    def gdn_chunk_steps(self, shapes, dtypes):
        """(chunk steps, those of them in the Pallas scan kernels, those
        whose chunk-local part runs in the Pallas local kernels) of one
        training pass over this graph's ``gated_delta_rule`` nodes at the
        bound argument ``shapes`` and ``dtypes``: chunks x batch x key heads
        a pass of a node, and a node makes two passes forward where its
        mirror stage is recomputed, one where not, and one backward.  The
        second number is the first's where ``ops.gdn_kernels.mode`` takes
        the node's shape under the caller's ``trace_scope``, the third where
        ``gdn_kernels.local_planned`` takes it too; else 0.  (0, 0, 0) for a
        graph without such a node."""
        nodes = [n for n in self.order
                 if not n.is_var and n.op_name == "gated_delta_rule"]
        if not nodes:
            return 0, 0, 0
        from .ops import gdn_kernels
        at, kinds = self.symbol._infer(dict(shapes), dict(dtypes))
        steps = in_kernel = local = 0
        for n in nodes:
            chunk = int(get_op(n.op_name).normalize_attrs(n.attrs)["chunk"])
            (b, t, hk, dk), value = at[n.inputs[0]], at[n.inputs[2]]
            r = int(value[2]) // int(hk)
            passes = 3 if n.attrs.get(MIRROR_STAGE) else 2
            mine = passes * -(-int(t) // chunk) * int(b) * int(hk)
            steps += mine
            q, v = (b, hk, t, dk), (b, hk, r, t, value[3])
            if gdn_kernels.mode(q, v, chunk, kinds[n.inputs[2]]):
                in_kernel += mine
                if gdn_kernels.local_planned(q, v, chunk, kinds[n.inputs[2]]):
                    local += mine
        return steps, in_kernel, local

    def _run_stage(self, stage, run, env, new_aux):
        def body(ins):
            local, aux = dict(zip(stage.inputs, ins)), {}
            for name in stage.aux:
                aux[name] = new_aux[name]
            for node in stage.nodes:
                run(node, local, aux)
            return [local[e] for e in stage.outputs], aux

        outs, aux = jax.checkpoint(body)([env[e] for e in stage.inputs])
        env.update(zip(stage.outputs, outs))
        new_aux.update(aux)


class _Stage:
    """The nodes of one ``__mirror_stage__``, in the graph's order, with
    the entries that enter it and those that leave it."""

    def __init__(self, name):
        self.name = name
        self.nodes = []
        self.inputs = self.outputs = self.aux = None


class Executor:
    def __init__(self, symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req):
        self._symbol = symbol
        self._ctx = ctx
        if os.environ.get("MXNET_TPU_VERIFY_GRAPH") == "1":
            # opt-in bind-time verifier (nnvm validation-pass analog):
            # structural checks only — a malformed graph fails here, with
            # a named node, BEFORE _Program's own get_op walk can throw a
            # nameless registry error.  Shape completeness is the
            # executor's own job (finalize_shapes / jit tracing), so it
            # is not re-judged here.
            from .analysis.graph_verify import verify_graph
            report = verify_graph(symbol)
            if not report.ok:
                raise MXNetError(
                    "MXNET_TPU_VERIFY_GRAPH: refusing to bind an invalid "
                    "graph:\n%s" % report.format())
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        arg_names = symbol.list_arguments()
        if isinstance(grad_req, str):
            grad_req = {k: grad_req for k in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(arg_names, grad_req))
        self._grad_req = {k: grad_req.get(k, "null") for k in arg_names}
        self._grad_names = [k for k in arg_names
                            if self._grad_req[k] != "null" and k in grad_dict
                            and grad_dict[k] is not None]
        self._has_add_req = any(self._grad_req[k] == "add"
                                for k in self._grad_names)
        self.outputs = []
        self._last_keys = None
        # backward() consistency state: the aux values the last forward
        # actually consumed (pre-update), whether a fused dispatch
        # already produced this step's gradients, and whether donation
        # destroyed the pre-update aux a re-dispatch would want
        self._last_aux_in = None
        self._fused_grads_valid = False
        self._aux_stash_lost = False
        self._monitor_callback = None
        self._monitor_all = False
        self._monitor_fallback_warned = False

        # process-wide program reuse (ref: CachedOp): identical
        # (graph, shapes, dtypes, grads) signatures share one traced
        # _Program + jitted fwd / fused fwd-bwd — a rebind, reshape, or
        # bucket revisit over a seen signature costs zero retracing
        entry = executor_cache.get_entry(
            symbol, arg_dict, aux_dict, tuple(self._grad_names),
            platform=ctx.jax_device().platform)
        self._prog = entry.prog
        self._fwd_jit = entry.fwd
        self._fwd_bwd_jit = entry.fwd_bwd
        self._fwd_bwd_nd_jit = entry.fwd_bwd_nd
        self._donates_aux = entry.donates_aux
        self._n_keys = entry.n_keys
        # health sentinel (MXNET_TPU_HEALTH=1, resolved at bind via the
        # cache key): fwd_bwd returns an extra packed numerics vector,
        # stashed on-device here until the training loop consumes it
        self._health_on = entry.health
        self.health_layout = entry.health_layout
        self._last_health = None

    # -- parameter access ----------------------------------------------------
    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._prog.arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._prog.arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._prog.aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def set_monitor_callback(self, callback, monitor_all=False):
        self._monitor_callback = callback
        self._monitor_all = monitor_all

    # -- execution -----------------------------------------------------------
    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %r" % k)
            dst = self.arg_dict[k]
            if isinstance(v, NDArray):
                src = v._h.array
            else:
                # graftlint: disable=GL001,GL003 — host->device UPLOAD
                # of user-fed python/numpy forward(**kwargs) data, not a
                # device sync or traced math
                src = jnp.asarray(np.asarray(v))
            if src.dtype != dst._h.array.dtype:
                src = src.astype(dst._h.array.dtype)
            # keep group2ctx placement
            dst._h.array = _to_device(src, next(iter(dst._h.array.devices())))
        arg_vals = self._gather([self.arg_dict[n]._h.array
                                 for n in self._prog.arg_names])
        aux_vals = self._gather([self.aux_dict[n]._h.array
                                 for n in self._prog.aux_names])
        keys = tuple(_random.next_key() for _ in range(self._n_keys))
        self._last_keys = keys
        # stash what this forward actually consumes so a later backward()
        # differentiates THIS evaluation: under is_train the aux_dict is
        # about to advance to the post-update values, and grads taken
        # against those would mismatch the recorded forward (BatchNorm
        # moving-stat ordering)
        self._last_aux_in = aux_vals
        self._fused_grads_valid = False
        self._aux_stash_lost = False

        if self._monitor_callback is not None:
            # monitor mode: run uncompiled so every op output can be tapped
            def tap(node, i, val):
                name = node.name + ("_output" if i == 0 else "_output%d" % i)
                self._monitor_callback(name, NDArray(val))

            arg_map = dict(zip(self._prog.arg_names, arg_vals))
            aux_map = dict(zip(self._prog.aux_names, aux_vals))
            outs, new_aux = self._prog.evaluate(arg_map, aux_map, keys,
                                                bool(is_train), tap=tap)
            new_aux = [new_aux[n] for n in self._prog.aux_names]
        else:
            from . import profiler as _profiler
            if _profiler.is_running():
                # symbolic-mode span: one event per jitted graph execution
                # (ref: kOnlySymbolic profiler mode, profiler.h:94-121)
                with _profiler.record_span(
                        "executor_forward", category="symbolic",
                        dev=str(self._ctx)), _oom_guard("executor_forward"):
                    outs, new_aux = self._fwd_jit(
                        arg_vals, aux_vals, keys, bool(is_train))
                    jax.block_until_ready(outs)
            else:
                with _oom_guard("executor_forward"):
                    outs, new_aux = self._fwd_jit(
                        arg_vals, aux_vals, keys, bool(is_train))
        if is_train:
            for n, v in zip(self._prog.aux_names, new_aux):
                buf = self.aux_dict[n]
                # aux stays on its group ctx
                buf._h.array = _to_device(v, next(iter(buf._h.array.devices())))
        self.outputs = [NDArray(o) for o in outs]
        return self.outputs

    def forward_backward(self, is_train=True, out_grads=None):
        """Forward AND backward as ONE fused jitted dispatch (tentpole
        dispatch model: a single XLA program per training step instead
        of a forward plus a recompute-forward vjp).  Outputs land in
        `self.outputs`, gradients in `grad_dict` (honoring grad_req),
        and aux states advance exactly as forward(is_train=True) +
        backward() would.  Falls back to the separate path when a
        monitor is installed, nothing takes gradients, or
        is_train=False."""
        if isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        if self._monitor_callback is not None or not self._grad_names \
                or not is_train \
                or (out_grads is not None
                    and any(g is None for g in out_grads)):
            # None head-grad entries mean ones_like(output) — outputs
            # only exist after a forward, so that form takes the
            # separate path
            if self._monitor_callback is not None \
                    and not self._monitor_fallback_warned:
                # once per executor: the fused one-program dispatch has
                # no tap points, so the monitor forces the separate
                # uncompiled path (satisfying the tap, at a perf cost)
                self._monitor_fallback_warned = True
                _module_logger(__name__).warning(
                    "monitor callback installed: forward_backward is "
                    "taking the separate tap-capable path (fused "
                    "fwd-bwd program skipped while the monitor is "
                    "active)")
            self.forward(is_train=is_train)
            if self._grad_names:
                self.backward(out_grads=out_grads)
            return self.outputs
        arg_vals = self._gather([self.arg_dict[n]._h.array
                                 for n in self._prog.arg_names])
        aux_vals = self._gather([self.aux_dict[n]._h.array
                                 for n in self._prog.aux_names])
        # aux write-back devices, captured BEFORE dispatch: on TPU the
        # fused program donates the aux input buffers
        aux_devs = [next(iter(self.aux_dict[n]._h.array.devices()))
                    for n in self._prog.aux_names]
        keys = tuple(_random.next_key() for _ in range(self._n_keys))
        self._last_keys = keys
        if out_grads is None:
            heads = ()  # ones head-grads are built inside the program
        else:
            heads = tuple(self._gather([g._h.array for g in out_grads]))
        from . import profiler as _profiler
        if _profiler.is_running():
            with _profiler.record_span(
                    "executor_fwd_bwd", category="symbolic",
                    dev=str(self._ctx)), _oom_guard("executor_fwd_bwd"):
                res = self._fwd_bwd_jit(arg_vals, aux_vals, keys, heads)
                jax.block_until_ready(res[0])
        else:
            with _oom_guard("executor_fwd_bwd"):
                res = self._fwd_bwd_jit(arg_vals, aux_vals, keys, heads)
        if self._health_on:
            outs, new_aux, grads, health_vec = res
            self._last_health = health_vec  # stays on device until read
        else:
            outs, new_aux, grads = res
        for n, v, dev in zip(self._prog.aux_names, new_aux, aux_devs):
            self.aux_dict[n]._h.array = _to_device(v, dev)
        self.outputs = [NDArray(o) for o in outs]
        self._store_grads(grads)
        # a later backward(out_grads) differentiates the aux this
        # dispatch consumed — unless donation already invalidated them
        self._last_aux_in = None if self._donates_aux else aux_vals
        self._aux_stash_lost = self._donates_aux \
            and bool(self._prog.aux_names)
        # a later backward() with default (ones) head-grads may reuse
        # these residuals instead of re-dispatching (grad_req='add'
        # excluded: an explicit backward() there means one more
        # accumulation, which the reuse would silently drop)
        self._fused_grads_valid = out_grads is None \
            and not self._has_add_req
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        if not self.outputs:
            raise MXNetError("backward() called before forward()")
        if not self._grad_names:
            return
        if out_grads is None and self._fused_grads_valid:
            # residual reuse: the preceding fused forward_backward()
            # already wrote exactly these gradients (ones head-grads)
            return
        # this call re-dispatches, so any previously fused gradients are
        # about to be overwritten — they must not satisfy a later reuse
        self._fused_grads_valid = False
        if out_grads is None:
            heads = ()  # ones built inside the fused program
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head_grads = [g._h.array if g is not None else
                          jnp.ones_like(o._h.array)
                          for g, o in zip(out_grads, self.outputs)]
            heads = tuple(self._gather(head_grads))  # user grads may live
            # on a group device; the jitted program computes on the bind ctx
        arg_vals = self._gather([self.arg_dict[n]._h.array
                                 for n in self._prog.arg_names])
        if self._last_aux_in is not None:
            # differentiate the aux values the recorded forward consumed,
            # not the post-update ones it produced
            aux_vals = self._last_aux_in
        else:
            if self._aux_stash_lost:
                import warnings
                warnings.warn(
                    "backward() after a fused forward_backward() on a "
                    "donating backend: the pre-update aux states were "
                    "donated into the fused program, so these gradients "
                    "differentiate the POST-update aux values (e.g. "
                    "advanced BatchNorm moving stats). Run forward("
                    "is_train=True) before backward() for exact "
                    "pre-update semantics.", stacklevel=2)
            aux_vals = self._gather([self.aux_dict[n]._h.array
                                     for n in self._prog.aux_names])
        keys = self._last_keys or tuple(_random.next_key()
                                        for _ in range(self._n_keys))
        # the NON-donating twin: these aux buffers stay live (the stash,
        # or aux_dict itself) and must survive the dispatch
        with _oom_guard("executor_backward"):
            res = self._fwd_bwd_nd_jit(arg_vals, aux_vals, keys, heads)
        if self._health_on:
            self._last_health = res[3]
        self._store_grads(res[2])

    def _store_grads(self, grads):
        for n, g in zip(self._grad_names, grads):
            buf = self.grad_dict[n]
            cur = buf._h.array
            # grads stay on their group ctx
            g = _to_device(g, next(iter(cur.devices())))
            if g.dtype != cur.dtype:
                g = g.astype(cur.dtype)
            # grad_req='add' accumulates on device — no host round trip
            buf._h.array = cur + g if self._grad_req[n] == "add" else g

    def _gather(self, vals):
        """Cross-device copy to the executor's device (ref: the
        _CrossDeviceCopy nodes PlaceDevice inserts, graph_executor.cc:406):
        group2ctx places arg STORAGE on per-group devices; the jitted
        program computes on the bind ctx, so inputs gather here.  No-op in
        the single-device common case."""
        dev = self._ctx.jax_device()
        return [_to_device(v, dev) for v in vals]

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                v.copyto(self.arg_dict[k])
            elif not allow_extra_params:
                raise MXNetError("invalid param %r" % k)
        if aux_params:
            for k, v in aux_params.items():
                if k in self.aux_dict:
                    v.copyto(self.aux_dict[k])
                elif not allow_extra_params:
                    raise MXNetError("invalid aux %r" % k)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to different input shapes.  The
        compiled program comes from the process-wide executor cache, so
        revisiting a previously-bound signature retraces nothing.

        Flag semantics follow the reference (python/mxnet/executor.py):
        an argument NOT named in kwargs whose inferred shape changes is
        an error unless ``partial_shaping=True`` (a silently-changed
        parameter shape means the new executor cannot share weights with
        this one), and any array growing beyond its bound size requires
        ``allow_up_sizing=True`` to authorize fresh allocation."""

        def _numel(s):
            n = 1
            for d in s:
                n *= int(d)
            return n

        def _check(name, old_shape, shape, specified, kind):
            if not partial_shaping and not specified:
                raise MXNetError(
                    "reshape changed the shape of unspecified %s %r "
                    "(%s -> %s); if intended, pass partial_shaping=True"
                    % (kind, name, old_shape, shape))
            if _numel(shape) > _numel(old_shape) and not allow_up_sizing:
                raise MXNetError(
                    "new shape of %s %r (%s) is larger than the bound "
                    "shape %s; pass allow_up_sizing=True to allow "
                    "allocating new arrays" % (kind, name, shape,
                                               old_shape))

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args, new_grads = {}, {}
        for name, shape in zip(self._prog.arg_names, arg_shapes):
            cur = self.arg_dict[name]
            shape = tuple(int(d) for d in shape)
            if tuple(cur.shape) == shape:
                new_args[name] = cur
                if name in self.grad_dict:
                    new_grads[name] = self.grad_dict[name]
            else:
                _check(name, tuple(cur.shape), shape, name in kwargs,
                       "argument")
                # reallocate on the OLD buffer's device so per-arg
                # group2ctx placement survives the reshape
                new_args[name] = nd_zeros(shape, cur.context, dtype=cur.dtype)
                if name in self.grad_dict and self.grad_dict[name] is not None:
                    new_grads[name] = nd_zeros(shape, cur.context,
                                               dtype=cur.dtype)
        new_aux = {}
        for name, shape in zip(self._prog.aux_names, aux_shapes):
            cur = self.aux_dict[name]
            shape = tuple(int(d) for d in shape)
            if tuple(cur.shape) == shape:
                new_aux[name] = cur
            else:
                _check(name, tuple(cur.shape), shape, False,
                       "auxiliary state")
                new_aux[name] = nd_zeros(shape, cur.context, dtype=cur.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads, new_aux,
                        self._grad_req)

    # -- binding classmethods -------------------------------------------------
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, shape_kwargs,
                     group2ctx=None):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        arg_shapes, _, aux_shapes = symbol.infer_shape(**shape_kwargs)
        type_dict = dict(type_dict or {})
        arg_types, _, aux_types = symbol.infer_type(**{
            k: v for k, v in type_dict.items()})
        # manual model parallelism (ref: ctx_group attr + PlaceDevice,
        # graph_executor.cc:406): arg STORAGE follows its group's device;
        # compute stays one XLA program (per-op placement is the
        # compiler's job here — real multi-device compute lives in
        # mxnet_tpu.parallel), so this preserves the observable contract
        # scripts rely on: each group's params live on its device.
        ctx_of = {}
        if group2ctx:
            for node in symbol._topo():
                grp = node.attrs.get("__ctx_group__") \
                    or node.attrs.get("ctx_group")
                if not grp or grp not in group2ctx:
                    continue
                if node.is_var:
                    ctx_of[node.name] = group2ctx[grp]
                else:
                    # an op's auto-created weights belong to its group
                    for src, _ in node.inputs:
                        if src.is_var:
                            ctx_of.setdefault(src.name, group2ctx[grp])
        arg_dict, grad_dict, aux_dict = {}, {}, {}
        if isinstance(grad_req, str):
            req_of = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            req_of = dict(zip(arg_names, grad_req))
        else:
            req_of = {n: grad_req.get(n, "null") for n in arg_names}
        # a gradient is stored by rebinding its handle, never written in
        # place, so until then the zeros of one shape are ONE buffer (a
        # half-billion-parameter model binds a gigabyte of zeros otherwise)
        zero_grads = {}
        for name, shape, dt in zip(arg_names, arg_shapes, arg_types):
            dt = np_dtype(type_dict.get(name, dt or np.float32))
            a_ctx = ctx_of.get(name, ctx)
            arg_dict[name] = nd_zeros(shape, a_ctx, dtype=dt)
            if req_of.get(name, "null") != "null":
                key = (tuple(shape), str(dt), a_ctx)
                if key not in zero_grads:
                    zero_grads[key] = nd_zeros(shape, a_ctx, dtype=dt)
                grad_dict[name] = NDArray(zero_grads[key]._h.array)
        for name, shape, dt in zip(aux_names, aux_shapes, aux_types):
            dt = np_dtype(type_dict.get(name, dt or np.float32))
            aux_dict[name] = nd_zeros(shape, ctx_of.get(name, ctx), dtype=dt)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, req_of)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad, grad_req, aux_states):
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        if args_grad is None:
            grad_dict = {}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = {n: g for n, g in zip(arg_names, args_grad)
                         if g is not None}
        else:
            grad_dict = dict(args_grad)
        if aux_states is None:
            aux_dict = {}
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states)
        return Executor(symbol, ctx, arg_dict, grad_dict, aux_dict, grad_req)

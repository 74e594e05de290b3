"""Fused training step: forward + backward + optimizer update in ONE XLA
computation.

This is the north-star dispatch model (SURVEY.md §7 stage 5 / BASELINE.json):
where the reference pushes every op of fwd/bwd through the engine and then
runs one fused optimizer kernel per parameter per batch
(graph_executor.cc RunOps + model.py _update_params), the whole training
step here is a single jitted program — one host->device dispatch per batch,
zero per-parameter Python overhead, and XLA fuses the optimizer update into
the backward pass epilogue.

Every optimizer that implements `fused_update` (all of them, mirroring the
reference's full fused-kernel set in src/operator/optimizer_op.cc) runs on
this path; exotic configurations (monitors, grad_req='add', non-collective
kvstores) fall back to the general path.

Mixed precision (ref: optimizer.py:446-476 multi_precision): when the bound
parameters are half-width (float16/bfloat16) and the optimizer has
multi_precision set, the step keeps float32 MASTER weights and optimizer
state internally and casts to the storage dtype for the forward.  The vjp
differentiates the STORAGE-dtype values, so activations and gradients stay
bfloat16 end-to-end — no materialized f32 gradient copies — and the single
f32 cast per parameter fuses into the master-weight update's elementwise
epilogue (value-identical to mp_sgd_*'s cast-at-the-boundary semantics,
generalized to every optimizer).  On TPU this is the native training mode:
bfloat16 compute feeds the MXU and halves HBM traffic while updates
accumulate in float32.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np

from .. import executor_cache as _exec_cache
from .. import program_cache as _program_cache
from .. import random as _random
from ..base import MXNetError
from ..ndarray import NDArray
from ..ndarray.ndarray import host_view
from ..observability import health as _health
from ..observability import instrument as _instrument
from ..observability import memprof as _memprof
from ..ops import pallas_kernels as _pallas_kernels
from ..optimizer import _is_low_precision


# create_state-shaped pytrees are None / array / tuple-of-those — exactly
# what jax.tree_util handles (None = empty node, NDArray = leaf)
def _map_state(fn, st):
    return jax.tree_util.tree_map(fn, st)


def _map2_state(fn, a, b):
    return jax.tree_util.tree_map(fn, a, b)


def _state_leaves(st):
    return jax.tree_util.tree_leaves(st)


def collective_counts(hlo_text):
    """Count collective ops in compiled-HLO text (async ``-start`` forms
    counted once): what ``FusedTrainStep.compiled_hlo`` of a dp step
    shows of the gradient all-reduce."""
    return {name: len(re.findall(r"%s(?:-start)?\(" % re.escape(name),
                                 hlo_text))
            for name in ("all-reduce", "all-gather", "reduce-scatter",
                         "collective-permute", "all-to-all")}


class FusedStepUnsupported(MXNetError):
    """A bound configuration ``FusedTrainStep.supports`` could not rule
    out statically but the constructor cannot serve; ``Module`` trains it
    on the general path.  Lowering and compile errors are never this."""


class FusedTrainStep:
    @staticmethod
    def supports(module):
        """Conservative gating; anything unusual uses the general path."""
        n = len(module._context)
        if module._exec_group is None or len(module._exec_group.execs) != n:
            return False
        if module._update_on_kvstore:
            return False
        if n == 1:
            if module._kvstore is not None:
                return False
        else:
            # multi-device DP: the fused step shards the batch over a dp
            # mesh and XLA inserts the gradient all-reduce, replacing the
            # kvstore's collective — only collective-style stores may be
            # silently subsumed this way.  kvstore=None is rejected: the
            # general path performs no aggregation there, and the fused
            # step must not silently train different math (advisor
            # finding, round 2).
            kv = module._kvstore
            if kv is None or not any(t in kv.type for t in ("tpu", "ici")):
                return False
            devs = [c.jax_device() for c in module._context]
            if len(set(devs)) != n:
                return False
            # equal batch slices so the dp shards line up with the execs
            sizes = {s.stop - s.start for s in module._exec_group.slices}
            if len(sizes) != 1:
                return False
        opt = module._optimizer
        if opt is None or not opt._fused_ok():
            return False
        for exe in module._exec_group.execs:
            if exe._monitor_callback is not None:
                return False
            if any(req == "add" for req in exe._grad_req.values()):
                return False
        if getattr(module, "inputs_need_grad", False):
            return False
        return True

    def __init__(self, module, _carry_states=None, _carry_masters=None):
        self.module = module
        self.exe = module._exec_group.execs[0]
        self.opt = module._optimizer
        self.ran = False
        self._schedule = None
        # input name -> (the batch's array, its upload): see ``stage``
        self._staged = {}
        exe = self.exe
        prog = exe._prog
        self.prog = prog
        self.n_dev = len(module._context)
        self.devices = [c.jax_device() for c in module._context]
        self.param_names = list(exe._grad_names)
        self.other_names = [n for n in prog.arg_names
                            if n not in set(self.param_names)]
        self.data_names = [d.name for d in module._data_shapes]
        self.label_names = [l.name for l in module._label_shapes] \
            if module._label_shapes else []
        idx_of = {n: i for i, n in
                  enumerate(module._exec_group.param_names)}
        # the optimizer's index of a parameter: Module keys idx2name (and
        # so lr_mult / wd_mult) by ``position * n_dev + device``; the
        # step is device 0's
        self.param_idx = [idx_of.get(n, i) * self.n_dev
                          for i, n in enumerate(self.param_names)]

        # storage dtype per param, and the master dtype the update runs in
        self.param_dtypes = [exe.arg_dict[n]._h.array.dtype
                             for n in self.param_names]
        mp = bool(getattr(self.opt, "multi_precision", False))
        self.low = [_is_low_precision(dt) for dt in self.param_dtypes]
        self.master_dtypes = [np.dtype(np.float32) if (mp and lo)
                              else dt
                              for dt, lo in zip(self.param_dtypes, self.low)]
        self.mixed = [np.dtype(m) != np.dtype(p) for m, p in
                      zip(self.master_dtypes, self.param_dtypes)]

        if self.n_dev > 1:
            from jax.sharding import (Mesh, NamedSharding,
                                      PartitionSpec as P)
            self._mesh = Mesh(np.array(self.devices), ("dp",))
            self._sh_repl = NamedSharding(self._mesh, P())
            self._sh_dp = NamedSharding(self._mesh, P("dp"))
            # batch bookkeeping for the sharding specs
            self._full_batch = int(module._data_shapes[0].shape[0])
            self._full_shape = {d.name: tuple(d.shape)
                                for d in module._data_shapes}
            if module._label_shapes:
                self._full_shape.update((l.name, tuple(l.shape))
                                        for l in module._label_shapes)
            batch_names = set(self.data_names) | set(self.label_names)
            self._other_is_batch = [n in batch_names
                                    for n in self.other_names]
        else:
            self._mesh = None
            self._sh_repl = None

        def _to_global(arr):
            # never the default backend: the bound device (or dp mesh)
            return jax.device_put(arr, self._sh_repl if self.n_dev > 1
                                  else self.devices[0])

        self._to_global = _to_global

        # canonical master weights + optimizer state live in the step;
        # per-exec arg_dicts receive storage-dtype values after every run.
        # On a reshape rebuild the carried masters are authoritative —
        # re-deriving them from half-width exec storage would truncate the
        # sub-ulp precision they exist to preserve.
        if _carry_masters is not None:
            self._masters = [
                _to_global(np.asarray(m).astype(self.master_dtypes[j]))
                for j, m in enumerate(_carry_masters)]
        else:
            self._masters = [
                _to_global(np.asarray(exe.arg_dict[n]._h.array)
                           .astype(self.master_dtypes[j]))
                for j, n in enumerate(self.param_names)]
        self._gaux = [
            _to_global(np.asarray(exe.aux_dict[n]._h.array))
            for n in prog.aux_names]
        if _carry_states is not None:
            self.states = [
                _map_state(_to_global, st) for st in _carry_states]
        else:
            self.states = [self._init_state(j)
                           for j in range(len(self.param_names))]

        # per-param extras width (bias-correction coefficients etc.) —
        # declared, not probed: fused_scalars needs _update_count to have
        # run and may be stateful (Nadam's m_schedule)
        self._n_extra = int(getattr(self.opt, "fused_n_scalars", 0))
        self._needs_rng = bool(getattr(self.opt, "fused_needs_rng", False))

        # health sentinel (MXNET_TPU_HEALTH=1): the step program appends
        # the packed numerics vector — here the update/param ratio is
        # EXACT, since the program holds both the old and new masters.
        # Resolved at construction; the step function is rebuilt (and so
        # retraced once) whenever the mode changes.
        self._health_on = _health.enabled()
        self.health_layout = _health.HealthLayout(
            len(prog.entries), self.param_names,
            tap_names=_health.attention_tap_names(prog.order)) \
            if self._health_on else None
        self.last_health = None

        # memprof label: the fused step is THE training program — its
        # memory_analysis row is the one an OOM post-mortem reads first
        memprof_label = "fused@%s" % exe._symbol.structural_hash()[:10]
        self._memprof_label = memprof_label

        prog_ref = prog
        param_names = self.param_names
        other_names = self.other_names
        aux_names = prog.aux_names
        opt = self.opt
        param_dtypes = self.param_dtypes
        mixed = self.mixed
        n_params = len(param_names)
        n_extra = self._n_extra
        needs_rng = self._needs_rng
        health_on = self._health_on
        health_layout = self.health_layout
        mesh_ref = self._mesh

        # Buffer donation lets XLA update masters and optimizer state in
        # place.  On by default where jax implements it (a TPU step):
        # through Module.fit on a v5e, ResNet-50 bf16 batch 32 stepped in
        # 58 ms donated vs 72 ms not, at 509 vs 713 MB peak device memory
        # (one 40-step run each way, PR 21 — an observation, not a
        # benchmark).  MXNET_TPU_FUSED_DONATE=0/1 overrides.
        donate = os.environ.get(
            "MXNET_TPU_FUSED_DONATE",
            "1" if self.devices[0].platform == "tpu" else "0") == "1"

        # On the dp path the constructor's jax.eval_shape probe below
        # IS the step's one real trace — jax's jaxpr cache serves the
        # later jit lowering from it, so the body never re-runs at
        # dispatch.  The probe therefore COUNTS as the retrace, but
        # must not arm a memprof build record: no compile follows the
        # probe directly (the real one attributes via aot_compile, or
        # never happens on a disk-restored warm boot), and a dangling
        # armed record swallows the next unrelated compile on the
        # thread — breaking the elastic warm-resume proof that
        # build_totals deltas are zero on a fully restored worker.
        shape_probe = {"on": False}

        def _step(*args):
            # the ops resolve their kernel flags against what THIS step
            # is traced for: its devices' platform, and whether XLA
            # partitions the graph by itself (the dp path)
            with _pallas_kernels.trace_scope(
                    platform=self.devices[0].platform,
                    partitioned=mesh_ref is not None):
                return _step_body(*args)

        def _step_body(masters, other_vals, states, aux_vals, keys, lrs,
                       wds, extras, opt_key, stored=None):
            # body runs only when jax (re)traces: counts real recompiles
            # of the fused step alongside the executor-cache counters
            _exec_cache.note_trace("fused_step", memprof_label,
                                   build_record=not shape_probe["on"])
            arg_map = dict(zip(other_names, other_vals))
            aux_map = dict(zip(aux_names, aux_vals))

            # Cast elimination (roofline kernel sprint): differentiate the
            # STORAGE-dtype parameter values, not the f32 masters.  The
            # old form (vjp through the master->bf16 cast) made the vjp
            # boundary materialize a full f32 copy of every gradient —
            # pure HBM traffic (a convert_reduce_fusion.* family in the
            # device trace).  Here activations AND gradients stay
            # bf16 end-to-end; the one f32 cast per parameter happens at
            # the master-weight update below, where XLA fuses the convert
            # into the update's elementwise epilogue.  The update math is
            # value-identical: cast-then-update(f32) == the old
            # update(cast_vjp(g)) — the master path remains f32.
            if stored is None:
                pvals = [m.astype(param_dtypes[j]) if mixed[j] else m
                         for j, m in enumerate(masters)]
            else:
                # the executor's storage-dtype copies of the mixed
                # parameters ARE the masters cast: the last step wrote
                # them (and _refresh re-derives a master wherever someone
                # else replaced a copy).  Read, not recast, and donated,
                # they become this step's copies in place: one copy a
                # parameter instead of three at the peak
                stored = iter(stored)
                pvals = [next(stored) if mixed[j] else m
                         for j, m in enumerate(masters)]

            def f(pv):
                amap = dict(arg_map)
                amap.update(zip(param_names, pv))
                outs, new_aux = prog_ref.evaluate(amap, aux_map, keys, True)
                return outs, [new_aux[n] for n in aux_names]

            if health_on:
                # attention-logit taps ride out of the vjp as has_aux
                # values (frame tracers must not leak out of the
                # linearization trace); topo order matches the layout's
                # tap slots
                def f_tapped(pv):
                    with _health.collect_taps() as frame:
                        result = f(pv)
                    return result, list(frame)

                (outs, new_aux), vjp_fn, taps = jax.vjp(
                    f_tapped, pvals, has_aux=True)
            else:
                taps = None
                (outs, new_aux), vjp_fn = jax.vjp(f, pvals)
            heads = [jnp.ones_like(o) for o in outs]
            zeros_aux = [jnp.zeros_like(a) for a in new_aux]
            (grads,) = vjp_fn((heads, zeros_aux))

            opt_keys = jax.random.split(opt_key, n_params) if needs_rng \
                else [None] * n_params
            new_masters, new_states, new_exec = [], [], []
            with jax.named_scope("mx:update"):
                for j, (w, g) in enumerate(zip(masters, grads)):
                    if mixed[j]:
                        # the ONLY master-precision cast on the gradient
                        # path
                        g = g.astype(w.dtype)
                    ex = extras[j] if n_extra else ()
                    nw, nst = opt.fused_update(w, g, states[j], lrs[j],
                                               wds[j], ex, key=opt_keys[j])
                    nw = nw.astype(w.dtype)
                    nst = _map2_state(lambda a, old: a.astype(old.dtype),
                                      nst, states[j])
                    new_masters.append(nw)
                    new_states.append(nst)
                    new_exec.append(nw.astype(param_dtypes[j]) if mixed[j]
                                    else nw)
            if health_on:
                # exact update/param ratio: the program holds old AND
                # new masters, so |Δw|/|w| needs no host-side estimate
                with jax.named_scope("mx:health"):
                    upd_sq = sum(jnp.sum(jnp.square(
                        nw.astype(jnp.float32) - w.astype(jnp.float32)))
                        for w, nw in zip(masters, new_masters))
                    par_sq = sum(jnp.sum(jnp.square(w.astype(jnp.float32)))
                                 for w in masters)
                    ratio = jnp.sqrt(upd_sq) / jnp.maximum(
                        jnp.sqrt(par_sq), jnp.float32(1e-12))
                    hvec = _health.pack_summary(health_layout, outs, masters,
                                                list(grads),
                                                update_ratio=ratio,
                                                taps=taps)
                return (outs, new_masters, new_states, new_aux, new_exec,
                        hvec)
            return outs, new_masters, new_states, new_aux, new_exec

        # donation: masters (0) and optimizer states (2)
        donate_idx = (0, 2) if donate else ()
        self._last_abstract = None
        self._hlo_text = self._op_scopes = None
        # single device: the executor's storage-dtype copies ride along
        # (argument 9, ``stored``) and are donated with the rest
        self._spare_idx = [j for j in range(n_params) if mixed[j]] \
            if donate and self.n_dev == 1 else []

        # persistent disk tier (program_cache.py): the step has no
        # executor-cache signature, so its key material is assembled
        # here — everything the trace bakes in beyond the argument
        # shapes the per-call fingerprint already covers: the graph,
        # name/dtype layout, donation, the optimizer's traced constants,
        # and the same health/kernel flags that key entry programs.
        def _disk_key():
            if not _program_cache.enabled():
                return None
            opt_fp, unkeyable = _program_cache.optimizer_fingerprint(opt)
            if unkeyable:
                # an optimizer attribute the trace could bake in but the
                # fingerprint cannot represent: caching would risk
                # restoring an executable with the WRONG constants —
                # decline (this step compiles; everything else persists)
                module.logger.warning(
                    "persistent program cache: fused step not persisted "
                    "— optimizer %s attribute(s) %s cannot key the disk "
                    "entry faithfully", type(opt).__name__,
                    list(unkeyable))
                return None
            return (
                "fused_step", exe._symbol.structural_hash(),
                tuple(param_names), tuple(other_names), tuple(aux_names),
                tuple(str(np.dtype(d)) for d in self.param_dtypes),
                tuple(str(np.dtype(d)) for d in self.master_dtypes),
                tuple(bool(m) for m in mixed),
                bool(donate), bool(health_on), int(n_extra),
                bool(needs_rng), int(self.n_dev),
                tuple(str(d) for d in self.devices),
                opt_fp,
                _pallas_kernels.kernel_signature(self.devices[0].platform),
                tuple(self._other_is_batch) if self.n_dev > 1 else ())

        def _wrap_step(jitted):
            if not _program_cache.enabled():
                # tier off: today's dispatchable, no indirection
                return _memprof.wrap_jit(jitted, "fused_step",
                                         memprof_label)
            # disk tier on: the wrapper is built LAZILY, at first
            # dispatch — jit bakes the optimizer's constants at
            # first-trace time, so a hyperparameter mutated between
            # init_optimizer and the first step must be fingerprinted
            # as the value the trace will actually read; a
            # construction-time key could save the executable under a
            # stale identity and a later process would restore wrong
            # constants
            box = []

            def _dispatch(*args):
                if not box:
                    box.append(_program_cache.wrap_program(
                        jitted, "fused_step", memprof_label,
                        key_material=_disk_key(),
                        platform=self.devices[0].platform))
                return box[0](*args)

            return _dispatch

        if self.n_dev == 1:
            self._step_jit = jax.jit(
                _step, donate_argnums=donate_idx
                + ((9,) if self._spare_idx else ()))
            self._step = _wrap_step(self._step_jit)
            # identity of the arrays we last wrote into exec's dicts; a
            # mismatch means set_params/init_params replaced them and the
            # master state must refresh from the exec value
            self._scattered = {}
            return

        # -- multi-device DP: derive shardings, validate at full shapes --
        repl, dp = self._sh_repl, self._sh_dp
        full_batch = self._full_batch
        full_shape = self._full_shape
        sds = jax.ShapeDtypeStruct
        others = [sds(full_shape.get(n, exe.arg_dict[n].shape),
                      exe.arg_dict[n]._h.array.dtype)
                  for n in self.other_names]
        mvals = [sds(m.shape, m.dtype) for m in self._masters]
        svals = [_map_state(lambda a: sds(a.shape, a.dtype), st)
                 for st in self.states]
        avals = [sds(a.shape, a.dtype) for a in self._gaux]
        keys = tuple(_random.next_key() for _ in range(exe._n_keys))
        f32v = sds((n_params,), np.float32)
        exv = sds((n_params, max(n_extra, 1)), np.float32)
        kv = sds((2,), np.uint32)
        shape_probe["on"] = True
        try:
            outs_sd = jax.eval_shape(
                _step, mvals, others, svals, avals, keys, f32v, f32v, exv,
                kv)[0]
        except (TypeError, ValueError, MXNetError) as exc:
            # a graph that bakes the PER-DEVICE batch into a shape attr
            # (Reshape(shape=(local_batch, ...))) cannot trace at the
            # global batch but traces fine at the shapes it was bound
            # with — the one configuration this constructor declines.
            # Anything that fails at the bound shapes too is a real
            # error and propagates.
            local = [sds(exe.arg_dict[n].shape,
                         exe.arg_dict[n]._h.array.dtype)
                     for n in self.other_names]
            try:
                jax.eval_shape(_step, mvals, local, svals, avals, keys,
                               f32v, f32v, exv, kv)
            except Exception:
                raise exc from None
            raise FusedStepUnsupported(
                "the program bakes per-device batch shapes (%s)"
                % (exc,)) from exc
        finally:
            shape_probe["on"] = False
        # XLA derives the gradient all-reduce from these shardings — the
        # kvstore collective collapsed into the step program
        state_sh = [_map_state(lambda a: repl, st) for st in self.states]
        out_sh = (
            [dp if (len(o.shape) >= 1 and o.shape[0] == full_batch)
             else repl for o in outs_sd],
            [repl] * n_params,
            state_sh,
            [repl] * len(aux_names),
            [repl] * n_params)
        if health_on:
            # the packed health vector is a global reduction: replicated
            out_sh = out_sh + (repl,)
        self._step_jit = jax.jit(
            _step,
            in_shardings=(
                [repl] * n_params,
                [dp if b else repl for b in self._other_is_batch],
                state_sh,
                [repl] * len(aux_names),
                (repl,) * exe._n_keys,
                repl, repl, repl, repl),
            out_shardings=out_sh,
            donate_argnums=donate_idx)
        self._step = _wrap_step(self._step_jit)
        self._scattered = {}

    def compiled_hlo(self):
        """Compiled-HLO text of the step program (None before the first
        run), lowered and compiled for it once and kept; its readers are
        ``collective_counts`` (the all-reduce XLA derived from the dp
        shardings) and ``op_scopes``."""
        if self._last_abstract is None:
            return None
        if self._hlo_text is None:
            # JAX keys its persistent compile cache with the metadata
            # stripped, so a hit may hand back an executable another build
            # traced, with THAT build's scopes and lines in its text (the
            # instructions and their names are the same either way).  For
            # this one compile the metadata is part of the key: the text
            # read here is this trace's own
            key = "jax_compilation_cache_include_metadata_in_key"
            before = getattr(jax.config, key)
            jax.config.update(key, True)
            try:
                self._hlo_text = self._step_jit.lower(
                    *self._last_abstract).compile().as_text()
            finally:
                jax.config.update(key, before)
        return self._hlo_text

    def op_scopes(self):
        """{instruction name: {"path", "mechanism", "detail", "pass"}} for
        every instruction of every computation of the compiled step (None
        before the first run): which ``mx:`` scope each compiled op was
        traced under, and in which pass (``instrument.scopes_of_hlo``).
        A device trace names its events by these instructions, so this
        table joined with the trace's seconds is device time by mechanism
        (``instrument.device_seconds_by_scope``)."""
        if self._op_scopes is None and self.compiled_hlo() is not None:
            self._op_scopes = _instrument.scopes_of_hlo(self._hlo_text)
        return self._op_scopes

    def _init_state(self, j):
        """create_state-shaped optimizer state in the master dtype, with
        jnp leaves (replicated across the dp mesh when present)."""
        name = self.param_names[j]
        exe = self.exe
        master_local = jax.device_put(
            np.asarray(exe.arg_dict[name]._h.array)
            .astype(self.master_dtypes[j]), self.devices[0])
        st_nd = self.opt.create_state(self.param_idx[j],
                                      NDArray(master_local))
        return _map_state(
            lambda a: self._to_global(a._h.array
                                      if isinstance(a, NDArray) else a),
            st_nd)

    def run(self, data_batch):
        """One fused step.  The host work is named in phases of the fit
        loop's tracker (``observability.instrument``): ``fused:refresh``,
        ``fused:load``, ``fused:scalars``, ``fused:dispatch`` (the
        tracker's in-flight mark), ``fused:scatter``."""
        with _instrument.phase("fused:refresh"):
            self._refresh()
        if self.n_dev > 1:
            self._run_dp(data_batch)
            return
        exe = self.exe

        # the batch into the bound input buffers: staged a step ahead by
        # ``stage``, or uploaded (and cast) now
        with _instrument.phase("fused:load"):
            inputs = self._inputs(data_batch)
            for name, arr in inputs.items():
                exe.arg_dict[name]._h.array = arr
            loaded = list(inputs.values())

        with _instrument.phase("fused:scalars"):
            lrs, wds, extras, opt_key = self._per_step_scalars()
            other_vals = [exe.arg_dict[n]._h.array
                          for n in self.other_names]
            aux_vals = list(self._gaux)
            keys = tuple(_random.next_key() for _ in range(exe._n_keys))
            args = (self._masters, other_vals, self.states, aux_vals, keys,
                    lrs, wds, extras, opt_key)
            if self._spare_idx:
                args += ([exe.arg_dict[self.param_names[j]]._h.array
                          for j in self._spare_idx],)
            self._note_abstract(args)
        res = self._dispatch(args, loaded, "fused_step")

        with _instrument.phase("fused:scatter"):
            outs, new_exec, new_aux = self._keep(res)
            for n, v in zip(self.param_names, new_exec):
                exe.arg_dict[n]._h.array = v
                self._scattered[n] = v
            for n, v in zip(self.prog.aux_names, new_aux):
                exe.aux_dict[n]._h.array = v
                self._scattered[n] = v
            exe.outputs = [NDArray(o) for o in outs]

    # -- the batch's way to the step's devices: one copy, the transfer ------

    def _batch_inputs(self, data_batch):
        """(name, array) of every input the step reads from a batch."""
        pairs = list(zip(self.data_names, data_batch.data))
        if self.label_names and data_batch.label:
            pairs += zip(self.label_names, data_batch.label)
        return [(n, a._h.array) for n, a in pairs if n in self.exe.arg_dict]

    def _place(self, name, src):
        """A batch's array as the step reads input ``name``: in the bound
        dtype, on the bound buffer's device or split over the ``dp``
        mesh — ``src`` itself where it is that already.  What lives in
        host memory goes up from its numpy view: jax cuts a view, not a
        copy, for each device, and the transfers are all that moves it
        (an array on another accelerator is resharded device to
        device)."""
        bound = self.exe.arg_dict[name]._h.array
        if src.dtype != bound.dtype:
            src = src.astype(bound.dtype)
        if self.n_dev > 1:
            target = self._sh_dp
            if src.sharding.is_equivalent_to(target, src.ndim):
                return src
        else:
            target, = bound.devices()
            if src.devices() == {target}:
                return src
        host = host_view(src)
        return jax.device_put(src if host is None else host, target)

    def stage(self, data_batch):
        """Start the upload of the batch the next ``run`` will be handed
        (``Module.prepare``, called by the fit loop once the step in
        flight is dispatched, so the transfer runs under it): phase
        ``fused:stage``.  One batch is held ahead, each upload beside
        the array it was made from; ``run`` takes it when it is handed
        that very array and drops it otherwise.  Nothing is staged of a
        batch that is where the step reads it, or whose shapes are not
        the bound ones (``forward_backward`` rebinds or retires the
        step for such a batch)."""
        with _instrument.phase("fused:stage"):
            staged = {}
            for name, src in self._batch_inputs(data_batch):
                want = self._full_shape[name] if self.n_dev > 1 \
                    else self.exe.arg_dict[name].shape
                if tuple(src.shape) != tuple(want):
                    staged = {}
                    break
                placed = self._place(name, src)
                if placed is not src:
                    staged[name] = (src, placed)
            self._staged = staged

    def _inputs(self, data_batch):
        """name -> the batch's arrays as the step reads them: what
        ``stage`` uploaded a step ahead where this is that batch, placed
        now otherwise.  The stage is given up either way.  Counts the
        step under ``module.input.staged`` or ``module.input.loaded``."""
        staged, self._staged = self._staged, {}
        inputs, ahead, late = {}, 0, 0
        for name, src in self._batch_inputs(data_batch):
            hit = staged.get(name)
            if hit is not None and hit[0] is src:
                inputs[name] = hit[1]
                ahead += 1
            else:
                inputs[name] = self._place(name, src)
                late += inputs[name] is not src
        _instrument.note_step_input(staged=ahead > 0 and not late)
        return inputs

    def _refresh(self):
        """Rebind after a reshape, and re-derive master state where
        ``set_params``/``init_params`` replaced the exec handles since
        the last write-back (the staleness scans)."""
        module = self.module
        if module._exec_group.execs[0] is not self.exe:
            # a reshape rebuilt the executors: rebind to the live one,
            # carrying optimizer state AND f32 masters over by position
            # (same symbol, so the param list is unchanged)
            states = self.states
            masters = [np.asarray(m) for m in self._masters]
            self.exe = module._exec_group.execs[0]
            self.__init__(module,
                          _carry_states=[_map_state(np.asarray, st)
                                         for st in states],
                          _carry_masters=masters)
            # the carried masters are authoritative: stop the staleness
            # check below from re-deriving them off half-width storage
            for n in self.param_names:
                self._scattered[n] = \
                    module._exec_group.execs[0].arg_dict[n]._h.array
        self.ran = True
        exe = self.exe
        for j, n in enumerate(self.param_names):
            cur = exe.arg_dict[n]._h.array
            if self._scattered.get(n) is not cur:
                self._masters[j] = self._to_global(
                    np.asarray(cur).astype(self.master_dtypes[j]))
        for j, n in enumerate(self.prog.aux_names):
            cur = exe.aux_dict[n]._h.array
            if self._scattered.get(n) is not cur:
                self._gaux[j] = self._to_global(np.asarray(cur))

    def _dispatch(self, args, uploads, oom_context):
        """The step program's call, to its return: from here one more
        step is in flight, finished when its first output reads ready
        and not started before ``uploads`` have landed."""
        with _instrument.phase("fused:dispatch", dispatches=True) as ph:
            try:
                res = self._step(*args)
            except Exception as exc:
                # OOM black box: RESOURCE_EXHAUSTED on the training step
                # leaves the augmented flight dump behind before it kills
                # the run (observability/memprof.py; no-op otherwise)
                _memprof.maybe_record_oom(oom_context, exc)
                raise
            ph.watch(res[0][:1] or res[1][:1], uploads)
        _instrument.note_recompute_blocks(self.prog.mirror_stages)
        pairs, chunk_steps = self._schedule_counts()
        _instrument.note_attention_pairs(*pairs)
        _instrument.note_gdn_chunk_steps(*chunk_steps)
        return res

    def _schedule_counts(self):
        """What the step program's kernels are scheduled to do, static per
        program: its attention as (computed, visible) pairs and its delta-rule
        scans as (chunk steps, those in the Pallas scan kernels, those whose
        chunk-local part is in the Pallas local kernels), worked out once
        from the shapes bound to device 0's executor (its share of the batch,
        so times the devices)."""
        if self._schedule is None:
            args = self.exe.arg_dict
            bound = ({n: a.shape for n, a in args.items()},
                     {n: a._h.array.dtype for n, a in args.items()})
            with _pallas_kernels.trace_scope(
                    platform=self.devices[0].platform,
                    partitioned=self._mesh is not None):
                counts = (self.prog.attention_pairs(*bound),
                          self.prog.gdn_chunk_steps(*bound))
            self._schedule = tuple(tuple(self.n_dev * x for x in c)
                                   for c in counts)
        return self._schedule

    def _keep(self, res):
        """Take the step's results as the next step's state; returns
        what is left to hand to the executors: (outputs, the parameters
        in their storage dtype, the auxiliary states)."""
        outs, new_masters, new_states, new_aux, new_exec = res[:5]
        self.last_health = res[5] if self._health_on else None
        self._masters = list(new_masters)
        self.states = list(new_states)
        self._gaux = list(new_aux)
        return outs, new_exec, new_aux

    def _per_step_scalars(self):
        opt = self.opt
        lrs, wds, extras = [], [], []
        for j, name in enumerate(self.param_names):
            i = self.param_idx[j]
            opt._update_count(i)
            lrs.append(opt._get_lr(i) * 1.0)
            wds.append(opt._get_wd(i) * 1.0)
            extras.append(opt.fused_scalars(i))
        n = len(self.param_names)
        ex = np.asarray(extras, np.float32) if self._n_extra \
            else np.zeros((n, 1), np.float32)
        opt_key = _random.next_key() if self._needs_rng \
            else jnp.zeros((2,), jnp.uint32)
        put = lambda a: jax.device_put(
            a, self._sh_repl if self.n_dev > 1 else self.devices[0])
        return (put(np.asarray(lrs, np.float32)),
                put(np.asarray(wds, np.float32)), put(ex), put(opt_key))

    def _note_abstract(self, args):
        """Stash the step's abstract signature once (first dispatch) so
        ``compiled_hlo`` can re-lower without holding real buffers."""
        if self._last_abstract is not None:
            return
        self._last_abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)

    @staticmethod
    def _replica_shard(garr, dev):
        """The addressable replica of a replicated/dp-sharded global array
        on `dev` (falls back to a copy if the device holds no shard)."""
        for s in garr.addressable_shards:
            if s.device == dev:
                return s.data
        return jax.device_put(np.asarray(garr), dev)

    def _run_dp(self, data_batch):
        """Multi-device data-parallel step: ONE jitted program over the dp
        mesh — batch sharded, params replicated, gradient all-reduce
        inserted by XLA from the shardings (replaces per-device executors
        + kvstore collective + per-device updater loop)."""
        exe = self.exe
        with _instrument.phase("fused:load"):
            inputs = self._inputs(data_batch)
            # a non-batch graph input (fixed param, state) is the bound
            # value, replicated
            other_vals = [
                inputs[n] if b and n in inputs else jax.device_put(
                    np.asarray(exe.arg_dict[n]._h.array), self._sh_repl)
                for n, b in zip(self.other_names, self._other_is_batch)]
        with _instrument.phase("fused:scalars"):
            lrs, wds, extras, opt_key = self._per_step_scalars()
            keys = tuple(_random.next_key() for _ in range(exe._n_keys))
            args = (self._masters, other_vals, self.states, self._gaux,
                    keys, lrs, wds, extras, opt_key)
            self._note_abstract(args)
        res = self._dispatch(args, list(inputs.values()), "fused_step_dp")

        with _instrument.phase("fused:scatter"):
            outs, new_exec, new_aux = self._keep(res)
            # hand every exec its local replica shard so eval/save/
            # get_params see the updated state with zero cross-device
            # traffic
            for k, exe_k in enumerate(self.module._exec_group.execs):
                dev = self.devices[k]
                for n, v in zip(self.param_names, new_exec):
                    shard = self._replica_shard(v, dev)
                    exe_k.arg_dict[n]._h.array = shard
                    if k == 0:
                        self._scattered[n] = shard
                for n, v in zip(self.prog.aux_names, new_aux):
                    shard = self._replica_shard(v, dev)
                    exe_k.aux_dict[n]._h.array = shard
                    if k == 0:
                        self._scattered[n] = shard
                # batch-carrying outs are dp-sharded: each exec's shard IS
                # its batch slice; batchless outs arrive as full replicas
                exe_k.outputs = [NDArray(self._replica_shard(o, dev))
                                 for o in outs]

    def _wrap_nd(self, arr, dev):
        return NDArray(self._replica_shard(arr, dev) if self.n_dev > 1
                       else arr)

    def sync_masters(self, arg_params, aux_params):
        """Copy the step's authoritative state into the host master
        dicts BITWISE (in each param's storage dtype — under
        multi_precision the bf16 value the forward consumes, exactly
        what the exec dicts hold).  Replaces the exec group's
        cross-device replica average for checkpointing: averaging N
        bitwise-identical replicas rounds, and a checkpoint an ulp off
        the live state breaks bitwise resume."""
        exe = self.exe
        covered = set()
        for j, name in enumerate(self.param_names):
            if name in arg_params:
                arg_params[name]._h.array = jax.device_put(
                    np.asarray(self._masters[j])
                    .astype(self.param_dtypes[j]),
                    arg_params[name].context.jax_device())
                covered.add(name)
        for name, nd in arg_params.items():
            # fixed (gradient-free) params are not step state: their
            # bound exec value is already authoritative
            if name not in covered and name in exe.arg_dict:
                nd._h.array = jax.device_put(
                    np.asarray(exe.arg_dict[name]._h.array)
                    .astype(np.dtype(nd.dtype)),
                    nd.context.jax_device())
        for j, name in enumerate(self.prog.aux_names):
            if name in aux_params:
                aux_params[name]._h.array = jax.device_put(
                    np.asarray(self._gaux[j])
                    .astype(np.dtype(aux_params[name].dtype)),
                    aux_params[name].context.jax_device())

    def transfer_to_updater(self, updater):
        """Seed a local Updater's per-index state from the fused buffers so
        retiring the fused path mid-training keeps optimizer state (and the
        f32 masters, under multi_precision)."""
        if updater is None:
            return
        for j, name in enumerate(self.param_names):
            idx = self.param_idx[j]
            for k, dev in enumerate(self.devices):
                slot = idx + k
                st_nd = _map_state(lambda a: self._wrap_nd(a, dev),
                                   self.states[j])
                if self.mixed[j]:
                    st_nd = self.opt.fused_wrap_mp_state(
                        st_nd, self._wrap_nd(self._masters[j], dev))
                updater.states[slot] = st_nd
                updater.states_synced[slot] = True

    # -- optimizer-state checkpoint interop ---------------------------------
    def export_states(self):
        out = {}
        for j, name in enumerate(self.param_names):
            entry = {"state": _map_state(np.asarray, self.states[j])}
            if self.mixed[j]:
                entry["master"] = np.asarray(self._masters[j])
            out[name] = entry
        return out

    def load_states(self, states):
        for n, v in states.items():
            if n not in self.param_names:
                # e.g. the __comm_residuals__ entry of an older build's file
                continue
            j = self.param_names.index(n)
            if isinstance(v, dict):  # fused_v2
                st = v["state"]
                if self.mixed[j] and v.get("master") is not None:
                    self._masters[j] = self._to_global(
                        np.asarray(v["master"])
                        .astype(self.master_dtypes[j]))
                    # pin: the restored f32 master is authoritative — the
                    # next run()'s staleness check must not re-derive it
                    # from the half-width exec value, and the step reads
                    # the exec value: make it the master's cast
                    handle = self.module._exec_group.execs[0].arg_dict[n]._h
                    if self._spare_idx:
                        handle.array = self._masters[j].astype(
                            self.param_dtypes[j])
                    self._scattered[n] = handle.array
            else:  # fused_v1: bare SGD momentum array
                st = v
            cur_leaves = _state_leaves(self.states[j])
            new_leaves = _state_leaves(st)
            if len(cur_leaves) != len(new_leaves) or any(
                    tuple(a.shape) != tuple(b.shape)
                    for a, b in zip(cur_leaves, new_leaves)):
                continue
            it = iter(new_leaves)
            self.states[j] = _map_state(
                lambda old: self._to_global(
                    np.asarray(next(it)).astype(old.dtype)),
                self.states[j])

"""Distributed kvstore: multi-host over DCN (replaces ps-lite).

Reference architecture (SURVEY.md §2.5, §3.4): ZeroMQ parameter server,
workers ZPush/ZPull to servers keyed by DMLC_* env vars; sync mode
aggregates all workers before applying the optimizer.  TPU-native: there
are no server processes — `jax.distributed` connects the hosts, reduction
runs as collectives across all hosts' devices (ICI intra-slice, DCN
across slices), and "update_on_kvstore" semantics (optimizer applied to the
reduced gradient once, result broadcast) hold because every host computes
the identical update from the identical reduced gradient.

dist_sync == dist_device_sync here (no CPU staging hop to remove);
dist_async is documented sync-equivalent (SURVEY.md §7 hard-part 5) —
on ICI the straggler problem async mode solved does not exist.

Backend discovery: on a real pod the default backend spans all processes;
in the localhost test topology (§4.6's "multi-process on one host"
pattern, `tools/launch.py --launcher local`) the workers run on the CPU
backend, which carries the cross-process view — `_dist_devices` picks
whichever platform actually sees more than one process.

Env compatibility: honors DMLC_NUM_WORKER/DMLC_WORKER_ID when
jax.distributed is not initialized (e.g. under the reference's launcher),
so `tools/launch.py`-style scripts still see rank/size.
"""
from __future__ import annotations

import os
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..ndarray import NDArray
from ..observability.instrument import record_comm_exposed
from . import KVStore, _key_value
from .gradient_compression import GradientCompression

_rendezvoused = False
_barrier_seq = 0  # process-global so barrier names are never reused

# LRU bound for the per-store jitted-collective cache (same discipline
# as the executor program cache: move-to-end on hit, evict oldest past
# the cap).  Each entry is one jitted psum/all-gather program family per
# device topology; topologies are few, but a long-lived process cycling
# exotic device subsets must not grow without bound.
_PSUM_CACHE_SIZE_ENV = "MXNET_TPU_PSUM_CACHE_SIZE"
_DEFAULT_PSUM_CACHE_SIZE = 64


def _psum_cache_size():
    try:
        return max(1, int(os.environ.get(_PSUM_CACHE_SIZE_ENV,
                                         _DEFAULT_PSUM_CACHE_SIZE)))
    except ValueError:
        return _DEFAULT_PSUM_CACHE_SIZE


def _global_state():
    from jax._src import distributed
    return distributed.global_state




def _dist_devices():
    """ONE device per process from a backend that spans every process, or
    None when this is a single-process job.  Prefers the default backend
    (real pods), falls back to cpu (localhost multi-process topology).
    One-per-process keeps the allreduce a process-sharded sum regardless
    of how many chips each host contributes."""
    if _global_state().num_processes in (None, 0, 1):
        return None
    for platform in (None, "cpu"):
        try:
            devs = jax.devices(platform) if platform else jax.devices()
        except Exception:
            continue
        by_proc = {}
        for d in sorted(devs, key=lambda d: (d.process_index, d.id)):
            by_proc.setdefault(d.process_index, d)
        if len(by_proc) > 1:
            return [by_proc[p] for p in sorted(by_proc)]
    return None


class DistKVStore(KVStore):
    def __init__(self, name="dist_sync"):
        super().__init__(name)
        self._gc = None
        # bytes handed to cross-host collectives by push() — observable
        # evidence for the compression wire saving (tests assert on it)
        self.wire_bytes_pushed = 0
        self._psum_cache = OrderedDict()  # LRU, bounded
        self._devs = None
        self._devs_resolved = False
        # launcher env bridge (shared impl; usually already ran at import)
        from ..base import maybe_initialize_distributed_from_env
        maybe_initialize_distributed_from_env()
        # localhost topology: cross-process CPU collectives need gloo,
        # selected before the cpu client is first created
        gs = _global_state()
        if gs.num_processes and gs.num_processes > 1:
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception:
                pass  # already created or unavailable: discovery decides
            # rendezvous before the first collective: workers reach this
            # point with minutes of skew (import + jit compile), far beyond
            # gloo's ~30s peer-connect window.  Only the FIRST store per
            # process synchronizes — later creations are past import skew,
            # and ranks may legitimately create different numbers of stores
            # (a fixed id would stall 180s per extra instance).
            global _rendezvoused
            if not _rendezvoused:
                _rendezvoused = True
                try:
                    gs.client.wait_at_barrier("mxnet_tpu_kvstore_init",
                                              180_000)
                except Exception:
                    from ..base import _logger
                    _logger.warning(
                        "kvstore init rendezvous failed; first collective "
                        "may race peer startup")
                # establish the collective context NOW, while workers are
                # aligned: the first gloo context handshake has a ~30s
                # window, and a large graph compiling on one worker before
                # its first collective can exceed it under load — a tiny
                # warm-up collective compiles in ~1s and later collectives
                # reuse the context.  Runs UNCONDITIONALLY: collectives
                # pair by order across ranks, so gating it on the local
                # rendezvous outcome could pair one rank's first real push
                # with its peers' warm-up barrier; if peers truly diverged,
                # gloo's own handshake timeout raises here rather than
                # corrupting a later reduction.
                self.barrier()

    @property
    def rank(self):
        gs = _global_state()
        if gs.num_processes and gs.num_processes > 1:
            return int(gs.process_id)
        if jax.process_count() > 1:
            return jax.process_index()
        return int(os.environ.get("DMLC_WORKER_ID", 0))

    @property
    def num_workers(self):
        gs = _global_state()
        if gs.num_processes and gs.num_processes > 1:
            return int(gs.num_processes)
        if jax.process_count() > 1:
            return jax.process_count()
        return int(os.environ.get("DMLC_NUM_WORKER", 1))

    def set_gradient_compression(self, compression_params):
        params = dict(compression_params or {})
        ctype = params.pop("type", "2bit")
        if ctype != "2bit":
            raise MXNetError("unsupported compression type %r" % ctype)
        self._gc = GradientCompression(**params)

    def _spanning_devices(self):
        """Memoized cross-process device list — the topology is fixed
        after jax.distributed init, so discover it once.  A multi-process
        job that cannot find a spanning backend is a hard error: silently
        skipping the allreduce would let each worker train on only its own
        gradients and diverge."""
        if not self._devs_resolved:
            self._devs = _dist_devices()
            self._devs_resolved = True
            gs = _global_state()
            if self._devs is None and gs.num_processes \
                    and gs.num_processes > 1:
                raise MXNetError(
                    "dist kvstore: %d processes connected but no jax "
                    "backend spans them (cpu collectives need gloo selected "
                    "before the cpu client is first created — create the "
                    "kvstore before touching jax devices)"
                    % gs.num_processes)
        return self._devs

    def _cached_fn(self, key, build):
        """LRU lookup in the jitted-collective cache (bounded; see
        ``MXNET_TPU_PSUM_CACHE_SIZE``)."""
        cached = self._psum_cache.get(key)
        if cached is None:
            cached = build()
            self._psum_cache[key] = cached
        else:
            self._psum_cache.move_to_end(key)
        while len(self._psum_cache) > _psum_cache_size():
            self._psum_cache.popitem(last=False)
        return cached

    def _psum_fn(self, devs):
        def build():
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(devs), ("host",))
            fn = jax.jit(lambda x: jnp.sum(x, axis=0),
                         out_shardings=NamedSharding(mesh, P()))
            return fn, mesh
        return self._cached_fn(tuple(d.id for d in devs), build)

    def _psum_list_fn(self, devs, n):
        """ONE jitted program summing a whole pytree of host-stacked
        arrays — the batched push_pull_list collective (one dispatch for
        every key instead of one program per key)."""
        def build():
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            # graftlint: disable=GL003 — np over the static device list
            mesh = Mesh(np.array(devs), ("host",))
            repl = NamedSharding(mesh, P())
            fn = jax.jit(lambda xs: [jnp.sum(x, axis=0) for x in xs],
                         out_shardings=[repl] * n)
            return fn, mesh
        return self._cached_fn(("ptree", n) + tuple(d.id for d in devs),
                               build)

    def _allgather_fn(self, devs):
        def build():
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(devs), ("host",))
            fn = jax.jit(lambda x: x,
                         out_shardings=NamedSharding(mesh, P()))
            return fn, mesh
        return self._cached_fn(("ag",) + tuple(d.id for d in devs), build)

    def _allgather_across_hosts(self, arr):
        """Gather a host-local array from all processes: returns the
        [n_hosts, ...] stack, fully replicated (same SPMD construction
        as _allreduce_across_hosts, identity function + replicated
        output sharding -> XLA lowers to an all-gather)."""
        devs = self._spanning_devices()
        if devs is None:
            return np.asarray(arr)[None]
        from jax.sharding import NamedSharding, PartitionSpec as P
        client = devs[0].client
        my_proc = client.process_index()
        local = [d for d in devs if d.process_index == my_proc][0]
        fn, mesh = self._allgather_fn(devs)
        shard = jax.device_put(np.asarray(arr)[None], local)
        garr = jax.make_array_from_single_device_arrays(
            (len(devs),) + tuple(arr.shape),
            NamedSharding(mesh, P("host")), [shard])
        out = fn(garr)
        return np.asarray(out.addressable_shards[0].data)

    def _allreduce_across_hosts(self, arr):
        """Sum a host-local array across all processes.  SPMD over the
        cross-process backend: every worker contributes its shard of a
        process-sharded global array, one jitted sum reduces it, XLA lowers
        the exchange to DCN collectives.  All workers must push the same
        keys in the same order — the reference's sync-mode contract."""
        devs = self._spanning_devices()
        if devs is None:
            return arr
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        client = devs[0].client
        my_proc = client.process_index()
        local = [d for d in devs if d.process_index == my_proc][0]
        fn, mesh = self._psum_fn(devs)
        shard = jax.device_put(np.asarray(arr)[None], local)
        garr = jax.make_array_from_single_device_arrays(
            (len(devs),) + tuple(arr.shape),
            NamedSharding(mesh, P("host")), [shard])
        out = fn(garr)
        res = np.asarray(out.addressable_shards[0].data)
        return jnp.asarray(res)

    def _allreduce_list_across_hosts(self, arrs):
        """Sum a LIST of host-local arrays across all processes in ONE
        jitted pytree program (one dispatch for the whole key batch —
        the batched analog of ``_allreduce_across_hosts``)."""
        devs = self._spanning_devices()
        if devs is None:
            return list(arrs)
        from jax.sharding import NamedSharding, PartitionSpec as P
        client = devs[0].client
        my_proc = client.process_index()
        local = [d for d in devs if d.process_index == my_proc][0]
        fn, mesh = self._psum_list_fn(devs, len(arrs))
        sharding = NamedSharding(mesh, P("host"))
        garrs = []
        for arr in arrs:
            # graftlint: disable=GL003 — deliberate host staging: each
            # process contributes its shard of the cross-host global
            # array (same contract as _allreduce_across_hosts above)
            shard = jax.device_put(np.asarray(arr)[None], local)
            garrs.append(jax.make_array_from_single_device_arrays(
                (len(devs),) + tuple(np.shape(arr)), sharding, [shard]))
        outs = fn(garrs)
        # graftlint: disable=GL003 — read back the replicated result
        return [jnp.asarray(np.asarray(o.addressable_shards[0].data))
                for o in outs]

    def _apply_reduced(self, k, merged):
        """Post-collective per-key bookkeeping: optimizer or store."""
        stored = self._stored.get(k)
        if stored is None:
            raise MXNetError("key %r has not been initialized" % (k,))
        if self._updater is not None:
            from . import _updater_key
            self._updater(_updater_key(k), merged, stored)
        else:
            merged.copyto(stored)

    def push(self, key, value, priority=0):
        keys, values = _key_value(key, value)
        for k, v in zip(keys, values):
            merged = self._reduce(v, key=k)  # local devices first
            t0 = time.perf_counter()
            if self._gc is not None:
                # the 2-bit codes ARE the wire payload: all-gather the
                # packed uint8 (2 bits/element — the reference ps-lite
                # density, gradient_compression.h:52) and sum the codes
                # locally; 16x fewer DCN bytes than a float32 allreduce,
                # same result as summing dequantized gradients
                packed = self._gc.quantize(k, merged._h.array)
                nbytes = int(packed.nbytes)
                self.wire_bytes_pushed += nbytes
                gathered = self._allgather_across_hosts(packed)
                arr = self._gc.dequantize_sum(
                    gathered, merged.shape, merged._h.array.dtype)
            else:
                nbytes = int(merged._h.array.nbytes)
                self.wire_bytes_pushed += nbytes
                arr = self._allreduce_across_hosts(merged._h.array)
            record_comm_exposed("push", nbytes,
                                time.perf_counter() - t0, self._type)
            self._apply_reduced(k, NDArray(arr))

    def push_pull_list(self, keys, push_values, pull_outs, priority=0):
        """Batched fused push+pull: ONE cross-host collective dispatch
        for every key (a single jitted pytree psum — or, compressed, a
        single all-gather of every key's concatenated 2-bit codes)
        instead of one program per key.  Semantics per key are identical
        to ``push`` + ``pull``: reduce across hosts, hand the reduced
        value to the updater (or the store), fill ``pull_outs`` from the
        stored state."""
        merged = [self._reduce(v, key=k)
                  for k, v in zip(keys, push_values)]
        for k in keys:
            if self._stored.get(k) is None:
                raise MXNetError("key %r has not been initialized" % (k,))
        t0 = time.perf_counter()
        if self._gc is not None:
            packed = [self._gc.quantize(k, m._h.array)
                      for k, m in zip(keys, merged)]
            lens = [int(p.shape[0]) for p in packed]
            concat = jnp.concatenate(packed) if len(packed) > 1 \
                else packed[0]
            nbytes = int(concat.nbytes)  # metadata; no device sync
            self.wire_bytes_pushed += nbytes
            gathered = self._allgather_across_hosts(concat)
            reduced, off = [], 0
            for m, n in zip(merged, lens):
                rows = jnp.asarray(gathered)[:, off:off + n]
                off += n
                reduced.append(self._gc.dequantize_sum(
                    rows, m.shape, m._h.array.dtype))
        else:
            arrs = [m._h.array for m in merged]
            nbytes = sum(int(a.nbytes) for a in arrs)
            self.wire_bytes_pushed += nbytes
            reduced = self._allreduce_list_across_hosts(arrs)
        record_comm_exposed("push_pull", nbytes,
                            time.perf_counter() - t0, self._type)
        for k, arr, out in zip(keys, reduced, pull_outs):
            self._apply_reduced(k, NDArray(jnp.asarray(arr)))
            self.pull(k, out=out, priority=priority)

    def barrier(self):
        """Named rendezvous barrier.

        An anonymous scalar allreduce pairs purely by call order: a rank
        calling barrier() a different number of times would silently pair
        its barrier with a peer's data reduction and corrupt values.  So
        a per-call named coordination-service barrier runs FIRST — call
        skew fails loudly there (timeout) — and the scalar allreduce runs
        after it, preserving this method's role as the gloo-context
        warm-up collective (see __init__)."""
        global _barrier_seq
        _barrier_seq += 1  # process-global: barrier ids never reused
        try:
            from jax._src import distributed
            client = getattr(distributed.global_state, "client", None)
        except Exception:
            client = None
        if client is not None:
            client.wait_at_barrier(
                "mxnet_tpu_kv_barrier_%d" % _barrier_seq, 180_000)
        self._allreduce_across_hosts(jnp.zeros((1,), jnp.float32))

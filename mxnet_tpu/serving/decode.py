"""Paged-KV autoregressive decode: iteration-level transformer serving
over the :class:`~mxnet_tpu.serving.kv_cache.KVBlockPool`.

The continuous batcher (serving/continuous.py) carries FIXED-SHAPE
recurrent state per slot — the right model for LSTMs, the wrong one
for transformers whose per-stream state (the KV cache) grows each
step.  This tier keeps the same slot/occupancy scheduling
(:class:`~mxnet_tpu.serving.continuous.SlotScheduler`) but swaps the
per-slot carry for a slot -> PAGE-TABLE indirection into one
device-resident block pool:

- ONE jitted fixed-shape step program per decoder config:
  ``(k_pool, v_pool, params, tokens, positions, active, tables) ->
  (k_pool, v_pool, next_tokens, logits)``.  Scatter writes this
  step's K/V row at each stream's (page, offset) cursor; gather-attend
  reads through the stream's table.  Joins, leaves, prefill and decode
  all run this exact signature, so after warmup the steady state is
  ZERO retraces — verified through the same ``executor_cache``
  counters as every other program (``note_trace`` in the traced body).
- Inactive slots write into trash page 0 and attend over nothing: the
  ``valid`` SELECT zeroes gathered operands AND masks scores (a
  multiply would turn ``0 * garbage`` into NaN).
- Determinism: a row's attention window is exactly its own appended
  tokens — pool positions beyond the cursor, other streams' pages, and
  table zeros are all dropped by SELECT — so every served stream is
  bitwise-equal to decoding it alone (tests/test_kv_cache.py pins
  this).
- Prefill is the same program fed one prompt token per iteration; the
  decode phase feeds the previous argmax (greedy).
- **Prefix reuse + COW.**  ``submit`` probes the pool's prefix cache
  with the chain hash of each leading FULL prompt page; hits are
  retained and skipped by prefill.  When the whole prompt is cached
  (an exact page multiple), the stream backs off one token — the last
  prompt token's forward must still run to produce the first generated
  token — and its K/V rewrite targets the shared tail page: that is
  the copy-on-write trigger, ``KVBlockPool.ensure_private`` clones the
  page and the stream's table entry swaps to the private copy.
- A stream that cannot get a page sheds with the typed ``Overloaded``
  (the STREAM fails; co-batched streams proceed).

See docs/serving.md §paged-KV for the anatomy and
``tools/traceview.py --serving`` for the page-pool dashboard.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import context as _context
from .. import threads as _threads
from ..analysis import locksan as _locksan
from ..base import MXNetError
from ..observability import instrument as _instrument
from ..observability import reqtrace as _reqtrace
from ..observability import tracing
from . import metrics
from .continuous import SlotScheduler, default_slot_count
from .errors import Overloaded
from .kv_cache import KVBlockPool, page_chain_hash


@functools.lru_cache(maxsize=None)
def _paged_step_program(num_layers, num_heads, head_dim, embed_dim,
                        ffn_dim, vocab_size, slot_count, max_pages,
                        page_size, donate):
    """Build (once per config) the jitted fixed-shape decode step:
    (k_pool, v_pool, params, tokens, positions, active, tables) ->
    (k_pool, v_pool, next_tokens, logits)."""
    import jax
    import jax.numpy as jnp

    S, T = slot_count, max_pages * page_size
    scale = 1.0 / float(head_dim) ** 0.5

    def _ln(x, g, b):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def step(k_pool, v_pool, params, tokens, positions, active, tables):
        from .. import executor_cache
        # count the (re)trace like every executor program: the zero-
        # retrace warmup contract is verified through the same counters
        executor_cache.note_trace("fwd", label="serving:paged_decode")
        rows = jnp.arange(S, dtype=jnp.int32)
        h = params["embed"][tokens] + params["pos"][positions]   # [S, E]
        page_idx = jnp.where(
            active, tables[rows, positions // page_size], 0)
        in_page = positions % page_size
        t_idx = jnp.arange(T, dtype=jnp.int32)
        # a row may see exactly the pool positions <= its own write
        # cursor; everything else in the gathered window — trash page,
        # table zeros, other streams' leftovers — is dropped by SELECT
        # (zeroed operands + masked scores), never by multiplication
        valid = (t_idx[None, :] <= positions[:, None]) & active[:, None]
        for l in range(num_layers):
            p = "l%d." % l
            x = _ln(h, params[p + "ln1_g"], params[p + "ln1_b"])
            q = (x @ params[p + "wq"].T + params[p + "bq"]) \
                .reshape(S, num_heads, head_dim)
            k = (x @ params[p + "wk"].T + params[p + "bk"]) \
                .reshape(S, num_heads, head_dim)
            v = (x @ params[p + "wv"].T + params[p + "bv"]) \
                .reshape(S, num_heads, head_dim)
            # append: one scatter per layer writes this step's K/V row
            # into each stream's current (page, offset); inactive slots
            # land in trash page 0
            k_pool = k_pool.at[l, page_idx, in_page].set(k)
            v_pool = v_pool.at[l, page_idx, in_page].set(v)
            # gather-attend over the stream's page table
            k_ctx = k_pool[l][tables].reshape(S, T, num_heads, head_dim)
            v_ctx = v_pool[l][tables].reshape(S, T, num_heads, head_dim)
            k_ctx = jnp.where(valid[:, :, None, None], k_ctx,
                              jnp.float32(0))
            v_ctx = jnp.where(valid[:, :, None, None], v_ctx,
                              jnp.float32(0))
            s = jnp.einsum("shd,sthd->sht", q, k_ctx) * scale
            s = jnp.where(valid[:, None, :], s, jnp.float32(-1e30))
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("sht,sthd->shd", w, v_ctx).reshape(S, -1)
            h = h + o @ params[p + "wo"].T + params[p + "bo"]
            y = _ln(h, params[p + "ln2_g"], params[p + "ln2_b"])
            f = y @ params[p + "w1"].T + params[p + "b1"]
            f = 0.5 * f * (1.0 + jax.lax.erf(f * jnp.float32(
                0.7071067811865476)))
            h = h + f @ params[p + "w2"].T + params[p + "b2"]
        hf = _ln(h, params["lnf_g"], params["lnf_b"])
        logits = hf @ params["head_w"].T + params["head_b"]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return k_pool, v_pool, nxt, logits

    kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(step, **kwargs)


class PagedDecodeStream:
    """One generation request against a :class:`PagedTransformerDecoder`:
    the prompt, the greedy continuation, and completion state."""

    __slots__ = ("prompt", "max_new_tokens", "eos_token", "slot",
                 "position", "history", "pages", "chain", "prefix_pages",
                 "generated", "logits_rows", "_done", "_cond", "error",
                 "ctx")

    def __init__(self, prompt, max_new_tokens, eos_token):
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = None if eos_token is None else int(eos_token)
        self.slot = None
        self.position = 0          # tokens already appended to KV
        self.history = []          # every appended token, in order
        self.pages = []            # page ids, table order
        self.chain = 0             # chain hash through the last full page
        self.prefix_pages = 0      # pages reused from the prefix cache
        self.generated = []        # greedy continuation token ids
        self.logits_rows = []      # per generated token: [vocab] f32 row
        self._done = False
        self._cond = _threads.package_condition("PagedDecodeStream._cond")
        self.error = None
        self.ctx = None

    @property
    def done(self):
        return self._done

    def _finish(self, error=None):
        with self._cond:
            if self._done:
                return
            self.error = error
            self._done = True
            self._cond.notify_all()

    def wait(self, timeout=None):
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise MXNetError("stream did not finish within %ss"
                                 % timeout)
        if self.error is not None:
            raise self.error
        return self

    def outputs(self):
        """(token_ids list, logits array [n_generated, vocab])."""
        if self.error is not None:
            raise self.error
        logits = np.stack(self.logits_rows) if self.logits_rows \
            else np.zeros((0, 0), np.float32)
        return list(self.generated), logits

    @property
    def steps_decoded(self):
        return len(self.generated)


class PagedTransformerDecoder(SlotScheduler):
    """Iteration-level greedy decode over a paged KV pool (module
    docstring has the model).

    ``params``: canonical float32 arrays (the
    ``TransformerLM.decode_param_arrays()`` schema).  ``config``: dict
    with vocab_size / embed_dim / num_heads / num_layers / ffn_dim /
    seq_len (``TransformerLM(...).config``).  ``max_len`` caps context
    per stream (default: config seq_len, the position-table size)."""

    def __init__(self, params, config, slot_count=None, pool=None,
                 max_len=None, name="paged"):
        import jax.numpy as jnp
        self._init_slots(slot_count, name)
        cfg = dict(config)
        self.vocab_size = int(cfg["vocab_size"])
        self.embed_dim = int(cfg["embed_dim"])
        self.num_heads = int(cfg["num_heads"])
        self.num_layers = int(cfg["num_layers"])
        self.ffn_dim = int(cfg.get("ffn_dim") or 4 * self.embed_dim)
        self.head_dim = self.embed_dim // self.num_heads
        pos_len = int(params["pos"].shape[0])
        self.max_len = min(int(max_len), pos_len) if max_len else pos_len
        self._owns_pool = pool is None
        self.pool = pool if pool is not None else KVBlockPool(
            self.num_layers, self.num_heads, self.head_dim,
            name="%s.kv" % self.name)
        if (self.pool.num_layers, self.pool.num_heads,
                self.pool.head_dim) != (self.num_layers, self.num_heads,
                                        self.head_dim):
            raise MXNetError("KVBlockPool geometry %s does not match "
                             "model (%d layers, %d heads, %d head_dim)"
                             % ((self.pool.num_layers,
                                 self.pool.num_heads, self.pool.head_dim),
                                self.num_layers, self.num_heads,
                                self.head_dim))
        self.page_size = self.pool.page_size
        self.max_pages = -(-self.max_len // self.page_size)
        # graftlint: disable=GL003 — one-time host->device upload of the
        # decoded parameter arrays at construction, not traced compute
        self._params = {k: jnp.asarray(np.asarray(v, np.float32))
                        for k, v in params.items()}
        # donating the pools makes each step an in-place append; jax
        # implements donation on accelerator backends only
        donate = _context.on_tpu()
        self._step_fn = _paged_step_program(
            self.num_layers, self.num_heads, self.head_dim,
            self.embed_dim, self.ffn_dim, self.vocab_size,
            self.slot_count, self.max_pages, self.page_size, donate)
        self._tracker = _instrument.StepTracker(
            pid="serving", names=_instrument.DECODE)

    # -- scheduling --------------------------------------------------------

    def submit(self, prompt, max_new_tokens=32, eos_token=None):
        """Queue one greedy-decode request.  ``prompt``: 1-D int token
        ids (at least one).  The prefix cache is probed here: every
        leading FULL page of the prompt whose chain hash is cached is
        reused (retained, its tokens never re-prefilled)."""
        prompt = np.asarray(prompt).reshape(-1).astype(np.int64)
        if prompt.size == 0:
            raise MXNetError("prompt must have at least one token")
        if prompt.size + int(max_new_tokens) > self.max_len:
            raise MXNetError(
                "prompt (%d) + max_new_tokens (%d) exceeds max context "
                "%d" % (prompt.size, int(max_new_tokens), self.max_len))
        stream = PagedDecodeStream(prompt, max_new_tokens, eos_token)
        stream.ctx = _reqtrace.mint(self.name, rows=1, kind="stream")
        ps = self.page_size
        usable = len(stream.prompt) // ps
        chain = 0
        probes = 0
        for pg in range(usable):
            nxt = page_chain_hash(
                chain, stream.prompt[pg * ps:(pg + 1) * ps])
            probes += 1
            page = self.pool.lookup_retain(nxt)
            if page is None:
                break
            stream.pages.append(page)
            chain = nxt
        stream.prefix_pages = len(stream.pages)
        stream.position = stream.prefix_pages * ps
        if stream.position >= len(stream.prompt):
            # the whole prompt (an exact page multiple) is cached: back
            # off one token — the LAST prompt token's forward must still
            # run, it produces the first generated token.  Its K/V
            # rewrite targets the shared tail page: that is the COW
            # trigger (step() clones it before writing).  The chain
            # rewinds to the pages that stay untouched.
            stream.position = len(stream.prompt) - 1
            chain = 0
            for pg in range(stream.prefix_pages - 1):
                chain = page_chain_hash(
                    chain, stream.prompt[pg * ps:(pg + 1) * ps])
        stream.chain = chain
        stream.history = stream.prompt[:stream.position]
        metrics.record_kv_prefix(lookups=probes,
                                 hit_pages=stream.prefix_pages)
        self._enqueue(stream)
        return stream

    # SlotScheduler hooks --------------------------------------------------

    def _queue_seg_args(self, stream):
        return {"prefix_pages": stream.prefix_pages}

    def _on_reject_locked(self, stream):
        self._release_stream_locked(stream)

    def _on_close_locked(self, doomed):
        for stream in doomed:
            self._release_stream_locked(stream)

    def _close_error(self, stream):
        return MXNetError(
            "PagedTransformerDecoder closed with the stream "
            "unfinished (%d tokens generated)" % len(stream.generated))

    # -- the iteration -----------------------------------------------------

    def _release_stream_locked(self, stream):
        for page in stream.pages:
            self.pool.release(page)
        stream.pages = []

    def _shed(self, slot, stream, exc, overflow):
        self._slots[slot] = None
        self._release_stream_locked(stream)
        overflow.append((stream, exc))

    def step(self):
        """One decode iteration: seat waiting streams, ensure each
        active stream's write-target page exists AND is private (a
        shared/prefix-registered page is COW-cloned first; a stream
        that cannot get a page fails with ``Overloaded`` — the STREAM,
        not the decoder), run the fixed-shape program, append/advance,
        register completed pages with the prefix cache, collect
        generated tokens, retire EOS streams.  Returns the number of
        active slots run.  The host work is named in phases of the
        decoder's own tracker (``decode:admit``, ``decode:tables``,
        ``decode:dispatch``, ``decode:fetch``, ``decode:commit``, under
        ``decode:iter``): ``observability.instrument``."""
        overflow = []
        tracker = self._tracker
        with tracker.phase("decode:admit"), self._lock:
            joins = self._admit_locked()
            batch = []
            for slot, stream in enumerate(self._slots):
                if stream is None:
                    continue
                need = stream.position // self.page_size
                if need >= len(stream.pages):
                    try:
                        stream.pages.append(self.pool.alloc())
                    except Overloaded as exc:
                        # this stream sheds; co-batched ones proceed
                        self._shed(slot, stream, exc, overflow)
                        continue
                batch.append((slot, stream, need))
        # COW pass OUTSIDE the scheduler lock: a clone dispatches a
        # device program (pool bookkeeping has its own lock); streams
        # seated in slots are only mutated by this stepping thread
        with tracker.phase("decode:tables"):
            active = []
            tokens = np.zeros((self.slot_count,), np.int32)
            positions = np.zeros((self.slot_count,), np.int32)
            active_mask = np.zeros((self.slot_count,), bool)
            tables = np.zeros((self.slot_count, self.max_pages), np.int32)
            for slot, stream, need in batch:
                try:
                    page, cloned = self.pool.ensure_private(
                        stream.pages[need])
                except Overloaded as exc:
                    with self._lock:
                        self._shed(slot, stream, exc, overflow)
                    continue
                if cloned:
                    stream.pages[need] = page
                if stream.position < len(stream.prompt):
                    fed = stream.prompt[stream.position]   # prefill
                else:
                    fed = stream.generated[-1]             # decode
                tokens[slot] = fed
                positions[slot] = stream.position
                active_mask[slot] = True
                tables[slot, :len(stream.pages)] = stream.pages
                active.append((slot, stream, fed))
            for stream, exc in overflow:
                metrics.record_rejection("Overloaded")
                stream._finish(exc)
                _reqtrace.finish_rejected(stream.ctx, exc)
        if not active:
            tracker.cancel_step()
            return 0
        t_i0 = time.monotonic()
        with tracing.span("serving:paged_decode_step", category="serving",
                          pid="serving",
                          args={"active": len(active), "joins": joins}):
            _locksan.check_dispatch_clear("paged.step")
            with tracker.phase("decode:dispatch", dispatches=True):
                k_pool, v_pool, nxt, logits = self._step_fn(
                    self.pool.k_pool, self.pool.v_pool, self._params,
                    tokens, positions, active_mask, tables)
                self.pool.k_pool, self.pool.v_pool = k_pool, v_pool
            # the results on the host: the device has run dry
            with tracker.phase("decode:fetch", drains=True):
                nxt_host = np.asarray(nxt)
                logits_host = np.asarray(logits)
        t_i1 = time.monotonic()
        self.iterations += 1
        with tracker.phase("decode:commit"):
            leaves = self._commit(active, nxt_host, logits_host, t_i0, t_i1)
            metrics.record_decode_step(len(active), joins, leaves)
        tracker.step_end(self.iterations - 1)
        return len(active)

    def _commit(self, active, nxt_host, logits_host, t_i0, t_i1):
        """Append/advance every stream that ran, offer filled pages to
        the prefix cache, finish the streams that are done.  Returns how
        many left."""
        pool_used = self.pool.pages_used()
        finished = []
        with self._lock:
            for slot, stream, fed in active:
                if stream.ctx is not None:
                    stream.ctx.seg(
                        "decode_step", t_i0, t_i1, slot=slot,
                        active=len(active), iteration=self.iterations - 1,
                        pages=len(stream.pages),
                        prefix_pages=stream.prefix_pages,
                        pool_in_use=pool_used)
                stream.history.append(int(fed))
                stream.position += 1
                if stream.position % self.page_size == 0:
                    # a page just filled: immutable from here on — offer
                    # it to the prefix cache under its chain hash
                    pg = stream.position // self.page_size - 1
                    stream.chain = page_chain_hash(
                        stream.chain,
                        stream.history[pg * self.page_size:])
                    self.pool.register_prefix(stream.chain,
                                              stream.pages[pg])
                eos = False
                if stream.position >= len(stream.prompt):
                    g = int(nxt_host[slot])
                    stream.generated.append(g)
                    stream.logits_rows.append(logits_host[slot].copy())
                    eos = (len(stream.generated) >= stream.max_new_tokens
                           or (stream.eos_token is not None
                               and g == stream.eos_token)
                           or stream.position >= self.max_len)
                if eos:
                    self._slots[slot] = None
                    pages_held = len(stream.pages)
                    self._release_stream_locked(stream)
                    finished.append((stream, pages_held))
        for stream, pages_held in finished:
            metrics.record_kv_stream_finished(pages_held)
            stream._finish(None)
            _reqtrace.finish(stream.ctx, status="ok",
                             steps=len(stream.generated),
                             prefix_pages=stream.prefix_pages)
        return len(finished)

    # -- warmup ------------------------------------------------------------

    def warmup(self, verify=True):
        """Trace the decode program AND the COW clone before traffic
        (all slots inactive: writes land in the trash page, reads are
        fully masked).  With ``verify``, a second iteration must add
        ZERO retraces — the steady-state contract every join/leave/
        prefill/decode/COW inherits, since they all run these exact
        signatures."""
        from .. import executor_cache
        if self.pending():
            raise MXNetError("warmup must run before streams are "
                             "submitted")
        with executor_cache.watch_traces() as w:
            self._warm_iteration()
        traces = w.total()
        if verify:
            with executor_cache.watch_traces() as w2:
                self._warm_iteration()
            if w2.total():
                raise MXNetError(
                    "paged-decoder warmup verification failed: %d "
                    "retraces on the second iteration (delta: %s)"
                    % (w2.total(), w2.delta()))
        self.iterations = 0
        return {"traces": traces, "slot_count": self.slot_count,
                "pool": self.pool.stats()}

    def _warm_iteration(self):
        tokens = np.zeros((self.slot_count,), np.int32)
        positions = np.zeros((self.slot_count,), np.int32)
        active_mask = np.zeros((self.slot_count,), bool)
        tables = np.zeros((self.slot_count, self.max_pages), np.int32)
        k_pool, v_pool, _, _ = self._step_fn(
            self.pool.k_pool, self.pool.v_pool, self._params,
            tokens, positions, active_mask, tables)
        self.pool.k_pool, self.pool.v_pool = k_pool, v_pool
        # pre-trace the COW clone (trash page onto itself) so a
        # mid-traffic clone adds zero retraces
        self.pool.warm_cow()

    # -- lifecycle ---------------------------------------------------------

    def close(self):
        SlotScheduler.close(self)
        if self._owns_pool:
            # a caller-supplied pool may outlive this decoder (shared
            # across decoders); one the decoder built is its to retire
            self.pool.close()

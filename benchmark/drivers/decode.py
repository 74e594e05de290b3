"""Generation cells: ``PagedTransformerDecoder.submit`` / ``.step`` under
clients that are state machines in the one loop that calls ``step()``.

Closed loop: each client submits its next request when its last finishes.
Set-up staggers the clients' first requests over ``stagger_iterations``
iterations of the real loop, so the window opens on slots whose streams are
spread over every phase of prefill and decode, not on 64 prefills in step.
The window opens at the end of an iteration and closes at the end of the
first iteration that ends at or after ``--seconds``.  Inside it the harness
reads the clock twice an iteration and stores into preallocated arrays."""
from __future__ import annotations

import gc
import importlib

import numpy as np

from .. import compare, harness, shapes, traffic as traffic_mod, weights
from ..window import StepWindow, quantile

CAPACITY = 1 << 15


def run(loaded, args, devices, spans, tracer, clock, t_start, fault=None,
        check_it=True):
    import jax

    from mxnet_tpu import executor_cache

    cfg, mix = loaded["config"], loaded["traffic"]
    model, arrivals = cfg["model"], mix["arrivals"]
    if arrivals["kind"] != "closed":
        raise harness.Refused("arrivals %r need a driver this benchmark "
                              "does not have yet" % arrivals["kind"])
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    builder = importlib.import_module("benchmark.builders." + cfg["builder"])
    seconds = min(args.seconds, mix["trace_seconds"]) if args.trace \
        else args.seconds
    requests = traffic_mod.decode_requests(mix, model["vocab_size"],
                                           args.seed)
    with jax.default_device(devices[0]):
        w = weights.make_weights(args.seed, ref.param_shapes(model),
                                 cfg["init"]["rules"],
                                 cfg["init"]["round_bf16"])
    dec, pool = builder.decoder(cfg, w)
    if fault is not None:
        fault(dec=dec)
    dec.warmup()
    page = int(cfg["serving"]["page_tokens"])

    n_clients = int(arrivals["clients"])
    stagger = int(arrivals["stagger_iterations"])
    first_iter = [(c * stagger) // n_clients for c in range(n_clients)]
    cur = [None] * n_clients            # the client's stream in flight
    t_submit = np.zeros(n_clients)
    seen = np.zeros(n_clients, np.int64)        # generated tokens seen
    last_pos = np.zeros(n_clients, np.int64)
    next_req = 0
    window = StepWindow(seconds, CAPACITY)
    call_s = np.zeros(CAPACITY)
    new_tokens = np.zeros(CAPACITY, np.int64)
    gap_weight = np.zeros(CAPACITY, np.int64)
    need_flops = np.zeros(CAPACITY)
    need_bytes = np.zeros(CAPACITY)
    ttft_rows, finished, errors = [], [], []
    marks = {}
    it = 0
    slots = int(cfg["serving"]["slots"])

    while not window.closed:
        for c in range(n_clients):
            if cur[c] is None and it >= first_iter[c]:
                prompt, n_new = requests[next_req % len(requests)]
                next_req += 1
                with spans("client:submit"):
                    t_submit[c] = harness.now()
                    cur[c] = dec.submit(prompt, max_new_tokens=n_new,
                                        eos_token=mix.get("eos"))
                seen[c] = 0
                last_pos[c] = cur[c].position
        t0 = harness.now()
        with spans("decode:step"):
            dec.step()
        t1 = harness.now()
        k = window.n
        counted = window.is_open
        fresh = gaps = 0
        contexts = []
        for c in range(n_clients):
            st = cur[c]
            if st is None:
                continue
            if st.position != last_pos[c]:
                last_pos[c] = st.position
                contexts.append(st.position)
            g = len(st.generated)
            if g > seen[c]:
                fresh += g - seen[c]
                if seen[c] == 0:
                    if counted:
                        ttft_rows.append((t1 - t_submit[c], len(st.prompt)
                                          - st.prefix_pages * page))
                else:
                    gaps += 1
                seen[c] = g
            if st.done:
                st.logits_rows = []     # 200 KB a token; only tokens kept
                if st.error is not None:
                    errors.append(repr(st.error))
                elif counted:
                    finished.append((np.asarray(st.prompt, np.int64),
                                     list(st.generated), st.max_new_tokens))
                cur[c] = None
        it += 1
        if counted:
            call_s[k] = t1 - t0
            new_tokens[k] = fresh
            gap_weight[k] = gaps
            if args.trace:
                need_flops[k] = sum(shapes.lm_token_flops(model, ctx, True)
                                    for ctx in contexts) \
                    - 2.0 * model["n_embd"] * model["vocab_size"] \
                    * (len(contexts) - fresh)
                need_bytes[k] = shapes.lm_iteration_bytes(model, contexts,
                                                          slots)
            if window.step_end(t1):
                spans.close_window()
                marks["close"] = (executor_cache.trace_counts(),
                                  clock.mark())
        else:
            if it == stagger - 2:
                tracer.start()
            if it >= stagger:
                jax.block_until_ready(pool.k_pool)
                marks["open"] = (executor_cache.trace_counts(), clock.mark())
                spans.open_window()
                window.open(harness.now())

    reduced = tracer.stop_and_reduce()
    memory_peak = harness.memory_peak_bytes(
        devices, loaded["cell"]["name"], lambda: dec._step_fn.lower(
                pool.k_pool, pool.v_pool, dec._params,
                np.zeros(slots, np.int32), np.zeros(slots, np.int32),
                np.zeros(slots, bool),
                np.zeros((slots, dec.max_pages), np.int32)).compile())
    n = window.n
    elapsed = window.elapsed
    gap_s = np.repeat(window.step_seconds(), gap_weight[:n])
    ttft = np.asarray([r[0] for r in ttft_rows])
    end_to_end = {
        "decode_output_tokens_per_s": float(new_tokens[:n].sum()) / elapsed,
        "decode_ttft_p95_ms": 1e3 * quantile(ttft, 0.95),
        "decode_itl_p95_ms": 1e3 * quantile(gap_s, 0.95),
        "setup_s": window.t_open - t_start,
    }
    obs = {
        "cell": loaded["cell"], "chips": len(devices),
        "device_kind": devices[0].device_kind, "trace": reduced,
        "iterations": n, "step_call_s": float(call_s[:n].sum()),
        "ttft_rows": ttft_rows,
        "retraces_in_window": sum(
            v - marks["open"][0].get(key, 0)
            for key, v in marks["close"][0].items()),
        "compile": {"compile_s": marks["open"][1][0],
                    "cache_hits": marks["open"][1][1],
                    "cache_misses": marks["open"][1][2]},
        "required_flops": float(need_flops[:n].sum()),
        "required_bytes": float(need_bytes[:n].sum()),
    }
    tails = ["iterations %d; first tokens (ttft samples) %d; token gaps (itl "
             "samples) %d; requests finished %d; ttft p50 %.1f ms; itl p50 "
             "%.3f ms" % (n, len(ttft), len(gap_s), len(finished),
                          1e3 * quantile(ttft, 0.5),
                          1e3 * quantile(gap_s, 0.5))]

    dec.close()
    del dec, pool, cur
    gc.collect()
    if not check_it:        # the calibration tool's probes of size alone
        return {"end_to_end": end_to_end, "numbers": {}, "notes": tails,
                "memory_peak": memory_peak}
    mismatched = sum(1 for _, gen, want in finished if len(gen) != want)
    sample = pick_sample(finished, int(mix["check_requests"]), args.seed)
    gaps, _ = served_gaps(cfg, mix, w, sample)
    numbers = {"logit_gap_max": float(np.max(gaps)) if len(gaps)
               else float("inf"),
               "length_mismatch": float(mismatched)}
    notes = ["compared %d served tokens of %d requests (longest %d tokens); "
             "gap p50 %.4g" % (len(gaps), len(sample),
                               max((len(p) + len(g) for p, g, _ in sample),
                                   default=0),
                               float(np.median(gaps)) if len(gaps) else 0.0)]
    notes += ["stream error: " + e for e in errors[:5]]
    return {"end_to_end": end_to_end, "obs": obs,
            "attempted": len(finished) + len(errors), "failed": len(errors),
            "numbers": numbers, "notes": notes, "memory_peak": memory_peak,
            "reduced": reduced, "tails": tails, "sample": sample,
            "weights": w}


def pick_sample(finished, count, seed):
    """The longest finished request and ``count - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rest = order[1:]
    rng = traffic_mod.host_rng(seed, 14)
    extra = rng.choice(len(rest), min(count - 1, len(rest)), replace=False) \
        if rest else []
    return [finished[order[0]]] + [finished[rest[i]] for i in extra]


def padded_length(mix, model):
    top = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] \
        + int((mix.get("shared_prefix") or {}).get("tokens", 0))
    return min(128 * -(-top // 128), int(model["n_positions"]))


def served_gaps(cfg, mix, w, sample, operand=None):
    """One reference pass over each sampled prompt with its served tokens.
    Returns (how far each served token's logit lies below the reference's
    best, and the same for the token the ``operand``-precision pass puts
    first: the control's reading)."""
    import jax
    import jax.numpy as jnp
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    model = cfg["model"]
    pad = padded_length(mix, model)
    plain = jax.jit(lambda p, t: ref.logits(p, t, model, 0))
    low = jax.jit(lambda p, t: jnp.argmax(
        ref.logits(p, t, model, 0, operand), axis=-1)) if operand else None
    served, control = [], []
    for prompt, gen, _ in sample:
        if not gen:
            continue
        tokens = np.zeros(pad, np.int32)
        seq = np.concatenate([prompt, gen])
        tokens[:len(seq)] = seq
        rows = slice(len(prompt) - 1, len(prompt) - 1 + len(gen))
        logits = np.asarray(plain(w, jnp.asarray(tokens)))[rows]
        served.append(compare.logit_gaps(logits, gen))
        if low is not None:
            first = np.asarray(low(w, jnp.asarray(tokens)))[rows]
            control.append(compare.logit_gaps(logits, first))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)
    return cat(served), cat(control)

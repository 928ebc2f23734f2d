"""Runner of kind `serve`: a closed loop.  The list of requests goes to
ContinuousBatchingLoop.run, which admits a new one as each retires; one warm
pass over the same list builds every executable, then whole passes repeat
until --seconds have gone, and the metrics are over the complete passes."""

from __future__ import annotations

import os
import threading
import time

from . import device as _device
from . import stats, trace, traffic

# max |logit - reference| allowed on the checked requests.  The loop runs
# float32 weights at the chip's default matmul precision (bf16 passes), the
# reference runs "highest": logits are O(1) (layer-normed activations
# against 1/sqrt(d) embeddings) and twelve post-norm layers of bf16-rounded
# products land within 4.3e-2 of the float32 result (measured on the v5e
# over three runs, PR 24: 0.0418-0.0428; the tolerance is twice that).  A
# wrong page, mask or position moves a logit by O(1) and fails.  Weights or
# KV stored in bf16 or int8 add rounding of about the same size again, which
# lands at or over this tolerance: such a change is expected to FAIL, and is
# a different result, not a faster one, until a benchmark PR sets a
# tolerance for it from a measurement.
LOGIT_TOLERANCE = 8e-2


def say(msg: str) -> None:
    print("[bench] " + msg, flush=True)


def run(cell, args, devices, t_start: float) -> dict:
    import jax
    import numpy as np
    from paddle_tpu import serving
    from paddle_tpu.kernels.paged_attention import fallback_count

    dev = devices[0]
    tpu = dev.platform == "tpu"
    cfg, mod, mix, sizing = (cell.config, cell.config_module, cell.traffic,
                             cell.sizing)
    counter = stats.CompileCounter()
    max_length = int(sizing["max_length"])
    if traffic.longest_context(mix) + 1 > max_length:
        raise ValueError(f"cell {cell.name}: the mix's longest context "
                         f"{traffic.longest_context(mix)} does not fit "
                         f"max_length {max_length}")
    dcfg = mod.decode_config(cfg, max_length)
    params = mod.build_params(dcfg, args.seed, dev)
    pool = serving.KVCachePool(
        num_pages=int(sizing["pool_pages"]), page_size=cfg["page_size"],
        num_layers=dcfg.n_layer, num_heads=dcfg.n_head,
        head_dim=dcfg.head_dim, dtype=cfg["kv_dtype"])
    loop = serving.ContinuousBatchingLoop(
        params, dcfg, pool, max_batch=int(sizing["max_batch"]))
    pairs = traffic.serve_requests(mix, dcfg.vocab_size, args.seed)

    def requests():
        return [serving.DecodeRequest(prompt=p, max_new_tokens=o)
                for p, o in pairs]

    def one_pass(name="bench.pass"):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter()
            got = loop.run(requests())
            return got, time.perf_counter() - t0

    say(f"weights and pool on the device after {time.perf_counter() - t_start:.1f} s")
    for _ in range(int(mix["warm_passes"])):
        _, s = one_pass("bench.warm_pass")
        say(f"warm pass {s:.1f} s, {counter.count} executables built or "
            f"loaded so far ({counter.seconds:.1f} s), {loop.steps} steps")

    # the measured window: whole passes until --seconds have gone
    compiles_before = counter.count
    steps0 = (loop.steps, loop.prefill_steps, loop.decode_steps)
    fallbacks0 = fallback_count()
    ttft, tpot, tokens, pass_seconds, errors = [], [], 0, [], 0
    finite = True
    checked = None
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    while True:
        got, s = one_pass()
        pass_seconds.append(s)
        for g in got:
            if g.error is not None or not g.tokens:
                errors += 1
                continue
            tokens += len(g.tokens)
            ttft.append(g.ttft_s)
            tpot.append(stats.tpot_s(g.admitted_at, g.ttft_s, g.finished_at,
                                     len(g.tokens)))
            finite = finite and bool(np.isfinite(g.logits[-1]).all())
        if checked is None:
            pick = np.random.RandomState(traffic.fold_seed(args.seed)).choice(
                len(got), size=min(int(mix["check_requests"]), len(got)),
                replace=False)
            checked = [got[i] for i in sorted(pick)]
        del got
        if time.perf_counter() - t_window >= args.seconds:
            break
    window_s = time.perf_counter() - t_window
    say(f"window {window_s:.1f} s, passes {pass_seconds}")
    compiles = counter.count - compiles_before
    steps = loop.steps - steps0[0]
    attempted = len(pairs) * len(pass_seconds)

    obs = {
        "kind": "serve", "chips": 1, "window_s": window_s,
        "setup_s": setup_s, "passes": len(pass_seconds),
        "pass_seconds": pass_seconds, "tokens": tokens,
        "loop_steps": steps,
        "loop_prefill_steps": loop.prefill_steps - steps0[1],
        "loop_decode_steps": loop.decode_steps - steps0[2],
        # over the loop's life: the warm pass is the same list
        "occupancy": loop.mean_occupancy(),
        "compiles_in_window": compiles,
        "compiles_in_setup": compiles_before,
        "compile_seconds_in_setup": counter.seconds,
        "paged_fallbacks": fallback_count() - fallbacks0,
        "paged_impl": loop.paged_impl,
        "device_kind": dev.device_kind, "platform": dev.platform,
        # the eager loop has no step program whose temporaries the
        # allocator would miss: its copies are arrays of their own
        "memory_peak_bytes": _device.allocator_peak_bytes(devices),
    }
    obs["end_to_end"] = {
        "decode_tokens_per_s": tokens / window_s,
        "tpot_p95_ms": 1e3 * stats.percentile(tpot, 95) if tpot else None,
        "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95) if ttft else None,
        "setup_s": setup_s,
    }

    # correct, outside the window: every request finished, logits finite, and
    # the checked requests' rows against the plain reference
    problems = []
    if errors:
        problems.append(f"{errors} requests ended with an error or no token")
    if not finite:
        problems.append("non-finite logits")
    if compiles:
        problems.append(f"{compiles} compilations inside the window")
    if obs["paged_fallbacks"]:
        problems.append(f"paged attention fell back "
                        f"{obs['paged_fallbacks']} times")
    if tpu and loop.paged_impl != "pallas":
        problems.append(f"paged tier resolved {loop.paged_impl}, not pallas")
    fed = [list(g.prompt) + list(g.tokens[:-1]) for g in checked]
    width = max_length if tpu else max(len(f) for f in fed)
    padded = np.zeros((len(fed), width), np.int32)
    for i, f in enumerate(fed):
        padded[i, :len(f)] = f
    ref = mod.reference_forward(params, dcfg, padded)
    worst = 0.0
    for i, g in enumerate(checked):
        rows = ref[i, len(g.prompt) - 1:len(fed[i])]
        worst = max(worst, float(np.max(np.abs(np.stack(g.logits) - rows))))
    obs["logit_max_abs_diff"] = worst
    if not worst <= LOGIT_TOLERANCE:
        problems.append(f"max |logit - reference| {worst} over "
                        f"{len(checked)} requests, tolerance "
                        f"{LOGIT_TOLERANCE}")
    del ref, checked

    if args.trace:
        logdir = os.path.join(args.trace_dir, cell.name)
        budget = float(mix["trace_seconds"])
        trace.start(logdir)
        # loop.run cannot be stopped half way: a timer ends the trace after
        # `budget` seconds and the pass runs on untraced to its end
        stopper = threading.Timer(budget, jax.profiler.stop_trace)
        stopper.start()
        with jax.profiler.TraceAnnotation("bench.pass"):
            loop.run(requests())
        stopper.join()
        obs["trace"] = trace.reduce(trace.load(logdir))
    return {"correct": not problems, "problems": problems,
            "attempted": attempted, "failed": errors, "obs": obs}

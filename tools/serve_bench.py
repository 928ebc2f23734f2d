#!/usr/bin/env python
"""Closed-loop serving load generator + regression gate.

Drives the serving tier end to end and prints the numbers that matter for
a batching server — latency percentiles, throughput, and batch occupancy
(the lever that dominates served throughput on TPU):

  engine mode (default): builds a model in-process, exports it as an AOT
  StableHLO artifact, wraps it in a serving.Engine, and replays a Poisson
  arrival process of mixed-size requests against submit().  Reports
  p50/p99 request latency, requests/s, rows/s, mean batch occupancy, and
  the engine's compile counters (distinct dispatched shapes must stay
  <= len(buckets)).

  decode mode (--mode decode): continuous-batching greedy decode of
  mixed-length prompts through the paged KV cache (serving/generate.py).
  Reports tokens/s, time-to-first-token percentiles, mean decode batch
  occupancy, page-pool stats, and prefill/decode step counters.
  --paged-impl {reference,pallas,interpret} pins the paged-attention
  path (default: FLAGS_serving_paged_impl, i.e. auto) and --prefill
  {batched,token} picks the prefill arm; both land in the result dict,
  so a reference-vs-pallas A/B rides the --baseline/--gate machinery
  like any other regression check.  --prefix-share P gives fraction P
  of the requests one common system-prompt prefix and enables the
  refcounted prefix cache (serving/prefixcache.py): the report gains
  prefix_hit_rate, cached_prefill_tokens, cow_copies, and TTFT
  p50/p99 still bank through the same 0/2/3 gate contract —
  shared-prefix capacity regressions fail CI like latency ones.
  --prefill-chunk N caps prefill tokens per engine step (chunked
  prefill); max_prefill_tokens_step in the report counter-asserts the
  cap, so banking it holds the TTFT-jitter discipline.  --kv-heads K
  serves a grouped-query (GQA/MQA) model from a K-head pool and
  --kv-dtype {fp32,bf16,int8} picks the page element type (int8 =
  amax-quantized pages with per-page fp32 scales); both land in the
  result next to kv_bytes_per_token (bytes one token's K/V occupies,
  scale overhead amortized in), so the H_q/H_kv x and 2x capacity wins
  bank and gate like every other metric.  --speculate N arms
  prompt-lookup speculative decoding (d=N draft tokens verified per
  step) on a REPEATED-STRUCTURE prompt workload (motif-tiled
  prompts, the traffic shape prompt lookup exists for) and runs the
  SAME replay once more at d=0 in the same invocation: the report
  banks acceptance_rate, tokens_per_step, drafted/accepted counts,
  tokens_per_s alongside tokens_per_s_d0, and spec_speedup (their
  ratio — bank it >= 1 and --gate holds the win).  --sampling
  {greedy,temp,topk,topp} attaches the matching SamplingParams
  scenario to every request (temp/topk/topp load-test the jitted
  sampling epilogue).  Speculation composes with ALL of them (ISSUE
  16): a greedy spec arm must stay token-identical to its d=0 run
  (checked in-process, exit 2 on divergence), a sampled spec arm
  instead replays itself once more and must be bit-identical (the
  (seed, token-index)-keyed stream is the contract — d=0 tokens
  legitimately differ because drafted rows consume salted keys), and
  --speculate together with --mesh N drives the SPMD program's
  multi-token verify step (d+1 tokens per mesh step, d=0 arm on the
  same mesh).

  router mode (--replicas N, engine-mode option): N Engine replicas of
  the same artifact behind one distributed.Router; the Poisson replay
  goes through router.submit().  Reports per-replica request counts /
  latency percentiles / rps, routing-decision counters
  (routed/skipped), and — with N >= 2 — a drain-handoff smoke: one
  replica is drained mid-run and the result must show
  post_drain_misroutes == 0 and lost_requests == 0 (bank those zeros
  and --gate holds them).

  fleet modes (--disagg / --fleet, decode-mode options): the replay
  through a disaggregated prefill/decode Fleet (serving/fleet) — one
  PrefillReplica chunk-prefills prompts and hands the KV pages off to
  a DecodeReplica (host-staged export_seq/import_seq; prefix-cache
  hits ship only the unshared tail).  Banks handoff_bytes_per_seq,
  fleet-level TTFT p50/p99, lost_requests=0 and zero leaked pages /
  green invariants on BOTH pools.  --fleet adds the elastic
  FleetController under a bursty load: scale_ups/scale_downs bank
  >= 1 on the same contract.  Arm FAULT_SERVE_HANDOFF_DROP /
  FAULT_SERVE_REPLICA_KILL in the environment to chaos a fleet run —
  the report's handoff_drops/failovers/re_prefills count the
  absorbed faults and lost_requests must still bank 0.

  mesh mode (--mesh N, decode-mode option): the same decode replay
  through the tensor-parallel ShardedDecodeProgram over an N-device
  mesh (chip-less: N virtual CPU devices are forced via XLA_FLAGS when
  jax is not yet initialized; exit 2 if the platform came up smaller).
  Reports the usual decode numbers plus the mesh size, so single- vs
  sharded-decode tokens/s rides the same gate.

  tenants mode (--tenants N, decode-mode option): the multi-tenant
  replay through the paged batched-LoRA adapter pool
  (serving/adapters.py) — N registered adapters, each request's tenant
  drawn from a Zipf(1.1) popularity curve, every continuous-batching
  step serving all resident tenants at once via per-row slot gathers.
  Banks adapter_hit_rate, adapter_gather_bytes_per_step, per-tenant
  TTFT percentiles, errored_sequences=0 and zero leaked pages / green
  invariants on the KV AND adapter pools; --adapter-slots under the
  working set (the CI teeth arm) thrashes the pack and fails the gate.

Gating mirrors tools/obsdump.py and tools/lint_programs.py — the shared
CI-gate exit-code contract (README "CI gates"): --baseline BANKED.json
re-checks this run against a banked artifact ({metric: value};
lower_is_better inferred from the metric name); exit 0 clean, 2 on
usage/environment errors (missing baseline file, --gate without
--baseline, unknown model), 3 when --gate finds a regression.

  --chaos arms the FAULT_SERVE_* knobs (resilience/faultinject.py)
  MID-RUN and reports how the serving tier recovered: engine mode turns
  FLAGS_observability on, arms breaker_threshold dispatcher raises
  (plus a slow-step to make latency observable) a third of the way
  through the replay — enough consecutive failures to TRIP the circuit
  breaker, which must leave a flight-recorder JSONL dump behind (the
  run exits 2 if it does not) — and gives a slice of the remaining
  requests unmeetable deadlines; the result gains recovered/poisoned/
  timeout/shed/breaker_rejected counts plus breaker/restart totals and
  flight_dumps.  Decode mode arms a NaN-poisoned sequence and a page
  leak under a check_every=1 integrity watchdog — the result gains
  quarantined / reclaimed_pages / invariants_ok, and pages_leaked must
  still end 0.  Bank {"pages_leaked": 0, "invariants_ok": 1} (decode)
  or {"flight_dumps": 1} (engine) and --gate asserts chaos runs finish
  with zero leaked pages and a black-box artifact.

Every report carries `started_at`/`finished_at` wall-clock timestamps;
with --obs-dir (or an engine chaos run, which picks a temp dir) the
run's observability artifacts (metrics.prom with exemplars, merged
trace.json, flight dumps) are exported there and their paths land in
the report's `artifacts`, so a banked gate result correlates back to
the traces behind it.

Usage:
    python tools/serve_bench.py --model mnist --requests 50 --rate 200
    python tools/serve_bench.py --mode decode --sequences 8 --max-new 16
    python tools/serve_bench.py ... --json out.json --obs-dir obs_run
    python tools/serve_bench.py ... --baseline BANK.json --tol 0.15 --gate
    python tools/serve_bench.py --mode decode --chaos --gate \
        --baseline CHAOS_BANK.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _percentile(vals, q):
    return float(np.percentile(np.asarray(vals), q)) if len(vals) else None


def _build_artifact(model: str, out_dir: str):
    """Build + AOT-export the requested model; returns (predict, feed
    builder(batch_size) -> feed dict)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.inference import (
        load_compiled_inference_model,
        save_compiled_inference_model,
    )

    if model == "mnist":
        from paddle_tpu.models.mnist import lenet5

        spec = lenet5()
        img_name = spec.feed_names[0]
        predict_var = spec.extras["predict"]
        shape = (1, 28, 28)
    elif model == "tiny":
        img = layers.data("image", [1, 8, 8], dtype="float32")
        c = layers.conv2d(img, num_filters=4, filter_size=3, padding=1)
        b = layers.batch_norm(c, act="relu")
        p = layers.pool2d(b, pool_size=8, pool_type="avg")
        predict_var = layers.fc(p, size=3, act="softmax")
        img_name = "image"
        shape = (1, 8, 8)
    else:
        sys.stderr.write(f"unknown --model {model!r} (mnist|tiny)\n")
        raise SystemExit(2)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    save_compiled_inference_model(out_dir, [img_name], [predict_var], exe)
    predict = load_compiled_inference_model(out_dir)

    rng = np.random.RandomState(0)

    def feed(batch: int):
        return {img_name: rng.rand(batch, *shape).astype(np.float32)}

    return predict, feed


def run_engine_bench(args) -> dict:
    from paddle_tpu import flags as pflags
    from paddle_tpu import serving
    from paddle_tpu.resilience import faultinject

    chaos = bool(args.chaos)
    arm_at = max(1, args.requests // 3) if chaos else None
    recovered = poisoned = timeouts = breaker_rejected = 0
    # enough consecutive raises to TRIP the breaker (the flight
    # recorder's dump trigger), not just poison one batch
    breaker_threshold = int(pflags.flag("serving_breaker_threshold"))
    # the arm step setdefault()s FAULT_SERVE_SLOW_STEP_MS so an
    # operator-exported value wins — cleanup must restore it, not pop it
    prior_slow = os.environ.get("FAULT_SERVE_SLOW_STEP_MS")
    try:
        with tempfile.TemporaryDirectory() as d:
            predict, feed = _build_artifact(args.model, d)
            buckets = serving.parse_buckets(args.buckets)
            cfg = serving.EngineConfig(
                buckets=buckets, max_wait_s=args.max_wait_ms / 1e3,
                queue_depth=args.queue_depth,
                # a chaos run must outlive its own induced outage
                breaker_cooldown_s=0.25 if chaos else None)
            engine = serving.Engine.from_artifact(predict, config=cfg,
                                                  name="serve_bench")
            rng = np.random.RandomState(args.seed)
            lo, hi = (int(p) for p in args.batch_range.split(","))
            # pre-generate the workload so generation cost stays off the
            # clock
            reqs = [feed(int(rng.randint(lo, hi + 1)))
                    for _ in range(args.requests)]
            # warmup compiles every bucket once — steady-state numbers,
            # not first-compile spikes (compile time is banked separately)
            if args.warmup:
                # the ENGINE's ladder, not the requested one: a
                # static-batch artifact collapses it, and feed(b) past
                # max_batch would be rejected at submit
                for b in engine.ladder.buckets:
                    engine.infer(feed(b))  # b rows land exactly in bucket b

            gaps = rng.exponential(1.0 / args.rate, size=args.requests)
            t_start = time.perf_counter()
            pending = []
            for i, f in enumerate(reqs):
                if chaos and i == arm_at:
                    # mid-run chaos: breaker_threshold poisoned batches
                    # (tripping the breaker -> flight dump) + sustained
                    # dispatch latency (makes shedding observable)
                    os.environ["FAULT_SERVE_DISPATCH_RAISE"] = str(
                        breaker_threshold)
                    os.environ.setdefault("FAULT_SERVE_SLOW_STEP_MS", "2")
                # closed-loop pacing: sleep to the Poisson schedule, but
                # never ahead of it
                target = t_start + float(gaps[: i + 1].sum())
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                timeout = None
                if chaos and i > arm_at and i % 4 == 3:
                    timeout = 1e-4  # unmeetable: exercises shed/timeout
                try:
                    pending.append(
                        (time.perf_counter(), engine.submit(f, timeout=timeout), i))
                except serving.RequestTimeoutError:
                    # deadline-shed at submit: the engine counts these
                    # itself — reported below as "shed_requests"
                    pass
                except serving.EngineUnhealthyError:
                    # breaker open (chaos): submit fails fast — the
                    # replica-shedding signal a real router acts on
                    breaker_rejected += 1
            lat = []
            rows = 0
            for t0, fut, i in pending:
                try:
                    fut.result(timeout=60)
                except serving.RequestTimeoutError:
                    if not chaos:  # only chaos runs expect casualties —
                        raise      # a clean run must fail loudly
                    timeouts += 1
                    continue
                except Exception:
                    # bucketed dispatches fail as EngineInternalError;
                    # a pass-through (empty-ladder) dispatch delivers
                    # the request's ORIGINAL exception — chaos counts
                    # either as poisoned
                    if not chaos:
                        raise
                    poisoned += 1
                    continue
                recovered += 1
                lat.append(time.perf_counter() - t0)
                rows += reqs[i][predict.feed_names[0]].shape[0]
            elapsed = time.perf_counter() - t_start
            stats = engine.stats()
            engine.close()
    finally:
        if chaos:
            os.environ.pop("FAULT_SERVE_DISPATCH_RAISE", None)
            if prior_slow is None:
                os.environ.pop("FAULT_SERVE_SLOW_STEP_MS", None)
            else:
                os.environ["FAULT_SERVE_SLOW_STEP_MS"] = prior_slow
            faultinject.reset()
    p50, p99 = _percentile(lat, 50), _percentile(lat, 99)
    result = {
        "mode": "engine",
        "model": args.model,
        "requests": args.requests,
        "buckets": list(stats["buckets"]),
        "p50_ms": p50 * 1e3 if p50 is not None else None,
        "p99_ms": p99 * 1e3 if p99 is not None else None,
        "throughput_rps": args.requests / elapsed,
        "throughput_rows_s": rows / elapsed,
        "mean_occupancy": stats["mean_occupancy"],
        "batches": stats["batches"],
        "distinct_shapes": stats["distinct_shapes"],
    }
    if chaos:
        result.update({
            "recovered_requests": recovered,
            "poisoned_requests": poisoned,
            "timeout_requests": timeouts,
            "shed_requests": stats["shed"],
            "breaker_rejected_requests": breaker_rejected,
            "internal_errors": stats["internal_errors"],
            "breaker_trips": stats["breaker_trips"],
            "dispatcher_restarts": stats["dispatcher_restarts"],
        })
    return result


def run_router_bench(args) -> dict:
    """--replicas N: the engine-mode replay through a Router fronting N
    replicas of the same artifact, with a mid-run drain handoff when
    N >= 2.  Zero lost requests and zero post-drain misroutes are the
    bankable contract.  With --chaos one replica is KILLED mid-run via
    FAULT_SERVE_REPLICA_KILL (its dispatcher dies without restart —
    a dead process): its queued requests fail typed and are FAILED
    OVER through the router to the survivors, so lost_requests still
    banks 0 next to the failover count (the drain smoke is skipped —
    the kill is the handoff under test)."""
    from paddle_tpu import serving
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving.distributed import Router

    chaos = bool(args.chaos)
    failovers = 0
    try:
        with tempfile.TemporaryDirectory() as d:
            predict, feed = _build_artifact(args.model, d)
            buckets = serving.parse_buckets(args.buckets)
            engines = [
                serving.Engine.from_artifact(
                    predict,
                    config=serving.EngineConfig(
                        buckets=buckets, max_wait_s=args.max_wait_ms / 1e3,
                        queue_depth=args.queue_depth),
                    name=f"replica{i}")
                for i in range(args.replicas)
            ]
            router = Router(engines)
            if args.warmup:
                for eng in engines:
                    for b in eng.ladder.buckets:
                        eng.infer(feed(b))
            rng = np.random.RandomState(args.seed)
            lo, hi = (int(p) for p in args.batch_range.split(","))
            reqs = [feed(int(rng.randint(lo, hi + 1)))
                    for _ in range(args.requests)]
            gaps = rng.exponential(1.0 / args.rate, size=args.requests)
            # drain-handoff smoke: hand the first replica's traffic off
            # halfway through (needs a survivor).  A chaos run replaces
            # it with the replica KILL (killing one replica AND
            # draining another would leave a 2-replica fleet empty)
            drain_at = (args.requests // 2
                        if args.replicas > 1 and not chaos else None)
            drained = router.replica_names()[0] if drain_at else None
            kill_at = max(1, args.requests // 3) if chaos else None
            victim = router.replica_names()[-1] if chaos else None
            t_start = time.perf_counter()
            pending = []
            for i, f in enumerate(reqs):
                if drain_at is not None and i == drain_at:
                    # claim the replica NOW (timeout=0 polls: routing
                    # stops atomically, the engine drains in the
                    # background while the replay keeps landing on the
                    # survivors)
                    router.drain_replica(drained, timeout=0)
                if kill_at is not None and i == kill_at:
                    # mid-run kill: the victim's dispatcher dies on its
                    # next cycle, queued requests fail typed, health
                    # goes BROKEN and the router skips it
                    os.environ["FAULT_SERVE_REPLICA_KILL"] = victim
                target = t_start + float(gaps[: i + 1].sum())
                now = time.perf_counter()
                if target > now:
                    time.sleep(target - now)
                pending.append((time.perf_counter(), router.submit(f), i))
            lat = []
            rows = 0
            per_replica = {}
            misroutes = 0
            for t0, fut, i in pending:
                try:
                    fut.result(timeout=60)
                except Exception:
                    # the killed replica failed this queued request
                    # typed — fail it over through the router (which
                    # now skips the BROKEN victim); a clean run must
                    # fail loudly instead
                    if not chaos:
                        raise
                    fut = router.submit(reqs[i])
                    fut.result(timeout=60)
                    failovers += 1
                l = time.perf_counter() - t0
                lat.append(l)
                rows += reqs[i][predict.feed_names[0]].shape[0]
                per_replica.setdefault(fut.replica, []).append(l)
                if drain_at is not None and i >= drain_at \
                        and fut.replica == drained:
                    misroutes += 1
            elapsed = time.perf_counter() - t_start
            drain_done = (router.drain_replica(drained, timeout=60.0)
                          if drain_at is not None else None)
            st = router.stats()
            killed = (router.engine(victim).stats()["replica_killed"]
                      if chaos else False)
            router.close()
    finally:
        if chaos:
            os.environ.pop("FAULT_SERVE_REPLICA_KILL", None)
            faultinject.reset()
    result = {
        "mode": "router",
        "model": args.model,
        "replicas": args.replicas,
        "requests": args.requests,
        "p50_ms": _percentile(lat, 50) * 1e3,
        "p99_ms": _percentile(lat, 99) * 1e3,
        "throughput_rps": args.requests / elapsed,
        "throughput_rows_s": rows / elapsed,
        "routed": st["routed"],
        "skipped_unhealthy": st["skipped"],
        "handoffs": st["handoffs"],
        # every submit returned a future and every future resolved —
        # anything else raised above, so this banks as a hard zero
        "lost_requests": args.requests - len(lat),
        "per_replica": {
            name: {
                "requests": len(ls),
                "rps": len(ls) / elapsed,
                "p50_ms": _percentile(ls, 50) * 1e3,
                "p99_ms": _percentile(ls, 99) * 1e3,
            } for name, ls in sorted(per_replica.items())
        },
    }
    if drain_at is not None:
        result.update({
            "drained_replica": drained,
            "drain_completed": int(bool(drain_done)),
            # requests submitted at/after the drain point must not have
            # landed on the drained replica
            "post_drain_misroutes": misroutes,
        })
    if chaos:
        result.update({
            "killed_replica": victim,
            "replica_kills": int(bool(killed)),
            "failovers": failovers,
        })
    return result


_KV_DTYPES = {"fp32": "float32", "bf16": "bfloat16", "int8": "int8"}


_SAMPLING_SCENARIOS = {
    # named load scenarios for the per-request sampling contract; the
    # non-greedy ones exercise the jitted sampling epilogue
    "greedy": None,
    "temp": {"temperature": 0.8},
    "topk": {"temperature": 0.8, "top_k": 20},
    "topp": {"temperature": 0.8, "top_p": 0.9},
}


def _decode_requests(args, cfg, rng, sampling=None) -> list:
    """The decode-mode traffic shape, shared by --mode decode and the
    fleet modes so their banked numbers stay comparable.
    --prefix-share P of requests open with ONE common system-prompt
    prefix (~3/4 of the max prompt length) — the shared-prefix traffic
    the prefix cache exists for; the first such request warms the
    cache, the rest should hit.  The remainder draw uniform random
    prompts, or, under --speculate, a short motif tiled to the drawn
    length — the templated/self-similar traffic prompt-lookup drafting
    exists for."""
    from paddle_tpu import serving

    plo, phi = (int(p) for p in args.prompt_range.split(","))
    phi = min(phi, args.max_len - args.max_new)
    if args.context_len:
        # the long-context replay: every request carries exactly
        # --context-len resident tokens into decode
        plo = phi = min(args.context_len, args.max_len - args.max_new)
    win = int(args.window) or None
    snk = int(args.sinks) if win else 0
    share = float(args.prefix_share)
    sys_prompt = rng.randint(
        1, cfg.vocab_size,
        size=max(1, int(phi * 0.75))).tolist() if share > 0 else []
    motif = rng.randint(
        1, cfg.vocab_size,
        size=max(2, min(6, plo))).tolist() if args.speculate else []
    reqs = []
    for _ in range(args.sequences):
        if share > 0 and rng.rand() < share:
            tail = int(rng.randint(1, max(2, phi - len(sys_prompt) + 1)))
            prompt = sys_prompt + rng.randint(
                1, cfg.vocab_size, size=tail).tolist()
        else:
            plen = int(rng.randint(plo, max(plo + 1, phi + 1)))
            if motif:
                reps = -(-plen // len(motif))
                prompt = (motif * reps)[:plen]
            else:
                prompt = rng.randint(
                    1, cfg.vocab_size, size=plen).tolist()
        reqs.append(serving.DecodeRequest(
            prompt=prompt, max_new_tokens=args.max_new,
            sampling=sampling, window=win, sinks=snk))
    return reqs


def run_decode_bench(args) -> dict:
    from paddle_tpu import serving

    kv_dtype = _KV_DTYPES[args.kv_dtype]
    cfg = serving.DecodeConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_head=args.n_head,
        n_layer=args.n_layer, d_inner=args.d_model * 2,
        max_length=args.max_len,
        n_kv_head=args.kv_heads or None)
    params = serving.init_decode_params(cfg, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    program = None
    if args.mesh > 1:
        from paddle_tpu.serving.distributed import ShardedDecodeProgram

        program = ShardedDecodeProgram(
            params, cfg, n_shards=args.mesh, paged_impl=args.paged_impl)
        pool = program.make_pool(num_pages=args.pages,
                                 page_size=args.page_size,
                                 dtype=kv_dtype)
    else:
        pool = serving.KVCachePool(
            num_pages=args.pages, page_size=args.page_size,
            num_layers=cfg.n_layer, num_heads=cfg.n_head,
            head_dim=cfg.head_dim, num_kv_heads=cfg.num_kv_heads,
            dtype=kv_dtype)
    share = float(args.prefix_share)
    spec_kw = _SAMPLING_SCENARIOS[args.sampling]
    sampling = (serving.SamplingParams(seed=args.seed, **spec_kw)
                if spec_kw is not None else None)
    reqs = _decode_requests(args, cfg, rng, sampling=sampling)
    chaos = bool(args.chaos)
    from paddle_tpu.kernels.paged_attention import fallback_count

    fallbacks_before = fallback_count()

    def _fresh_pool():
        # the A/B and replay arms must ride the SAME pool kind as the
        # timed arm — mesh runs compare mesh-vs-mesh, never
        # mesh-vs-single-device
        if program is not None:
            return program.make_pool(num_pages=args.pages,
                                     page_size=args.page_size,
                                     dtype=kv_dtype)
        return serving.KVCachePool(
            num_pages=args.pages, page_size=args.page_size,
            num_layers=cfg.n_layer, num_heads=cfg.n_head,
            head_dim=cfg.head_dim, num_kv_heads=cfg.num_kv_heads,
            dtype=kv_dtype)

    def _warm_replay(speculate):
        # the engine mode warms every bucket before timing; the
        # speculate A/B needs the same discipline — one untimed replay
        # per arm compiles each arm's step shapes so the timed numbers
        # compare steady-state decode, not XLA compile queues
        wpool = _fresh_pool()
        wcache = (serving.PrefixCache(wpool)
                  if (share > 0 or args.prefix_cache) else None)
        serving.ContinuousBatchingLoop(
            params, cfg, wpool, max_batch=args.max_batch,
            paged_impl=args.paged_impl, prefill=args.prefill,
            program=program, prefix_cache=wcache,
            prefill_chunk=args.prefill_chunk,
            prefill_flops=args.prefill_flops or None,
            table_block=args.table_block or None,
            speculate=speculate).run(reqs)
        if wcache is not None:
            wcache.clear()

    if args.speculate and args.warmup:
        _warm_replay(args.speculate)
    cache = (serving.PrefixCache(pool)
             if (share > 0 or args.prefix_cache) else None)
    loop = serving.ContinuousBatchingLoop(
        params, cfg, pool, max_batch=args.max_batch,
        paged_impl=args.paged_impl, prefill=args.prefill,
        check_every=1 if chaos else 0, program=program,
        prefix_cache=cache, prefill_chunk=args.prefill_chunk,
        prefill_flops=args.prefill_flops or None,
        table_block=args.table_block or None,
        speculate=args.speculate)
    if chaos:
        from paddle_tpu.resilience import faultinject  # noqa: F401

        # poison one sequence's logits on the first decode step and leak
        # pages on the next append — the quarantine + integrity watchdog
        # must absorb both with zero pages leaked at the end
        os.environ["FAULT_SERVE_NAN_SEQ"] = "1@1"
        os.environ["FAULT_SERVE_LEAK_PAGES"] = "2"
    t0 = time.perf_counter()
    try:
        results = loop.run(reqs)
    finally:
        if chaos:
            from paddle_tpu.resilience import faultinject

            os.environ.pop("FAULT_SERVE_NAN_SEQ", None)
            os.environ.pop("FAULT_SERVE_LEAK_PAGES", None)
            faultinject.reset()
    elapsed = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in results)
    ttfts = [r.ttft_s for r in results if r.ttft_s is not None]
    d0 = None
    if args.speculate:
        # the SAME replay at d=0 in the same invocation: the speedup
        # claim gates against its own contemporaneous baseline, not a
        # banked number from a different machine/day
        if args.warmup:
            _warm_replay(0)
        pool_d0 = _fresh_pool()
        cache_d0 = (serving.PrefixCache(pool_d0)
                    if (share > 0 or args.prefix_cache) else None)
        loop_d0 = serving.ContinuousBatchingLoop(
            params, cfg, pool_d0, max_batch=args.max_batch,
            paged_impl=args.paged_impl, prefill=args.prefill,
            program=program, prefix_cache=cache_d0,
            prefill_chunk=args.prefill_chunk,
            prefill_flops=args.prefill_flops or None,
            table_block=args.table_block or None,
            speculate=0)
        t0_d0 = time.perf_counter()
        results_d0 = loop_d0.run(reqs)
        elapsed_d0 = time.perf_counter() - t0_d0
        tokens_d0 = sum(len(r.tokens) for r in results_d0)
        if args.sampling == "greedy":
            # greedy speculation is token-identical to d=0 — anything
            # else is a correctness bug, not a perf result
            for a, b in zip(results, results_d0):
                if a.tokens != b.tokens:
                    sys.stderr.write(
                        "serve_bench: speculative tokens diverged from "
                        "the d=0 run — refusing to report throughput "
                        "for wrong output\n")
                    raise SystemExit(2)
        else:
            # sampled speculation is distribution-exact, not token-
            # identical to d=0 (drafted rows consume salted replay
            # keys); the checkable contract is DETERMINISM — the same
            # seeded replay must reproduce the stream bit-identically
            pool_rp = _fresh_pool()
            cache_rp = (serving.PrefixCache(pool_rp)
                        if (share > 0 or args.prefix_cache) else None)
            loop_rp = serving.ContinuousBatchingLoop(
                params, cfg, pool_rp, max_batch=args.max_batch,
                paged_impl=args.paged_impl, prefill=args.prefill,
                program=program, prefix_cache=cache_rp,
                prefill_chunk=args.prefill_chunk,
                prefill_flops=args.prefill_flops or None,
                table_block=args.table_block or None,
                speculate=args.speculate)
            results_rp = loop_rp.run(reqs)
            for a, b in zip(results, results_rp):
                if a.tokens != b.tokens:
                    sys.stderr.write(
                        "serve_bench: sampled speculative replay is "
                        "non-deterministic — refusing to report "
                        "throughput for an unreproducible stream\n")
                    raise SystemExit(2)
            if cache_rp is not None:
                cache_rp.clear()
        d0 = {"tokens": tokens_d0, "elapsed": elapsed_d0,
              "steps": loop_d0.steps}
        if cache_d0 is not None:
            cache_d0.clear()
    if cache is not None:
        # release the cache's page holds BEFORE the leak audit: pinned
        # prefix pages are a feature, pages nobody owns are a leak
        cache.clear()
    st = pool.stats()
    result = {
        "mode": "decode",
        "mesh": args.mesh,
        "paged_impl": loop.paged_impl,  # the impl that actually ran
        "prefill": loop.prefill,
        "prefill_chunk": args.prefill_chunk,
        # the KV capacity knobs (ISSUE 12) and their banked win:
        # bytes ONE token's K/V occupies across all layers — H_kv
        # heads at the pool dtype plus the amortized per-page scale
        # overhead, i.e. bytes_per_page / page_size
        "kv_heads": cfg.num_kv_heads,
        "kv_dtype": args.kv_dtype,
        "kv_bytes_per_token": pool.bytes_per_page() / pool.page_size,
        "sequences": args.sequences,
        "steps": loop.steps,
        "prefill_steps": loop.prefill_steps,
        "decode_steps": loop.decode_steps,
        "tokens": tokens,
        "tokens_per_s": tokens / elapsed,
        "ttft_p50_ms": _percentile(ttfts, 50) * 1e3,
        "ttft_p99_ms": _percentile(ttfts, 99) * 1e3,
        "mean_occupancy": loop.mean_occupancy(),
        "pages_high_water": st["used_pages_high_water"],
        "page_allocs": st["page_allocs"],
        "pages_leaked": st["used_pages"],  # must be 0 after a full run
        # resolve_paged_impl fallbacks during the run: bank 0 so a pool
        # geometry drifting out of the Mosaic envelope fails the gate
        # instead of silently running the reference gather
        "paged_fallbacks": fallback_count() - fallbacks_before,
        # chunked-prefill contract: no engine step processed more
        # prefill tokens than the cap (bank the cap, gate holds it)
        "prefill_tokens": loop.prefill_tokens,
        "max_prefill_tokens_step": loop.max_prefill_tokens_step,
        # the sampling scenario the replay ran (greedy keeps the
        # oracle-identical contract; temp/topk/topp exercise the
        # jitted epilogue)
        "sampling": args.sampling,
        "tokens_per_step": tokens / loop.steps if loop.steps else 0.0,
    }
    if args.context_len or args.window or args.prefill_flops \
            or args.table_block:
        from paddle_tpu.kernels.paged_attention import (
            attention_bytes_per_step)

        # the long-context contract (ISSUE 20): decode_bytes_per_step
        # is the analytic attention stream of the WIDEST page-table
        # walk any decode step paid — post-eviction, so a windowed
        # 128k replay banks near its 8k number while the no-window
        # teeth arm walks the full context and trips the (lower-is-
        # better) gate; decode_step_p99_during_prefill_ms is the
        # per-step latency hit in-flight sequences took while chunked
        # prefill was pending, the number --prefill-flops bounds
        result.update({
            "context_len": args.context_len,
            "window": args.window,
            "sinks": args.sinks,
            "prefill_flops": args.prefill_flops,
            "table_block": args.table_block,
            "pages_evicted": loop.pages_evicted,
            "max_decode_table_pages": loop.max_decode_table_pages,
            "decode_bytes_per_step": float(attention_bytes_per_step(
                loop.paged_impl, args.max_batch,
                loop.max_decode_table_pages, pool.page_size,
                cfg.n_head, cfg.head_dim, num_layers=cfg.n_layer,
                num_kv_heads=cfg.num_kv_heads, dtype=kv_dtype)),
            "decode_step_p99_during_prefill_ms":
                loop.decode_step_p99_during_prefill_s() * 1e3,
        })
    if args.speculate:
        result.update({
            "speculate": args.speculate,
            "spec_steps": loop.spec_steps,
            "drafted_tokens": loop.drafted_tokens,
            "accepted_tokens": loop.accepted_tokens,
            "rolled_back_tokens": loop.rolled_back_tokens,
            "acceptance_rate": loop.acceptance_rate(),
            # the contemporaneous d=0 arm and the headline ratio —
            # bank spec_speedup >= 1 and --gate holds the win
            "steps_d0": d0["steps"],
            "tokens_per_s_d0": d0["tokens"] / d0["elapsed"],
            "spec_speedup": (tokens / elapsed)
            / (d0["tokens"] / d0["elapsed"]),
        })
    if cache is not None:
        result.update({
            "prefix_share": share,
            "prefix_hit_rate": loop.prefix_hits / float(args.sequences),
            "cached_prefill_tokens": loop.cached_prefill_tokens,
            "prefix_evictions": cache.stats()["evictions"],
            "cow_copies": st["cow_copies"],
        })
    if chaos:
        result.update({
            "quarantined": loop.quarantined,
            "reclaimed_pages": loop.reclaimed_pages,
            "invariants_ok": int(pool.check_invariants()["ok"]),
        })
    return result


def run_multiturn_bench(args) -> dict:
    """--turns N (decode mode): the multi-turn chat replay the tiered
    KV cache (ISSUE 18) exists for.  --sequences sessions each hold a
    conversation of N turns; between turns every session idles for
    --think-time-s and is parked to host RAM (``spill_idle`` — the
    proactive policy a deployment runs on think time), so turn k+1
    must resume from the host tier instead of re-prefilling its whole
    transcript.

    Banked contract (0/2/3 gate): resume_hit_rate == resumed turns /
    resumable turns (1.0 when the tier does its job), re_prefills == 0
    (no resume fell back to recompute), host_transfer_bytes (the
    deterministic spill+resume traffic), first-turn vs resumed-turn
    TTFT percentiles, and retention_ratio — conversation tokens still
    resumable across all sessions over the HBM pool's token capacity;
    > 1.0 is the headline: the tier retains more concurrent chat state
    than HBM alone could hold.  --no-tier replays the same workload
    with no session manager (every turn re-prefills from scratch) —
    the CI teeth arm gates that against the tiered baseline and must
    fail."""
    from paddle_tpu import serving

    kv_dtype = _KV_DTYPES[args.kv_dtype]
    cfg = serving.DecodeConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_head=args.n_head,
        n_layer=args.n_layer, d_inner=args.d_model * 2,
        max_length=args.max_len,
        n_kv_head=args.kv_heads or None)
    params = serving.init_decode_params(cfg, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    pool = serving.KVCachePool(
        num_pages=args.pages, page_size=args.page_size,
        num_layers=cfg.n_layer, num_heads=cfg.n_head,
        head_dim=cfg.head_dim, num_kv_heads=cfg.num_kv_heads,
        dtype=kv_dtype)
    cache = serving.PrefixCache(pool) if args.prefix_cache else None
    mgr = None
    if not args.no_tier:
        mgr = serving.TieredSessionManager(
            pool, prefix_cache=cache,
            host_bytes=int(args.host_mb) << 20)
    loop = serving.ContinuousBatchingLoop(
        params, cfg, pool, max_batch=args.max_batch,
        paged_impl=args.paged_impl, prefill=args.prefill,
        prefix_cache=cache, prefill_chunk=args.prefill_chunk,
        session_manager=mgr)
    sessions = ([mgr.open_session() for _ in range(args.sequences)]
                if mgr is not None else [None] * args.sequences)
    plo, phi = (int(p) for p in args.prompt_range.split(","))
    transcripts = [
        rng.randint(1, cfg.vocab_size,
                    size=int(rng.randint(plo, max(plo + 1,
                                                  phi + 1)))).tolist()
        for _ in range(args.sequences)]
    followup = 3  # tokens the "user" adds each turn
    ttft_first, ttft_resumed = [], []
    errored = 0
    tokens = 0
    t0 = time.perf_counter()
    for turn in range(args.turns):
        reqs = [serving.DecodeRequest(prompt=list(t),
                                      max_new_tokens=args.max_new,
                                      session=s)
                for t, s in zip(transcripts, sessions)]
        for i, r in enumerate(loop.run(reqs)):
            if r.error is not None:
                errored += 1
                continue
            tokens += len(r.tokens)
            if r.ttft_s is not None:
                (ttft_first if turn == 0 else
                 ttft_resumed).append(r.ttft_s)
            transcripts[i] = (transcripts[i] + r.tokens + rng.randint(
                1, cfg.vocab_size, size=followup).tolist())
        if turn < args.turns - 1:
            # think time: the conversation goes quiet, the tier parks
            # every idle session — turn k+1 resumes from host RAM
            if args.think_time_s > 0:
                time.sleep(args.think_time_s)
            if mgr is not None:
                mgr.spill_idle(older_than_s=0.0, wait=True)
    elapsed = time.perf_counter() - t0
    resumable = args.sequences * (args.turns - 1)
    if mgr is not None:
        mst = mgr.stats()
        retained = sum(s.tokens_retained() for s in sessions)
        tier = mst["tier"]
        host_transfer = (tier["bytes_parked_total"]
                         + tier["bytes_fetched_total"])
        invariants = mgr.check_invariants()
        mgr.close()
    else:
        mst = {"resumes": 0, "resumed_host": 0, "re_prefills": 0,
               "spills": 0, "evictions": 0}
        retained = 0
        host_transfer = 0
        invariants = pool.check_invariants()
        invariants = {"ok": invariants["ok"]}
    if cache is not None:
        cache.clear()
    st = pool.stats()
    return {
        "mode": "multiturn",
        "sequences": args.sequences,
        "turns": args.turns,
        "think_time_s": args.think_time_s,
        "tiered": int(mgr is not None),
        "kv_heads": cfg.num_kv_heads,
        "kv_dtype": args.kv_dtype,
        "tokens": tokens,
        "tokens_per_s": tokens / elapsed,
        "errored_sequences": errored,
        # the headline: every resumable turn resumed (none fell back
        # to a full-transcript re-prefill)
        "resume_hit_rate": (mst["resumes"] / resumable
                            if resumable else 0.0),
        "resumed_host": mst["resumed_host"],
        "re_prefills": mst["re_prefills"],
        "spills": mst["spills"],
        "tier_evictions": mst["evictions"],
        "host_transfer_bytes": host_transfer,
        # conversation state still resumable at the end vs what HBM
        # alone could hold — > 1.0 is the capacity win
        "retained_tokens": retained,
        "retention_ratio": retained / float(args.pages
                                            * args.page_size),
        "ttft_turn1_p50_ms": _percentile(ttft_first, 50) * 1e3,
        "ttft_turn1_p99_ms": _percentile(ttft_first, 99) * 1e3,
        "ttft_resumed_p50_ms": _percentile(ttft_resumed, 50) * 1e3,
        "ttft_resumed_p99_ms": _percentile(ttft_resumed, 99) * 1e3,
        "pages_leaked": st["used_pages"],
        "invariants_ok": int(invariants["ok"]),
    }


def run_tenants_bench(args) -> dict:
    """--tenants N (decode mode): the multi-tenant replay the paged
    adapter pool (ISSUE 19) exists for.  N LoRA adapters are registered
    up front (``tenant1`` .. ``tenantN``) and every request draws its
    tenant from a Zipf(s=1.1) popularity curve — the head tenants stay
    hot in the --adapter-slots device pack, the tail faults in from the
    host tier on demand, and one continuous-batching step serves every
    resident tenant at once (each row gathers its own slot's factors).

    Banked contract (0/2/3 gate): adapter_hit_rate == warm-slot
    acquires / all acquires (high when the working set fits the pack;
    a one-slot pool under a 16-tenant Zipf THRASHES — the CI teeth
    arm), adapter_gather_bytes_per_step (the analytic per-step adapter
    traffic — gathers, not dense weight copies), errored_sequences ==
    0 (no admission rejects on the happy path), zero leaked pages and
    green invariants on BOTH pools, plus a per-tenant TTFT p50/p99
    breakdown (report-only: the gate walks top-level scalars)."""
    from paddle_tpu import serving

    kv_dtype = _KV_DTYPES[args.kv_dtype]
    cfg = serving.DecodeConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_head=args.n_head,
        n_layer=args.n_layer, d_inner=args.d_model * 2,
        max_length=args.max_len,
        n_kv_head=args.kv_heads or None)
    params = serving.init_decode_params(cfg, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    tenants = [f"tenant{k}" for k in range(1, args.tenants + 1)]
    weights = {t: serving.make_adapter(cfg, rank=args.adapter_rank,
                                       seed=args.seed + k)
               for k, t in enumerate(tenants, start=1)}

    def _fresh_adapters():
        ap = serving.AdapterPool(cfg, slots=args.adapter_slots,
                                 max_rank=args.adapter_rank)
        for t in tenants:
            ap.register_adapter(t, weights[t])
        return ap

    # Zipf(s=1.1) tenant popularity: rank-k tenant drawn w.p. ~ 1/k^s
    zipf = np.array([1.0 / k ** 1.1
                     for k in range(1, args.tenants + 1)])
    zipf /= zipf.sum()
    draws = rng.choice(args.tenants, size=args.sequences, p=zipf)
    plo, phi = (int(p) for p in args.prompt_range.split(","))
    phi = min(phi, args.max_len - args.max_new)
    reqs = [serving.DecodeRequest(
        prompt=rng.randint(
            1, cfg.vocab_size,
            size=int(rng.randint(plo, max(plo + 1, phi + 1)))).tolist(),
        max_new_tokens=args.max_new,
        adapter_id=tenants[d])
        for d in draws]

    def _fresh_pool():
        return serving.KVCachePool(
            num_pages=args.pages, page_size=args.page_size,
            num_layers=cfg.n_layer, num_heads=cfg.n_head,
            head_dim=cfg.head_dim, num_kv_heads=cfg.num_kv_heads,
            dtype=kv_dtype)

    if args.warmup:
        # untimed replay on throwaway pools: compiles the adapter-armed
        # step shapes so the timed numbers compare steady-state decode
        serving.ContinuousBatchingLoop(
            params, cfg, _fresh_pool(), max_batch=args.max_batch,
            paged_impl=args.paged_impl, prefill=args.prefill,
            prefill_chunk=args.prefill_chunk,
            adapter_pool=_fresh_adapters()).run(reqs)
    pool = _fresh_pool()
    adapters = _fresh_adapters()
    cache = serving.PrefixCache(pool) if args.prefix_cache else None
    loop = serving.ContinuousBatchingLoop(
        params, cfg, pool, max_batch=args.max_batch,
        paged_impl=args.paged_impl, prefill=args.prefill,
        prefix_cache=cache, prefill_chunk=args.prefill_chunk,
        adapter_pool=adapters)
    t0 = time.perf_counter()
    results = loop.run(reqs)
    elapsed = time.perf_counter() - t0
    errored = sum(1 for r in results if r.error is not None)
    tokens = sum(len(r.tokens) for r in results)
    per_tenant = {}
    for d, r in zip(draws, results):
        if r.error is None and r.ttft_s is not None:
            per_tenant.setdefault(tenants[d], []).append(r.ttft_s)
    if cache is not None:
        cache.clear()
    ast = adapters.stats()
    st = pool.stats()
    kv_ok = pool.check_invariants()["ok"]
    ad_ok = adapters.check_invariants()["ok"]
    return {
        "mode": "tenants",
        "tenants": args.tenants,
        "adapter_slots": args.adapter_slots,
        "adapter_rank": args.adapter_rank,
        "sequences": args.sequences,
        "kv_heads": cfg.num_kv_heads,
        "kv_dtype": args.kv_dtype,
        "tokens": tokens,
        "tokens_per_s": tokens / elapsed,
        "steps": loop.steps,
        "errored_sequences": errored,
        "adapter_rejects": loop.adapter_rejects,
        # the headline: acquires served from a warm device slot vs
        # faulted in from the host tier — a working set that fits
        # --adapter-slots stays ~1, a thrashing pool collapses
        "adapter_hit_rate": ast["hit_rate"],
        "adapter_fault_ins": ast["fault_ins"],
        "adapter_spills": ast["spills"],
        "adapter_device_bytes": ast["device_bytes"],
        "adapter_utilization": ast["utilization"],
        # analytic per-step adapter traffic: slot GATHERS, priced like
        # the banked lora_decode zoo entry — not dense weight copies
        "adapter_gather_bytes_per_step":
            loop.adapter_gather_bytes / max(1, loop.steps),
        "adapter_in_flight": ast["in_flight"],  # must end 0
        "per_tenant": {
            t: {
                "requests": len(ls),
                "ttft_p50_ms": _percentile(ls, 50) * 1e3,
                "ttft_p99_ms": _percentile(ls, 99) * 1e3,
            } for t, ls in sorted(per_tenant.items())
        },
        "pages_leaked": st["used_pages"],
        "invariants_ok": int(kv_ok and ad_ok),
    }


def run_fleet_bench(args, elastic: bool) -> dict:
    """--disagg / --fleet (decode-mode options): the decode replay
    through a disaggregated prefill/decode Fleet (serving/fleet).

    --disagg runs a fixed 1-prefill + 1-decode fleet under the Poisson
    replay and banks the handoff contract: handoff_bytes_per_seq, TTFT
    percentiles (fleet-level submit→first-token), lost_requests=0, and
    zero leaked pages / green invariants on BOTH pools.  --fleet adds
    the elastic controller under a BURSTY load (the whole request set
    submitted at once, then a quiet tail): sustained queue growth must
    scale a class up and the idle tail must scale it back down —
    scale_ups/scale_downs bank >= 1 on the same 0/2/3 gate.

    --procs N upgrades --fleet to real OS processes: N prefill + N
    decode replica processes behind ProcSpawner, every handoff and
    result crossing the framed socket plane, the directory served over
    a real RemoteMaster.  The banked contract hardens accordingly —
    lost_requests=0 and clean audits must now survive
    FAULT_SERVE_PROC_KILL (a SIGKILLed pid, not a cooperative thread
    death), and respawns / handoff_drops_recovered / failover_p99_ms
    join the gate."""
    from paddle_tpu import serving
    from paddle_tpu.serving.fleet import (
        AutoscalePolicy,
        DecodeReplica,
        Fleet,
        FleetController,
        PrefillReplica,
        ProcSpawner,
    )

    kv_dtype = _KV_DTYPES[args.kv_dtype]
    cfg = serving.DecodeConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_head=args.n_head,
        n_layer=args.n_layer, d_inner=args.d_model * 2,
        max_length=args.max_len,
        n_kv_head=args.kv_heads or None)
    params = serving.init_decode_params(cfg, seed=args.seed)
    rng = np.random.RandomState(args.seed)
    share = float(args.prefix_share)
    reqs = _decode_requests(args, cfg, rng)

    def spawn_prefill(name):
        return PrefillReplica(
            name, params, cfg, num_pages=args.pages,
            page_size=args.page_size, dtype=kv_dtype,
            max_batch=args.max_batch,
            prefill_chunk=args.prefill_chunk or None)

    def spawn_decode(name):
        return DecodeReplica(
            name, params, cfg, num_pages=args.pages,
            page_size=args.page_size, dtype=kv_dtype,
            max_batch=args.max_batch, paged_impl=args.paged_impl)

    spawner = master_srv = None
    procs = int(getattr(args, "procs", 0) or 0)
    if procs:
        from paddle_tpu.elastic.master import InMemStore, MasterService
        from paddle_tpu.elastic.rpc import RemoteMaster, serve_master
        from paddle_tpu.serving.distributed import ReplicaDirectory

        master_srv = serve_master(MasterService(InMemStore()))
        directory = ReplicaDirectory(
            RemoteMaster(master_srv.endpoint), max_silence_s=2.0)
        spawner = ProcSpawner(
            params, cfg,
            prefill_kwargs=dict(
                num_pages=args.pages, page_size=args.page_size,
                dtype=kv_dtype, max_batch=args.max_batch,
                prefill_chunk=args.prefill_chunk or None),
            decode_kwargs=dict(
                num_pages=args.pages, page_size=args.page_size,
                dtype=kv_dtype, max_batch=args.max_batch,
                paged_impl=args.paged_impl),
            master_endpoint=master_srv.endpoint)
        fleet = Fleet(spawner.prefill, spawner.decode,
                      n_prefill=procs, n_decode=procs,
                      directory=directory,
                      max_retries=args.fleet_retries)
    else:
        fleet = Fleet(spawn_prefill, spawn_decode,
                      max_retries=args.fleet_retries)
    controller = None
    if elastic:
        n_min = {r: max(1, procs) for r in ("prefill", "decode")}
        n_max = {r: max(3, procs + 1) for r in ("prefill", "decode")}
        controller = FleetController(
            fleet,
            policy=AutoscalePolicy(queue_high=2, sustain=2,
                                   idle_sustain=2, cooldown=0),
            min_replicas=n_min if procs else None,
            max_replicas=n_max)
    t_start = time.perf_counter()
    futs = []
    if elastic:
        # bursty load: everything lands at once — the queue-growth
        # signal the autoscaler scales up on — then a quiet tail
        for r in reqs:
            futs.append(fleet.submit(r))
        controller.step()
        controller.step()  # sustain=2: the second pressured step acts
    else:
        gaps = rng.exponential(1.0 / args.rate, size=len(reqs))
        for i, r in enumerate(reqs):
            target = t_start + float(gaps[: i + 1].sum())
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            futs.append(fleet.submit(r))
    results, hard_failures = [], 0
    for f in futs:
        try:
            results.append(f.result(timeout=180 if procs else 120))
        except Exception:  # noqa: BLE001 — a typed fleet failure is a
            hard_failures += 1  # banked metric, not a bench crash
    elapsed = time.perf_counter() - t_start
    if elastic:
        # the idle tail: queues are empty, the controller scales back
        # down through the zero-loss drain — and, in process mode,
        # quarantines any SIGKILL casualty and respawns below min
        for _ in range(controller.policy.idle_sustain + 1):
            controller.step()
            if procs:
                time.sleep(0.3)
    errored = hard_failures + sum(
        1 for r in results if r.error is not None)
    tokens = sum(len(r.tokens) for r in results)
    st = fleet.stats()
    audit = fleet.audit()
    ttfts = list(fleet.ttfts)
    result = {
        "mode": "fleet" if elastic else "disagg",
        "sequences": args.sequences,
        "prefill_replicas": st["prefill_replicas"],
        "decode_replicas": st["decode_replicas"],
        "kv_heads": cfg.num_kv_heads,
        "kv_dtype": args.kv_dtype,
        "tokens": tokens,
        "tokens_per_s": tokens / elapsed,
        "ttft_p50_ms": _percentile(ttfts, 50) * 1e3,
        "ttft_p99_ms": _percentile(ttfts, 99) * 1e3,
        "handoffs": st["handoffs"],
        "handoff_bytes_per_seq": (st["handoff_bytes"] / st["handoffs"]
                                  if st["handoffs"] else 0.0),
        "skipped_tokens": st["skipped_tokens"],
        "handoff_drops": st["handoff_drops"],
        "failovers": st["failovers"],
        "re_prefills": st["re_prefills"],
        "errored_sequences": errored,
        # every submit's future resolved — the bankable hard zero
        "lost_requests": st["lost_requests"],
        "failed_requests": st["failed"],
        "pages_leaked": audit["pages_leaked"],
        "invariants_ok": audit["invariants_ok"],
    }
    if share > 0:
        result["prefix_share"] = share
    if elastic:
        result.update({
            "scale_ups": st["scale_ups"],
            "scale_downs": st["scale_downs"],
            "controller_steps": controller.steps,
        })
    if procs:
        fl = list(fleet.failover_latencies)
        result.update({
            "procs": procs,
            "respawns": st["respawns"],
            "handoff_drops_recovered": st["handoff_drops_recovered"],
            "failover_p99_ms": (_percentile(fl, 99) * 1e3
                                if fl else 0.0),
        })
    fleet.close()
    if spawner is not None:
        spawner.close()
    if master_srv is not None:
        master_srv.shutdown()
    return result


# metrics where bigger is better; everything else (latencies, leak
# counters) gates as lower-is-better.  flight_dumps is higher-is-better
# so banking {"flight_dumps": 1} asserts the chaos breaker trip left a
# black-box artifact behind
_HIGHER_IS_BETTER = ("throughput", "tokens_per_s", "occupancy",
                     "recovered", "invariants_ok", "flight_dumps",
                     "drain_completed", "prefix_hit_rate",
                     "cached_prefill_tokens", "acceptance_rate",
                     "tokens_per_step", "spec_speedup",
                     "accepted_tokens", "scale_ups", "scale_downs",
                     "handoffs", "replica_kills", "respawns",
                     "skipped_tokens", "resume_hit_rate",
                     "retained_tokens", "retention_ratio",
                     "resumed_host", "adapter_hit_rate",
                     "adapter_utilization")


def gate(result: dict, baseline_path: str, tol: float):
    with open(baseline_path) as f:
        baseline = json.load(f)
    verdicts = []
    for metric, want in baseline.items():
        have = result.get(metric)
        if not isinstance(want, (int, float)) or have is None:
            continue
        higher_better = any(k in metric for k in _HIGHER_IS_BETTER)
        if want == 0:
            ok = have <= 0 if not higher_better else have >= 0
            delta_pct = 0.0 if have == want else float("inf")
        else:
            delta = (have - want) / abs(want)
            delta_pct = delta * 100.0
            ok = delta >= -tol if higher_better else delta <= tol
        verdicts.append({
            "metric": metric, "current": have, "baseline": want,
            "delta_pct": delta_pct, "tolerance_pct": tol * 100.0,
            "verdict": "pass" if ok else "fail",
        })
    return verdicts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("engine", "decode"), default="engine")
    ap.add_argument("--model", default="mnist",
                    help="engine mode: mnist|tiny (default mnist)")
    ap.add_argument("--requests", type=int, default=50)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--batch-range", default="1,4",
                    help="engine mode: per-request rows drawn uniformly "
                         "from lo,hi")
    ap.add_argument("--buckets", default=None,
                    help="bucket ladder (default FLAGS_serving_buckets)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine mode: front N replica engines with a "
                         "distributed.Router (N >= 2 adds the drain-"
                         "handoff smoke: one replica drained mid-run, "
                         "post_drain_misroutes and lost_requests must "
                         "bank 0)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--queue-depth", type=int, default=1024)
    ap.add_argument("--no-warmup", dest="warmup", action="store_false")
    # decode mode
    ap.add_argument("--sequences", type=int, default=8)
    ap.add_argument("--prompt-range", default="2,16",
                    help="decode mode: prompt lengths drawn uniformly "
                         "from lo,hi")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--mesh", type=int, default=1,
                    help="decode mode: run the tensor-parallel "
                         "ShardedDecodeProgram over an N-device mesh "
                         "(chip-less via virtual CPU devices)")
    ap.add_argument("--paged-impl", default=None,
                    choices=("reference", "pallas", "interpret"),
                    help="decode mode: paged-attention impl (default: "
                         "FLAGS_serving_paged_impl, i.e. auto-select)")
    ap.add_argument("--prefill", default="batched",
                    choices=("batched", "token"),
                    help="decode mode: whole-prompt vs token-by-token "
                         "prefill")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="decode mode: fraction of requests opening "
                         "with one common system-prompt prefix; > 0 "
                         "enables the prefix cache and banks "
                         "prefix_hit_rate / cached_prefill_tokens")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="decode mode: enable the prefix cache even "
                         "with --prefix-share 0")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="decode mode: cap prefill tokens per engine "
                         "step (FLAGS_serving_prefill_chunk; 0 = "
                         "uncapped); max_prefill_tokens_step in the "
                         "report counter-asserts it")
    ap.add_argument("--context-len", type=int, default=0,
                    help="decode mode: serve FIXED-length prompts of N "
                         "tokens (overrides --prompt-range) — the "
                         "long-context replay (ISSUE 20); needs "
                         "--max-len >= N + --max-new and a --pages "
                         "pool that holds them")
    ap.add_argument("--window", type=int, default=0,
                    help="decode mode: sliding-window attention of W "
                         "tokens per request — the pool drops interior "
                         "pages past the window each step and "
                         "pages_evicted / decode_bytes_per_step bank "
                         "the capacity win (the no-window replay at "
                         "the same --context-len is the CI teeth arm)")
    ap.add_argument("--sinks", type=int, default=0,
                    help="with --window: keep the first K tokens' "
                         "(attention-sink) pages visible forever")
    ap.add_argument("--prefill-flops", type=float, default=0.0,
                    help="decode mode: budget each chunked-prefill "
                         "step by estimated attention FLOPs instead of "
                         "tokens alone (needs --prefill-chunk); bounds "
                         "decode_step_p99_during_prefill_ms at deep "
                         "contexts where a token cap misprices "
                         "quadratic attention work")
    ap.add_argument("--table-block", type=int, default=0,
                    help="decode mode: walk decode page tables through "
                         "the two-level view with N-entry L2 blocks "
                         "(ISSUE 20 — SMEM rides live blocks, not "
                         "total pages); 0 = flat tables")
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="decode mode: KV heads for a grouped-query "
                         "(GQA/MQA) pool — must divide --n-head; 0 = "
                         "n-head (no grouping).  Lands in the result as "
                         "kv_heads next to kv_bytes_per_token")
    ap.add_argument("--kv-dtype", default="fp32",
                    choices=tuple(_KV_DTYPES),
                    help="decode mode: KV page element type; int8 "
                         "stores amax-quantized pages with per-page "
                         "fp32 scales (single-device pools only)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="decode mode: prompt-lookup speculative "
                         "decoding with N draft tokens per step over a "
                         "repeated-structure prompt workload; runs a "
                         "d=0 arm of the same replay in the same "
                         "invocation and banks acceptance_rate / "
                         "tokens_per_step / spec_speedup.  Composes "
                         "with every --sampling scenario (sampled rows "
                         "verify through the exact accept/resample "
                         "epilogue; greedy stays oracle-identical) and "
                         "with --mesh N (the SPMD program's multi-"
                         "token verify step)")
    ap.add_argument("--sampling", default="greedy",
                    choices=tuple(_SAMPLING_SCENARIOS),
                    help="decode mode: per-request SamplingParams "
                         "scenario attached to every request (greedy = "
                         "none, the oracle-identical arm; temp/topk/"
                         "topp exercise the jitted sampling epilogue)")
    ap.add_argument("--turns", type=int, default=1,
                    help="decode mode: > 1 runs the multi-turn chat "
                         "replay — each of --sequences sessions holds "
                         "a conversation of N turns through the "
                         "tiered KV cache (host-RAM spill between "
                         "turns, resume on the next one)")
    ap.add_argument("--think-time-s", type=float, default=0.0,
                    help="idle gap between turns before sessions are "
                         "parked to the host tier")
    ap.add_argument("--no-tier", action="store_true",
                    help="multi-turn replay WITHOUT the tiered KV "
                         "cache (every turn re-prefills its full "
                         "transcript) — the CI teeth arm")
    ap.add_argument("--host-mb", type=int, default=256,
                    help="host KV tier capacity for --turns, in MiB")
    ap.add_argument("--tenants", type=int, default=0,
                    help="decode mode: multi-tenant replay — register N "
                         "LoRA adapters (paged AdapterPool, ISSUE 19) "
                         "and draw each request's tenant from a "
                         "Zipf(1.1) popularity curve; banks "
                         "adapter_hit_rate, "
                         "adapter_gather_bytes_per_step, per-tenant "
                         "TTFT percentiles, errored_sequences=0 and "
                         "zero leaked pages / green invariants on both "
                         "pools.  A pool sized under the working set "
                         "(--adapter-slots 1 vs 16 tenants) thrashes "
                         "— the CI teeth arm")
    ap.add_argument("--adapter-slots", type=int, default=4,
                    help="with --tenants: device-resident adapter "
                         "slots in the batched A/B pack (the paged "
                         "tier; cold tenants fault in from host)")
    ap.add_argument("--adapter-rank", type=int, default=4,
                    help="with --tenants: LoRA rank of every "
                         "registered adapter (= the pack's padded "
                         "max_rank)")
    ap.add_argument("--disagg", action="store_true",
                    help="decode mode: run the replay through a "
                         "disaggregated prefill/decode Fleet "
                         "(serving/fleet, 1 prefill + 1 decode "
                         "replica) and bank handoff_bytes_per_seq, "
                         "fleet-level TTFT, lost_requests=0 and zero "
                         "leaked pages on both pools")
    ap.add_argument("--fleet", action="store_true",
                    help="decode mode: --disagg plus the elastic "
                         "FleetController under a bursty load — "
                         "scale_ups/scale_downs bank >= 1 next to "
                         "lost_requests=0")
    ap.add_argument("--procs", type=int, default=0,
                    help="with --fleet: run N prefill + N decode "
                         "replicas as real OS processes (ProcSpawner "
                         "over the framed socket plane) instead of "
                         "threads; banks lost_requests=0, "
                         "handoff_drops_recovered, respawns, and "
                         "failover_p99_ms — arm FAULT_SERVE_PROC_KILL "
                         "to SIGKILL a named replica mid-run")
    ap.add_argument("--fleet-retries", type=int, default=3,
                    help="fleet failover retry budget per request "
                         "(0 = a killed replica's work fails typed "
                         "instead of failing over — the chaos-teeth "
                         "arm)")
    ap.add_argument("--pages", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-head", type=int, default=4)
    ap.add_argument("--n-layer", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="arm FAULT_SERVE_* knobs mid-run and report "
                         "recovery counts (engine: dispatcher raise + "
                         "shed deadlines; decode: NaN sequence + page "
                         "leak under a check_every=1 watchdog; with "
                         "--replicas N>=2: one replica KILLED mid-run "
                         "via FAULT_SERVE_REPLICA_KILL — its queued "
                         "requests fail over through the router and "
                         "lost_requests still banks 0)")
    ap.add_argument("--json", default=None, help="write the result dict here")
    ap.add_argument("--obs-dir", default=None,
                    help="enable FLAGS_observability for the run and "
                         "export its artifacts (metrics.prom with "
                         "exemplars, merged trace.json, flight dumps) "
                         "into this directory; their paths land in the "
                         "report (engine chaos runs default to a temp "
                         "dir — the flight recorder needs a home)")
    ap.add_argument("--baseline", default=None,
                    help="banked {metric: value} JSON to gate against")
    ap.add_argument("--tol", type=float, default=0.15)
    ap.add_argument("--gate", action="store_true",
                    help="exit 3 when a baseline verdict fails")
    args = ap.parse_args(argv)

    # usage validation FIRST: a usage error must exit 2 before --mesh
    # mutates the process environment or forces a jax backend
    if args.replicas < 1 or (args.replicas > 1 and args.mode != "engine"):
        sys.stderr.write(
            "serve_bench: --replicas needs engine mode and N >= 1\n")
        return 2
    if args.mesh > 1 and args.mode != "decode":
        sys.stderr.write("serve_bench: --mesh needs --mode decode\n")
        return 2
    if (args.prefix_share or args.prefix_cache or args.prefill_chunk) \
            and args.mode != "decode":
        sys.stderr.write(
            "serve_bench: --prefix-share/--prefix-cache/--prefill-chunk "
            "need --mode decode\n")
        return 2
    if (args.kv_heads or args.kv_dtype != "fp32") \
            and args.mode != "decode":
        sys.stderr.write(
            "serve_bench: --kv-heads/--kv-dtype need --mode decode\n")
        return 2
    if args.kv_heads and (args.kv_heads < 1
                          or args.n_head % args.kv_heads):
        sys.stderr.write(
            f"serve_bench: --kv-heads {args.kv_heads} must be a "
            f"positive divisor of --n-head {args.n_head}\n")
        return 2
    if args.kv_dtype == "int8" and args.mesh > 1:
        sys.stderr.write(
            "serve_bench: int8 KV pages are single-device only (the "
            "sharded pool rejects them) — drop --mesh or --kv-dtype\n")
        return 2
    if args.mesh > 1 and (args.kv_heads or args.n_head) % args.mesh:
        sys.stderr.write(
            f"serve_bench: --kv-heads {args.kv_heads or args.n_head} "
            f"must divide by --mesh {args.mesh} — the sharded pool "
            "splits over the KV-head axis\n")
        return 2
    if not 0.0 <= args.prefix_share <= 1.0:
        sys.stderr.write("serve_bench: --prefix-share must be in [0, 1]\n")
        return 2
    # the long-context knobs (ISSUE 20) ride the monolithic decode loop
    if args.context_len < 0 or args.window < 0 or args.sinks < 0 \
            or args.prefill_flops < 0 or args.table_block < 0:
        sys.stderr.write(
            "serve_bench: --context-len/--window/--sinks/"
            "--prefill-flops/--table-block must be >= 0\n")
        return 2
    if args.context_len or args.window or args.sinks \
            or args.prefill_flops or args.table_block:
        if args.mode != "decode" or args.mesh > 1 or args.chaos \
                or args.disagg or args.fleet or args.turns > 1 \
                or args.tenants:
            sys.stderr.write(
                "serve_bench: --context-len/--window/--sinks/"
                "--prefill-flops/--table-block need plain --mode decode "
                "(no --mesh/--chaos/--disagg/--fleet/--turns/"
                "--tenants)\n")
            return 2
    if args.sinks and not args.window:
        sys.stderr.write(
            "serve_bench: --sinks pins pages against a sliding window "
            "— pass --window with it\n")
        return 2
    if args.prefill_flops and not args.prefill_chunk:
        sys.stderr.write(
            "serve_bench: --prefill-flops budgets CHUNKED prefill — "
            "pass a nonzero --prefill-chunk with it\n")
        return 2
    if args.context_len and args.context_len + args.max_new > args.max_len:
        sys.stderr.write(
            f"serve_bench: --context-len {args.context_len} + --max-new "
            f"{args.max_new} exceeds --max-len {args.max_len}\n")
        return 2
    if (args.speculate or args.sampling != "greedy") \
            and args.mode != "decode":
        sys.stderr.write(
            "serve_bench: --speculate/--sampling need --mode decode\n")
        return 2
    if args.speculate < 0:
        sys.stderr.write("serve_bench: --speculate must be >= 0\n")
        return 2
    if args.speculate and args.chaos:
        sys.stderr.write(
            "serve_bench: --chaos is a single-replay contract (its "
            "knobs fire once); run it without --speculate\n")
        return 2
    if args.disagg or args.fleet:
        if args.mode != "decode":
            sys.stderr.write(
                "serve_bench: --disagg/--fleet need --mode decode\n")
            return 2
        if args.mesh > 1 or args.speculate or args.chaos:
            sys.stderr.write(
                "serve_bench: --disagg/--fleet run their own replica "
                "topology — drop --mesh/--speculate/--chaos (fleet "
                "chaos is driven by the FAULT_SERVE_REPLICA_KILL / "
                "FAULT_SERVE_HANDOFF_DROP env knobs, which the fleet "
                "absorbs and reports as handoff_drops/failovers)\n")
            return 2
        if args.sampling != "greedy":
            sys.stderr.write(
                "serve_bench: --disagg/--fleet bank the greedy "
                "oracle-identical arm; drop --sampling\n")
            return 2
    if args.turns < 1:
        sys.stderr.write("serve_bench: --turns must be >= 1\n")
        return 2
    if args.turns > 1:
        if args.mode != "decode" or args.mesh > 1 or args.speculate \
                or args.chaos or args.disagg or args.fleet \
                or args.sampling != "greedy":
            sys.stderr.write(
                "serve_bench: --turns needs plain --mode decode "
                "(no --mesh/--speculate/--chaos/--disagg/--fleet/"
                "--sampling)\n")
            return 2
        plo, phi = (int(p) for p in args.prompt_range.split(","))
        worst = phi + args.turns * (args.max_new + 3)
        if worst > args.max_len:
            sys.stderr.write(
                f"serve_bench: --turns {args.turns} can grow a "
                f"transcript to ~{worst} tokens > --max-len "
                f"{args.max_len}; shrink --prompt-range/--max-new or "
                "raise --max-len\n")
            return 2
    if (args.no_tier or args.think_time_s) and args.turns <= 1:
        sys.stderr.write(
            "serve_bench: --no-tier/--think-time-s need --turns > 1\n")
        return 2
    if args.tenants < 0 or args.adapter_slots < 1 \
            or args.adapter_rank < 1:
        sys.stderr.write(
            "serve_bench: --tenants must be >= 0 and "
            "--adapter-slots/--adapter-rank >= 1\n")
        return 2
    if args.tenants:
        if args.mode != "decode" or args.mesh > 1 or args.speculate \
                or args.chaos or args.disagg or args.fleet \
                or args.turns > 1 or args.sampling != "greedy":
            sys.stderr.write(
                "serve_bench: --tenants needs plain --mode decode "
                "(no --mesh/--speculate/--chaos/--disagg/--fleet/"
                "--turns/--sampling)\n")
            return 2
    if args.procs and not args.fleet:
        sys.stderr.write(
            "serve_bench: --procs needs --fleet (the process topology "
            "rides the elastic controller)\n")
        return 2
    if args.procs < 0 or args.fleet_retries < 0:
        sys.stderr.write(
            "serve_bench: --procs/--fleet-retries must be >= 0\n")
        return 2
    if args.mesh > 1:
        # the sharded decode program needs a mesh of the devices jax
        # finds: real chips, or the virtual CPU devices the CALLER set
        # up (JAX_PLATFORMS=cpu XLA_FLAGS=
        # --xla_force_host_platform_device_count=N) — never chosen here
        import jax

        if len(jax.devices()) < args.mesh:
            sys.stderr.write(
                f"serve_bench: --mesh {args.mesh} needs {args.mesh} "
                f"devices but the platform initialized with "
                f"{len(jax.devices())}\n")
            return 2

    # shared CI-gate contract (README "CI gates"): usage/environment
    # errors exit 2 so wiring can tell "gate broken" from "regressed"
    if args.gate and not args.baseline:
        sys.stderr.write(
            "serve_bench: --gate needs --baseline BANKED.json\n")
        return 2
    if args.baseline and not os.path.exists(args.baseline):
        sys.stderr.write(
            f"serve_bench: baseline {args.baseline} missing\n")
        return 2

    # observability for the run: --obs-dir opts in explicitly; an engine
    # chaos run opts in implicitly (its contract is "the induced breaker
    # trip leaves a flight-recorder dump", and the flight recorder — like
    # every instrument — only runs with FLAGS_observability on)
    obs_dir = args.obs_dir
    chaos_engine = bool(args.chaos) and args.mode == "engine"
    if chaos_engine and not obs_dir:
        obs_dir = tempfile.mkdtemp(prefix="serve_bench_obs_")
    prev_flags = None
    started_at = time.time()
    if obs_dir:
        from paddle_tpu import flags as pflags
        from paddle_tpu import observability as obs

        prev_flags = {k: pflags.flag(k)
                      for k in ("FLAGS_observability", "FLAGS_flight_dir")}
        pflags.set_flags({"FLAGS_observability": True,
                          "FLAGS_flight_dir": obs_dir})
        obs.reset()  # run-scoped artifacts, not whatever came before
    try:
        if args.mode == "engine" and args.replicas > 1:
            result = run_router_bench(args)
        elif args.mode == "engine":
            result = run_engine_bench(args)
        elif args.disagg or args.fleet:
            result = run_fleet_bench(args, elastic=args.fleet)
        elif args.tenants:
            result = run_tenants_bench(args)
        elif args.turns > 1:
            result = run_multiturn_bench(args)
        else:
            result = run_decode_bench(args)
    finally:
        if prev_flags is not None:
            pflags.set_flags(prev_flags)
    result["started_at"] = started_at
    result["finished_at"] = time.time()
    if obs_dir:
        obs.export_run(obs_dir)
        dumps = list(obs.default_flight().dump_paths)
        result["flight_dumps"] = len(dumps)
        result["artifacts"] = {
            "obs_dir": os.path.abspath(obs_dir),
            "trace": os.path.join(os.path.abspath(obs_dir), "trace.json"),
            "metrics": os.path.join(os.path.abspath(obs_dir),
                                    "metrics.prom"),
            "flight_dumps": dumps,
        }
    print(json.dumps(result, indent=1, sort_keys=True))
    if chaos_engine and not result.get("flight_dumps"):
        # the chaos harness itself failed to produce its black box —
        # an environment error (exit 2), not a regression verdict
        sys.stderr.write(
            "serve_bench: chaos induced a breaker trip but no "
            "flight-recorder dump was written\n")
        return 2

    failed = False
    if args.baseline:
        verdicts = gate(result, args.baseline, args.tol)
        result["regression"] = verdicts
        for v in verdicts:
            sign = "+" if v["delta_pct"] >= 0 else ""
            print(f"[{v['verdict'].upper():4}] {v['metric']}: "
                  f"{v['current']:.4g} vs baseline {v['baseline']:.4g} "
                  f"({sign}{v['delta_pct']:.2f}%, tol "
                  f"{v['tolerance_pct']:.0f}%)")
            failed = failed or v["verdict"] == "fail"
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=1, sort_keys=True)
    return 3 if (args.gate and failed) else 0


if __name__ == "__main__":
    # run as a program (not main() called by a test): share the entry
    # points' persistent compile cache
    from paddle_tpu.core.compiler import default_compile_cache

    sys.stderr.write(f"# compile cache: {default_compile_cache()}\n")
    sys.exit(main())

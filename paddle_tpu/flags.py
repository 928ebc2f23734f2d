"""Global flags tier (reference: python/paddle/fluid/__init__.py:125
__bootstrap__ reading gflags from the environment, e.g. FLAGS_check_nan_inf,
FLAGS_cpu_deterministic, FLAGS_benchmark; framework/operator.cc:777 consumes
check_nan_inf after every op run).

TPU-native shape: flags are plain Python state seeded from `FLAGS_*` env
vars at import, mutable via set_flags()/get_flags() (the modern public
spelling).  check_nan_inf is consumed by the executors as a post-step scan
of fetches and persistable state (the per-op granularity of the reference
would force a host sync between ops — against the one-XLA-program design;
the post-step scan still names the first offending variable).
cpu_deterministic is satisfied by construction — lowerings use counter-based
jax PRNG keys and XLA reductions are run-to-run deterministic on TPU — so
setting it only pins the default program seed.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict

__all__ = ["get_flags", "set_flags", "flag"]

_DEFS: Dict[str, Any] = {
    # debugging
    "FLAGS_check_nan_inf": False,
    "FLAGS_benchmark": False,
    # resilience: NaN/Inf step sentinel (resilience/sentinel.py).  Where
    # FLAGS_check_nan_inf raises the moment a non-finite value appears
    # (post-write-back, debugging), check_numerics implements the
    # AMP-loss-scaler recovery contract in Executor.run: the offending
    # step is SKIPPED (persistable state is not written back — previous
    # params stay live), consecutive trips are counted, and after
    # check_numerics_max_consecutive trips the executor raises
    # NonFiniteStepError naming the first offending fetch/var of the
    # streak.  ElasticTrainer lets that raise report the task failed, so
    # the lease machinery re-dispatches it instead of publishing poisoned
    # params.  Turning it on disables state-buffer donation for affected
    # programs (a skipped step must keep the pre-step params alive) and
    # costs one scalar device sync per step for the jitted finite scan.
    "FLAGS_check_numerics": False,
    "FLAGS_check_numerics_max_consecutive": 3,
    # observability (paddle_tpu/observability/): master switch for the
    # unified telemetry spine — per-step executor metrics (wall-time
    # histogram, compile-cache hit/miss, donation status, sentinel
    # skips), trace spans (compile/step/ckpt, exported as one merged
    # Chrome/Perfetto trace) and resilience/elastic counters.  Off
    # (default): each of those returns after a single dict lookup — no
    # locks, allocations, or clock reads on the hot path; the set-up log
    # and the step log (observability/compiles.py, stepstats.py) are on
    # whatever this says (tier-1 asserts both).
    "FLAGS_observability": False,
    # per-program bytes/step cost attribution, recorded once per fresh
    # compiled entry when observability is on: "native" prices the
    # executable the host actually runs (cheap — the re-lower hits jax's
    # compile cache), "tpu" prices the CHIP program via the chip-less
    # AOT topology tier (core/aot_tpu.py — minutes for big models),
    # "off" skips costing
    "FLAGS_observability_cost": "off",
    # request-scoped tracing (observability/requesttrace.py): hard
    # per-run cap on how many requests keep FULL span detail in the
    # merged trace.  Tail-based sampling keeps slow (>= rolling p99),
    # errored, shed, timed-out, and quarantined requests; everything
    # else contributes only to metrics.  Once the budget is spent even
    # keep-worthy requests are dropped (counted on
    # paddle_tpu_request_traces{decision="budget_dropped"}) — a
    # long-lived server must not grow host memory one span tree per
    # slow request forever
    "FLAGS_request_trace_budget": 256,
    # flight-recorder dump directory (observability/flight.py): where
    # the black-box JSONL lands when the serving circuit breaker trips
    # or engine.health() enters BROKEN.  "" (default) resolves to
    # <tempdir>/paddle_tpu_flight
    "FLAGS_flight_dir": "",
    # determinism
    "FLAGS_cpu_deterministic": False,
    # accepted for reference-script compatibility; memory/threads are
    # XLA/jax concerns here (documented no-ops)
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_eager_delete_tensor_gb": -1.0,
    "FLAGS_init_allocated_mem": False,
    "FLAGS_paddle_num_threads": 1,
    "FLAGS_use_pinned_memory": True,
    # internal conv compute layout: "NCHW" (reference parity) or "NHWC"
    # (TPU-preferred — convs lower with NHWC dimension_numbers behind
    # boundary transposes that XLA cancels between chained convs).
    # "auto" (default) resolves per compiled program: NHWC when tracing
    # for a TPU device, NCHW otherwise — NHWC measured +8% on-chip and
    # won every round-3 tuner probe, so TPUPlace gets it with no env vars
    # (VERDICT r3 item 5) while CPU keeps bit-parity with the reference
    "FLAGS_conv_layout": "auto",
    # serving (paddle_tpu/serving/): the dynamic batcher's batch-size
    # bucket ladder.  Queued requests coalesce into micro-batches padded
    # UP to the smallest bucket that fits, so a polymorphic-batch AOT
    # artifact (or an executor program) compiles at most once per bucket
    # and never again — arbitrary-size batching would compile every
    # batch size traffic ever produces.  Engine-level knobs (max wait,
    # queue depth, deadlines) live on serving.EngineConfig; this flag
    # only sets the process default ladder
    "FLAGS_serving_buckets": "1,2,4,8,16",
    # paged-attention decode implementation (kernels/paged_attention.py):
    # "auto" (default) streams pages through the pallas ragged
    # paged-attention kernel on TPU whenever pallas_paged_viable accepts
    # the pool geometry (head_dim%128==0, page_size sublane-aligned) and
    # takes the reference gather everywhere else; "reference" forces the
    # gather + flash ragged k_lengths tier; "pallas" forces the kernel
    # (falling back to reference OUTSIDE the envelope, with a one-time
    # log — never a Mosaic compile failure); "interpret" runs the pallas
    # kernel under the interpreter (CPU parity testing)
    "FLAGS_serving_paged_impl": "auto",
    # chip-less linter (paddle_tpu/analysis/pallas.py): the v5e VMEM
    # budget the vmem-overflow detector prices every pallas_call's
    # statically-estimated working set (double-buffered padded blocks +
    # scratch) against.  Default: the full 16 MiB/core
    # (analysis.pallas.V5E_VMEM_BYTES); lower it to lint with headroom
    # for compiler spills, raise it only for a different chip
    "FLAGS_analysis_vmem_budget": 16 * 1024 * 1024,
    # chip-less linter (paddle_tpu/analysis/pallas.py): the scalar-
    # memory budget the smem-overflow detector prices every
    # pallas_call's scalar-prefetch operands + SMEM scratch against.
    # SMEM is where the paged-attention page tables and per-page int8
    # scales live — at 128k contexts (~1k pages/seq) FLAT tables and
    # pool-sized scale rows blow through it, the failure the two-level
    # table view (kernels/paged_attention.TwoLevelTables) exists to
    # avoid.  Default: the modeled 128 KiB/core envelope
    # (analysis.pallas.V5E_SMEM_BYTES)
    "FLAGS_analysis_smem_budget": 128 * 1024,
    # chunked prefill (serving/generate.py): cap on PREFILL tokens one
    # engine step may process across the batch.  0 (default) is
    # uncapped — whole prompts prefill in one pass.  With a cap, long
    # prompts split into <=N-token chunks and the scheduler interleaves
    # decode steps between chunks, bounding how long an in-flight
    # sequence's next token can stall behind someone else's prefill
    # (the TTFT/inter-token-jitter knob for bursty shared-prefix load)
    "FLAGS_serving_prefill_chunk": 0,
    # speculative decoding (serving/generate.py + serving/speculative.py):
    # draft tokens per generating sequence per decode step, proposed by
    # the prompt-lookup drafter (n-gram match against prompt +
    # generation history — no draft model) and verified in ONE
    # multi-token model step through the paged kernel; rejected tokens
    # roll back via KVCachePool.truncate_seq.  0 (default) disables.
    # Greedy output stays token-identical to full_decode; sequences
    # with non-greedy SamplingParams degrade to 0 per-sequence
    "FLAGS_serving_speculate": 0,
    # serving circuit breaker (serving/engine.py): after
    # serving_breaker_threshold CONSECUTIVE batch-dispatch failures the
    # engine opens its breaker — submit() fails fast with
    # EngineUnhealthyError for serving_breaker_cooldown_s seconds, then
    # half-opens (requests probe the backend; one successful dispatch
    # closes it).  Process defaults only; per-engine overrides live on
    # serving.EngineConfig(breaker_threshold=, breaker_cooldown_s=)
    "FLAGS_serving_breaker_threshold": 3,
    "FLAGS_serving_breaker_cooldown_s": 5.0,
    # persistent XLA executable cache directory ("" = disabled): repeated
    # runs of the same program skip compilation entirely.  Applied
    # immediately by set_flags (and re-checked at each fresh block compile,
    # core/compiler.py); setting "" disables the cache again.  Where
    # JAX_COMPILATION_CACHE_DIR is set the variable wins and this flag
    # changes nothing
    "FLAGS_compile_cache_dir": "",
}

_VALUES: Dict[str, Any] = {}


def _coerce(default: Any, raw: str) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def _bootstrap() -> None:
    for name, default in _DEFS.items():
        raw = os.environ.get(name)
        _VALUES[name] = default if raw is None else _coerce(default, raw)


_bootstrap()


def _canon(name: str) -> str:
    return name if name.startswith("FLAGS_") else "FLAGS_" + name


def flag(name: str) -> Any:
    """Read one flag (accepts 'check_nan_inf' or 'FLAGS_check_nan_inf')."""
    return _VALUES[_canon(name)]


def get_flags(names=None) -> Dict[str, Any]:
    """reference parity: paddle.get_flags."""
    if names is None:
        return dict(_VALUES)
    if isinstance(names, str):
        names = [names]
    return {_canon(n): _VALUES[_canon(n)] for n in names}


# flags restricted to an exact value set (a typo'd value would otherwise
# silently select the default branch at the use site)
_CHOICES: Dict[str, tuple] = {
    "FLAGS_conv_layout": ("auto", "NCHW", "NHWC"),
    "FLAGS_observability_cost": ("off", "native", "tpu"),
    "FLAGS_serving_paged_impl": ("auto", "reference", "pallas", "interpret"),
}


# -- trace-time device scope -------------------------------------------------
# Executors enter this scope (keyed off the ACTUAL jax device platform, not
# the Place class) around cache-key computation, compilation, and execution,
# so "auto" flags and the un-set AMP policy resolve to the chip-measured
# winners exactly when the program targets a TPU.  Thread-local: hogwild
# AsyncExecutor threads each carry their own scope.
_tls = threading.local()


def tpu_trace_active() -> bool:
    return getattr(_tls, "tpu_active", False)


@contextlib.contextmanager
def tpu_trace_scope(active: bool):
    prev = getattr(_tls, "tpu_active", False)
    _tls.tpu_active = bool(active)
    try:
        yield
    finally:
        _tls.tpu_active = prev


# one-time notices when an "auto" flag / un-set policy silently resolves to
# the TPU-tuned value (ADVICE r4: there was no runtime signal that a
# TPU-traced program picked bf16/NHWC while paths compiling OUTSIDE the
# trace scope — inference/aot.py export, the py_reader preprocessor —
# resolve to fp32/NCHW reference parity; AOT-exported artifacts therefore
# use reference-parity defaults regardless of target device unless the
# policy is set explicitly)
_auto_noted: set = set()
_auto_noted_lock = threading.Lock()


def note_auto_resolution(kind: str, resolved: str) -> None:
    """Log once per process the first time an auto default engages."""
    with _auto_noted_lock:
        if kind in _auto_noted:
            return
        _auto_noted.add(kind)
    import logging

    logging.getLogger("paddle_tpu").info(
        "auto-resolved %s -> %s for a TPU-traced program (explicit "
        "enable_amp()/FLAGS_conv_layout overrides; programs compiled "
        "outside the TPU trace scope, e.g. AOT export, keep "
        "reference-parity fp32/NCHW)", kind, resolved)


def conv_layout() -> str:
    """FLAGS_conv_layout with "auto" resolved for the active device."""
    v = _VALUES["FLAGS_conv_layout"]
    if v == "auto":
        if tpu_trace_active():
            note_auto_resolution("conv_layout", "NHWC")
            return "NHWC"
        return "NCHW"
    return v


def trace_key() -> tuple:
    """Resolved values of every flag that changes the traced program —
    executors include this (plus amp.state_key()) in compiled-program
    cache keys so a flag flip between runs recompiles instead of reusing
    a stale executable."""
    return (conv_layout(),
            # not trace-affecting, but executable-affecting: the sentinel
            # turns state-buffer donation off, so a flag flip must land on
            # a different compiled entry instead of reusing one whose
            # donated inputs a skipped step would have to keep alive
            _VALUES["FLAGS_check_numerics"])


def set_flags(flags: Dict[str, Any]) -> None:
    """reference parity: paddle.set_flags({'FLAGS_check_nan_inf': True}).

    Validates the WHOLE dict before committing any value or side effect:
    a typo in one flag must not leave a partial update (or an already-
    redirected compile cache) behind the raised error."""
    staged: Dict[str, Any] = {}
    for name, value in flags.items():
        cname = _canon(name)
        if cname not in _DEFS:
            raise KeyError(f"unknown flag {name!r}")
        default = _DEFS[cname]
        coerced = (
            _coerce(default, value) if isinstance(value, str)
            else type(default)(value)
        )
        if cname in _CHOICES and coerced not in _CHOICES[cname]:
            raise ValueError(
                f"{cname} must be one of {_CHOICES[cname]}, got {coerced!r}")
        staged[cname] = coerced
    _VALUES.update(staged)
    if "FLAGS_compile_cache_dir" in staged:
        # apply immediately: the compile-path hook only fires on cache
        # misses, so a redirect between two cached runs would otherwise
        # be ignored until the next fresh compile (ADVICE r3)
        from .core import compiler

        compiler._maybe_enable_compile_cache()

"""The device a run is on, the published peaks, and the compile cache."""

from __future__ import annotations

import sys

# Published per-chip peaks, keyed by jax's device_kind.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
# A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            "benchmark/harness/device.py PEAKS: add it with its source")
    return PEAKS[device_kind]


def claim(chips: int, rehearse: bool):
    """The `chips` devices this run uses, or None (and a line on stderr)
    where jax finds no TPU or too few: the caller exits non-zero and builds
    nothing.  Only --rehearse takes whatever jax finds."""
    import jax

    found = jax.devices()
    d0 = found[0]
    if d0.platform != "tpu" and not rehearse:
        sys.stderr.write(
            f"benchmark: jax found no TPU (platform {d0.platform!r}, "
            f"{len(found)} devices); nothing was built\n")
        return None
    if len(found) < chips:
        sys.stderr.write(
            f"benchmark: the cell needs {chips} chips, jax found "
            f"{len(found)} ({d0.device_kind}); nothing was built\n")
        return None
    return found[:chips]


def describe(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def allocator_peak_bytes(devices) -> int:
    """memory_stats()["peak_bytes_in_use"] on the fullest of the devices."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use",
                                    st.get("bytes_in_use", 0))))
    return peak


def largest_program_temp_bytes(devices) -> int:
    """The largest temp_size_in_bytes (per device) among the executables the
    client holds: on the v5e the allocator's peak does not count a running
    program's temporaries (PERF.md 6, PR 22 and PR 24), and a training step
    is mostly temporaries.  0 where the client cannot say."""
    try:
        execs = devices[0].client.live_executables()
    except Exception:
        return 0
    worst = 0
    for e in execs:
        try:
            worst = max(worst, int(
                e.get_compiled_memory_stats().temp_size_in_bytes))
        except Exception:
            continue
    return worst


def compile_cache() -> str:
    """jax's persistent cache at the program's own fixed place
    (JAX_COMPILATION_CACHE_DIR where set, else <checkout>/xla_cache), with
    the floors off: the eager serving loop builds hundreds of sub-second
    executables that the default one-second floor would never cache."""
    import jax
    from paddle_tpu.core.compiler import default_compile_cache

    path = default_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path

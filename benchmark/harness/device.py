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


def allocator_bytes_in_use(devices) -> int:
    """memory_stats()["bytes_in_use"] on the fullest of the devices: what
    the allocator holds at this moment (0 where the backend keeps no
    statistics, as the CPU's)."""
    return max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in devices), default=0)


def memory_limit_bytes(devices) -> int | None:
    """memory_stats()["bytes_limit"] of the smallest of the devices: what a
    chip can hold (16.909e9 on a v5e: the 15.75 GiB the compiler fits a
    program into), or None where the backend does not say."""
    limits = [int((d.memory_stats() or {}).get("bytes_limit", 0))
              for d in devices]
    return min(limits) if limits and all(limits) else None


def fits(peak_bytes: int, limit_bytes) -> bool:
    """Whether a reading of the peak can be one: a run that did not fail
    held no more than the chip has.  True where the limit is not known."""
    return not limit_bytes or peak_bytes <= limit_bytes


def live_executables(devices) -> list:
    """The executables the client holds, [] where it cannot say."""
    try:
        return list(devices[0].client.live_executables())
    except Exception:
        return []


def largest_temp_bytes(executables) -> int:
    """The largest temp_size_in_bytes (per device) among `executables`; 0
    where none can say."""
    worst = 0
    for e in executables:
        try:
            worst = max(worst, int(
                e.get_compiled_memory_stats().temp_size_in_bytes))
        except Exception:
            continue
    return worst


def largest_program_temp_bytes(devices) -> int:
    """The largest temp_size_in_bytes (per device) among the executables the
    client holds: on the v5e the allocator's peak does not count a running
    program's temporaries (PERF.md 6, PR 22 and PR 24), and a training step
    is mostly temporaries.  0 where the client cannot say."""
    return largest_temp_bytes(live_executables(devices))


class StepMemory:
    """The fullest device's memory at the two moments at which a training
    cell can peak, each a sum of two numbers of ONE moment: the bytes the
    allocator holds when a step is launched, and the temporaries of the
    program then launched, which the allocator does not count.

    `first`: the first step, with whatever the harness keeps beside the
    state (reference.FirstStep's copy of the parameters).  `window`: a step
    of the window, read between two steps, when the allocator holds what it
    holds at the next one's launch.  The step's program is known by
    appearing among the client's executables during the first step; the
    largest that does is taken.  The allocator's peak over the whole run and
    the largest temporaries of any program (the plain reference's among
    them) are two peaks of different moments, and their sum can pass what a
    chip holds on a run that did not fail: `peak` cannot."""

    def __init__(self, devices):
        self.devices = devices
        self.first_in_use = self.window_in_use = self.step_temp = 0
        self._held = []

    def before_first_step(self) -> None:
        self._held = live_executables(self.devices)
        self.first_in_use = allocator_bytes_in_use(self.devices)

    def after_first_step(self) -> None:
        held = set(map(id, self._held))   # alive, so no id is used twice
        self.step_temp = largest_temp_bytes(
            e for e in live_executables(self.devices) if id(e) not in held)
        self._held = []

    def between_window_steps(self) -> None:
        self.window_in_use = allocator_bytes_in_use(self.devices)

    def peak(self) -> int:
        return max(self.first_in_use, self.window_in_use) + self.step_temp


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

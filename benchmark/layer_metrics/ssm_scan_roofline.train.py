"""The selective scans against the chip's roofline: the larger of the time
their bytes take at the published HBM bandwidth and the time their
operations take at the MXU's published bf16 peak, over the device time under
the name scope `ssm.scan`, in % (kind train).  Bytes and operations are the
algorithm's forward and backward
(benchmark/configs/phi-4-mini-flash.py::scan_bytes_per_step,
scan_flops_per_step): the same whatever engine runs the scan and whatever it
recomputes or keeps, so a faster engine moves the share through the time
alone.  The BYTES bound it by this count: a layer's two passes move 0.67 GB
at the op's boundary (x, dt, y and their cotangents in bf16), 0.82 ms at 819
GB/s, where their 14 G operations would take 0.07 ms at 197 TFLOP/s.  But
none of those operations is a matmul: they are multiplies, adds and
exponentials of fp32 vectors, one token after the other, and the vector unit
runs them at a small fraction of the MXU's rate.  The scan is VPU-bound and
the share therefore LOW by construction (5-20% expected): it says how far
the scan is from streaming its operands, not that a kernel is badly made.
Every pass that runs reads and writes at least those bytes (the kernels move
fp32, twice the count) and does at least those operations, so the share
cannot pass 100%.  None where the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "phi-4-mini-flash")


def read(obs):
    ms = scope_time.per_step_ms(obs, "ssm.scan")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    mod = manifest.load_py(CONFIG + ".py")
    peak = peaks(obs["device_kind"])
    floor_s = max(
        mod.scan_flops_per_step(cfg, obs["samples_per_step"])
        / peak["bf16_flops"],
        mod.scan_bytes_per_step(cfg, obs["samples_per_step"])
        / peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)

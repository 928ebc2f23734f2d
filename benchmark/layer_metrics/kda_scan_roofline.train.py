"""Kimi Delta Attention's chunk scan against the chip's roofline: the larger
of the time its FLOPs take at the MXU's published bf16 peak and the time its
bytes take at the published HBM bandwidth, over the device time under the
name scope `kda.scan`, in % (kind train).  FLOPs and bytes are the
algorithm's forward and backward at chunks of 64 tokens
(benchmark/configs/kimi-linear-48b-a3b.py::scan_flops_per_step,
scan_bytes_per_step): the same whatever engine runs the scan and whatever
it recomputes or keeps, so a faster engine moves the share through the time
alone.  At this shape (32 heads of 128, S 4096) the BYTES bound it: a layer's
two passes move 0.57 GB (q, k, v, out and their cotangents in bf16, the
log-decay and its gradient in fp32), 0.70 ms at 819 GB/s, where their 55
GFLOP take 0.28 ms at 197 TFLOP/s (the ratio is the same at any S).  Every
pass that runs reads and writes at least those bytes and multiplies at least
those operands, so the share cannot pass 100%.  None where the program has no
such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "kimi-linear-48b-a3b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "kda.scan")
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

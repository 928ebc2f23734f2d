"""The state-space-dual scans against the chip's roofline: the larger of the
time their bytes take at the published HBM bandwidth and the time their
matmul operations take at the MXU's published bf16 peak, over the device
time under the name scope `ssd.scan`, in % (kind train).  Bytes and
operations are the algorithm's forward and backward at the published chunk
of 256 and the pairs i >= j only
(benchmark/configs/granite-4.0-h-micro.py::scan_bytes_per_step,
scan_flops_per_step): the same whatever engine runs the scan and whatever
chunk it walks, recomputes or keeps, so a faster engine moves the share
through the time alone.  The BYTES bound it at this shape, narrowly: a
layer's two passes move 215 MB at the op's boundary (x, y and their
cotangents [8192, 2048] in bf16, B, C, dt and theirs), 0.263 ms at 819 GB/s,
where their 39.5 G matmul operations take 0.200 ms at 197 TFLOP/s.  What the
count leaves out is what the time is mostly made of: a head's decay mask is
an exponential and two products for each of 256 x 256 pairs a chunk on the
vector unit, and the masked products are 64 lanes wide on a 128-lane MXU; so
the share is LOW by construction (10-25% expected) and says how far the scan
is from streaming its operands.  Every pass that runs reads and writes at
least those bytes and does at least those operations, so the share cannot
pass 100%.  None where the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "granite-4.0-h-micro")


def read(obs):
    ms = scope_time.per_step_ms(obs, "ssd.scan")
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

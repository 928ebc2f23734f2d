"""The Gated DeltaNet chunk scans against the chip's roofline: the larger of
the time their bytes take at the published HBM bandwidth and the time their
matmul operations take at the MXU's published bf16 peak, over the device
time under the name scope `gdn.scan`, in % (kind train).  Bytes and
operations are the algorithm's forward and backward at chunks of 64, counted
AS THE MODEL STATES THE LAYER
(benchmark/configs/qwen3-next-80b-a3b.py::scan_bytes_per_step,
scan_flops_per_step): q and k at 16 heads, v, out and their cotangents at 32
in bf16, g and beta [S, 32] in fp32, the k k^T and q k^T products once a key
head, no recomputed pass, no state traffic: the same whatever engine runs
the scan and whatever form the op was given, so a faster engine moves the
share through the time alone.  The BYTES bound it at this shape: a layer's
two passes move 0.54 GB at the op's boundary, 0.66 ms at 819 GB/s, where
their 104 G matmul operations take 0.53 ms at 197 TFLOP/s.  What the count
leaves out is what the time is mostly made of (the unit norms, the decay
mask's exponential and products for each of 64 x 64 pairs a chunk, the
fp32 inverse, the cotangents pulled back on the vector unit), so the share
is LOW by construction and says how far the scan is from streaming its
operands.  Every pass that runs reads and writes at least those bytes and
does at least those operations, so the share cannot pass 100%.  None where
the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "qwen3-next-80b-a3b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "gdn.scan")
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

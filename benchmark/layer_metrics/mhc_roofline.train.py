"""The hyper-connections against the chip's roofline: the larger of the
time their products' FLOPs take at the MXU's published bf16 peak and the
time their bytes take at the published HBM bandwidth, over the device time
under the name scopes `mhc.maps` and `mhc.mix` together, in % (kind train).
FLOPs and bytes are benchmark/configs/xing4.0-29b-a4b.py's
mhc_flops_per_step and mhc_bytes_per_step: a sublayer, the product with Phi
forward and backward, and 5 passes over the streams (forward: read X, write
X'; backward: read X, read dX', write dX) + 4 over a [T, C] value (x_in, y
and their cotangents) at the stream's element size + Phi once: 0.706 GB a
sublayer at 4096 tokens in bf16, 0.86 ms at 819 GB/s, where its 8.5 GFLOP
take 0.04 ms at 197 TFLOP/s: the BYTES bound it.  The count is the same
whatever implements the ops and whatever is recomputed, and every
implementation reads and writes at least those bytes, so the share cannot
pass 100% and a faster engine moves it through the time alone.  None where
the program has neither scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "xing4.0-29b-a4b")


def read(obs):
    # "mhc." is in the path of an operation under either scope
    ms = scope_time.per_step_ms(obs, "mhc.")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    mod = manifest.load_py(CONFIG + ".py")
    peak = peaks(obs["device_kind"])
    floor_s = max(
        mod.mhc_flops_per_step(cfg, obs["samples_per_step"])
        / peak["bf16_flops"],
        mod.mhc_bytes_per_step(cfg, obs["samples_per_step"])
        / peak["hbm_bytes_per_s"])
    return 100.0 * floor_s / (ms * 1e-3)

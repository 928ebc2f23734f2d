"""The expert layers' grouped matmuls against the MXU's peak: their FLOPs a
step at the expected rows (the algorithm's passes: forward 1 and backward 2;
recomputed work is never counted, so a PR that stops or starts recomputing
a forward moves the share through the time alone;
benchmark/configs/moonlight-16b-a3b.py::grouped_matmul_flops_per_step) over
the device time under the name scope `moe.experts` and the chip's published
bf16 peak, in % (kind train).  Compute bounds it: a row's 17.3 M multiply-adds
read 4 KB of activations and the expert's weights are shared by ~1500 rows.
None where the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "moonlight-16b-a3b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "moe.experts")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    flops = manifest.load_py(CONFIG + ".py").grouped_matmul_flops_per_step(
        cfg, obs["samples_per_step"] * cfg["max_length"])
    return 100.0 * flops / (ms * 1e-3) / peaks(obs["device_kind"])["bf16_flops"]

"""The sparse attention's core against the MXU's peak: the FLOPs of the
CHOSEN keys a step (the algorithm's passes: forward 2 products and backward
5; recomputed work is never counted, so a PR that stops or starts
recomputing a forward moves the share through the time alone;
benchmark/configs/keye-vl-2.0-30b-a3b.py::attend_flops_per_step) over the
device time under the name scope `dsa.attend` and the chip's published bf16
peak, in % (kind train).  The kept engine computes every causal score block
on the MXU and masks it, so its matmuls bound it, not its bandwidth (K and V
are read at 4 heads, a block once for the 8 query heads that share it); at
S 16384 it runs 4.3 times the chosen keys' FLOPs, none of which is counted
here, so the share reads low by design and cannot pass 100%.  None where
the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "keye-vl-2.0-30b-a3b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "dsa.attend")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    flops = manifest.load_py(CONFIG + ".py").attend_flops_per_step(
        cfg, obs["samples_per_step"])
    return 100.0 * flops / (ms * 1e-3) / peaks(obs["device_kind"])["bf16_flops"]

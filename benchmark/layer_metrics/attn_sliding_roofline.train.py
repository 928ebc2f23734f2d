"""The sliding layers' attention against the MXU's peak: the FLOPs of the
query-key pairs the mask lets through a step, every pass that runs counted
once (forward and backward: the recomputed forward is merged away;
benchmark/configs/mellum2-12b-a2.5b.py::attend_flops_per_step), over the
device time under the name scope `attn.sliding` and the chip's published
bf16 peak, in % (kind train).  The kernels compute whole score blocks on
the MXU and mask the ones an edge of the mask cuts, none of which is
counted here, and K and V are read at 4 heads: the matmuls bound the time,
not the bandwidth, so the share cannot pass 100%.  None where the program
has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "mellum2-12b-a2.5b")


def share(obs, kind):
    """attend_flops_per_step of the layers of `kind` over the device time
    under `attn.<kind>` and the peak, in %."""
    ms = scope_time.per_step_ms(obs, "attn." + kind)
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    flops = manifest.load_py(CONFIG + ".py").attend_flops_per_step(
        cfg, kind, obs["samples_per_step"])
    return 100.0 * flops / (ms * 1e-3) / peaks(obs["device_kind"])["bf16_flops"]


def read(obs):
    return share(obs, "sliding")

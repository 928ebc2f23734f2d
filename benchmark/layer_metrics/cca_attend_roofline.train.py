"""Compressed convolutional attention's scores against the MXU's peak: the
FLOPs of the causal query-key pairs a step, every pass that runs counted
once (forward and backward; the layer's recomputed forward RUNS in this cell
and is not counted: benchmark/configs/zaya1-8b.py::attend_flops_per_step,
attend_passes), over the device time
under the name scope `cca.attend` and the chip's published bf16 peak, in %
(kind train).  The kernels compute whole score blocks on the MXU and mask
the ones the diagonal cuts, none of which is counted here, and K and V are
read at 2 heads: the matmuls bound the time, not the bandwidth, so the share
cannot pass 100%.  None where the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "zaya1-8b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "cca.attend")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    flops = manifest.load_py(CONFIG + ".py").attend_flops_per_step(
        cfg, obs["samples_per_step"])
    return 100.0 * flops / (ms * 1e-3) / peaks(obs["device_kind"])["bf16_flops"]

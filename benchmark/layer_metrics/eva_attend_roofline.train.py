"""EVA's attention against the MXU's peak: the FLOPs of the pairs its two
masks let through a step (a query's own window up to itself, and the chunk
summaries of every window before it), every pass that runs counted once
(forward and backward, 7 block products: the flash sites keep their output
and logsumexp, so the layer's recomputation runs no second forward;
benchmark/configs/evabyte-6.5b.py::eva_attend_flops_per_step), over the
device time under the name scope `eva.attend` and the chip's published bf16
peak, in % (kind train).  The kernels compute whole score blocks and mask
what the diagonal or a prefix's end cuts, the summaries' call computes
every window against all pooled chunks, and the merge of the two softmaxes
is elementwise time with no FLOP counted: the share cannot pass 100% and
reads low by design.  None where the program has no such scope."""

import os

from benchmark.harness import manifest, scope_time
from benchmark.harness.device import peaks

CONFIG = os.path.join(manifest.BENCH, "configs", "evabyte-6.5b")


def read(obs):
    ms = scope_time.per_step_ms(obs, "eva.attend")
    if ms is None or obs.get("platform") != "tpu":
        return None
    cfg = manifest.read_json(CONFIG + ".json")
    flops = manifest.load_py(CONFIG + ".py").eva_attend_flops_per_step(
        cfg, obs["samples_per_step"])
    return 100.0 * flops / (ms * 1e-3) / peaks(obs["device_kind"])["bf16_flops"]

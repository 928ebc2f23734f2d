"""The full layers' attention against the MXU's peak: the FLOPs of the
query-key pairs the mask lets through a step, every pass that runs counted
once (forward and backward: the recomputed forward is merged away;
benchmark/configs/mellum2-12b-a2.5b.py::attend_flops_per_step), over the
device time under the name scope `attn.full` and the chip's published
bf16 peak, in % (kind train).  The kernels compute whole score blocks on
the MXU and mask the ones an edge of the mask cuts, none of which is
counted here, and K and V are read at 4 heads: the matmuls bound the time,
not the bandwidth, so the share cannot pass 100%.  None where the program
has no such scope."""

import os

from benchmark.harness import manifest

_SLIDING = os.path.join(manifest.BENCH, "layer_metrics",
                        "attn_sliding_roofline.train.py")


def read(obs):
    return manifest.load_py(_SLIDING).share(obs, "full")

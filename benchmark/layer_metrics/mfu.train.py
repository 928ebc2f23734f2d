"""Model FLOP/s utilization: samples/s x the model's FLOPs a sample (from
shapes, benchmark/harness/flops.py) over chips x the published bf16 peak.
Only a TPU has a peak in the table; a TPU that is not in it is an error."""

from benchmark.harness.device import peaks


def read(obs):
    if obs.get("kind") != "train" or obs.get("platform") != "tpu":
        return None
    rate = obs["steps"] * obs["samples_per_step"] / obs["window_s"]
    peak = obs["chips"] * peaks(obs["device_kind"])["bf16_flops"]
    return 100.0 * rate * obs["flops_per_sample"] / peak

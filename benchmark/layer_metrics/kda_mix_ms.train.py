"""Device ms a traced step in what Kimi Delta Attention streams through the
vector unit around its scan (name scope `kda.mix`: the three causal
depthwise convolutions with their SiLU, the decay's two maps of rank 128,
its softplus and the head's rate, beta, and after the scan the norm a head
and the sigmoid gate; the four [d, 4096] projections and the scan are
outside), forward, recomputed forward and backward (kind train), from the
trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "kda.mix")

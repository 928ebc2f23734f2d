"""Device ms a traced step in what streams [S, E] values around the
state-space-dual scan (name scope `ssd.mix`: the causal convolution of x | B
| C with its bias and SiLU, the step's softplus, the gated RMS norm y *
silu(z) over the inner width; the two projections and the scan are outside),
forward, recomputed forward and backward (kind train), from the trace.  None
where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "ssd.mix")

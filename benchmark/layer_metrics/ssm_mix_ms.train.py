"""Device ms a traced step in what streams [S, 5120] values around the
selective scan (name scope `ssm.mix`: the causal convolution with its bias
and SiLU, the step's softplus, the gate y * silu(z); the four projections
and the scan are outside), forward, recomputed forward and backward (kind
train), from the trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "ssm.mix")

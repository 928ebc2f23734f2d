"""Device ms a traced step in what streams [S, 8192 | 4096] values around
the Gated DeltaNet scan (name scope `gdn.mix`: the ONE causal convolution of
q | k | v with its SiLU, the head's decay -exp(A_log) softplus(a + dt_bias),
beta, and the norm a head times silu(z); the projections and the scan are
outside), forward, recomputed forward and backward (kind train), from the
trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "gdn.mix")

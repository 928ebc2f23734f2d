"""Device ms a traced step in the Gated Memory Units (name scope `gmu`: the
whole mixer, W_o(m * silu(W_i u)), both matmuls and the product with the
memory another layer handed out), forward, recomputed forward and backward
(kind train), from the trace.  None where the program has no such
scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "gmu")

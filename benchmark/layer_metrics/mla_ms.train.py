"""Device ms a traced step in the operations of the latent attention blocks (name scope `mla`: the q, kva and o projections, N_kv, rotary, the flash forward and its backward), forward, recomputed
forward and backward (kind train), from the trace.  None where the program
has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "mla")

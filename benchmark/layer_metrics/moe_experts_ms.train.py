"""Device ms a traced step in the operations of the expert layers' grouped matmuls (name scope `moe.experts`: gate, up and down over the rows routed to the held experts), forward, recomputed
forward and backward (kind train), from the trace.  None where the program
has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "moe.experts")

"""Device ms a traced step in the operations of the expert layers' dispatch (name scope `moe.dispatch`: the sort by expert, the gather into the row buffer and the weighted combine back to tokens), forward, recomputed
forward and backward (kind train), from the trace.  None where the program
has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "moe.dispatch")

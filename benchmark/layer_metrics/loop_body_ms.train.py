"""Device ms a traced step in the operations under the program's name scope
`loop.body`: the recurrence's body, that is forward, recomputed forward and
backward of the R trips (kind train), from the trace.  None where the program
has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "loop.body")

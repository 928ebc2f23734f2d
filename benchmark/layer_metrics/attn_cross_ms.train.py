"""Device ms a traced step in the cross layers' attention (name scope
`attn.cross`: the op `differential_attention` over keys and values another
layer made: its two flash sites, forward and backward with their glue, the
difference of the maps, its norm and scale; the query's projection and the
output map are outside), kind train, from the trace.  None where the
program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "attn.cross")

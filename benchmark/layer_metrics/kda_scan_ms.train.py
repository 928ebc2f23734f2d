"""Device ms a traced step in Kimi Delta Attention's chunk scan (name scope
`kda.scan`, the op `gated_delta_attention`: the heads' q and k to unit
length, the decayed products inside a chunk, the triangular inverse, the
scan over chunks that carries the states, and the backward's second scan
last chunk to first; the projections, the convolutions, the decay and the
output's norm and gate are outside), forward, recomputed forward where the
compiler leaves one and backward (kind train), from the trace.  None where
the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "kda.scan")

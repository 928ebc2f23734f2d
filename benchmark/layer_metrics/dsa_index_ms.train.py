"""Device ms a traced step in the index's operations (name scope `dsa.index`:
its three projections, the LayerNorm on its key, rotary, the kernel that scores
every chunk of queries against the keys, and that kernel's backward), forward,
recomputed forward and backward (kind train), from the trace.  None where the
program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "dsa.index")

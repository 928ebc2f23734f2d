"""Device ms a traced step in the exact selection of each query's top keys
(name scope `dsa.select`: the order-preserving integer image of the index
scores, the 32 compare-and-count passes that find a row's k-th largest, the
mask), forward, recomputed forward and backward (kind train), from the trace.
None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "dsa.select")

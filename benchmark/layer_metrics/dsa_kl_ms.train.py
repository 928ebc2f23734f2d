"""Device ms a traced step in the index's loss (name scope `dsa.kl`: the
softmax of the index scores over the chosen keys, its KL from the heads' mean
probabilities, and that loss's gradient with respect to the scores), forward,
recomputed forward and backward (kind train), from the trace.  None where the
program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "dsa.kl")

"""Device ms a traced step under the name scope `eva.attend` (the op
`eva_attention`'s attention alone: its two flash calls, one over a window's
own keys with the windows folded into the batch-head axis and one over the
chunk summaries of the windows before, the merge of the two under one
normaliser, and their backward; the projections, the rotary and the pooling
outside), forward and backward (kind train), from the trace.  None where
the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "eva.attend")

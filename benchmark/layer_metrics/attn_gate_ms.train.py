"""Device ms a traced step in what gated attention does around its flash
site (name scope `attn.gate`: the RMS norms over each q and k head, the
rotary on the first quarter of a head, the transpositions, and sigmoid(gate)
on the context; the projections and the flash site, `attn.full`, are
outside), forward, recomputed forward and backward (kind train), from the
trace.  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "attn.gate")

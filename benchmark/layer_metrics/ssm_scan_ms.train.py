"""Device ms a traced step in the selective scans (name scope `ssm.scan`,
the op `selective_scan`'s own: the recurrence that carries every channel's
states through time, forward, and the backward that walks the chunks from
the last to the first, with the sums of dB and dC over their last 128 lanes;
the projections, the convolution, the step's softplus and the gate are
outside), kind train, from the trace.  None where the program has no such
scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "ssm.scan")

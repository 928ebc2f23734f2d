"""Device ms a traced step in the state-space-dual scans (name scope
`ssd.scan`, the op `ssd_scan`'s own: the chunk walk that carries every
head's state, forward, and the backward that walks the chunks from the last
to the first, with the running sums of dt A and of dcum around the kernels;
the projections, the convolution, the step's softplus, the gate and the norm
are outside), kind train, from the trace.  None where the program has no
such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "ssd.scan")

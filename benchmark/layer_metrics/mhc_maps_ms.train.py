"""Device ms a traced step in computing the hyper-connections' maps (name
scope `mhc.maps`, the op `mhc_maps`, one a sublayer: the RMS over a token's
n x C stream values, their [T, nC] x [nC, 2n + n^2] product with Phi in
fp32, the sigmoids, exp and the Sinkhorn-Knopp iterations on n x n
matrices with the tokens on the lanes), forward, recomputed forward and
backward (kind train), from the trace.  None where the program has no such
scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "mhc.maps")

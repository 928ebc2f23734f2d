"""Device ms a traced step in mixing the residual streams under the
hyper-connections' maps (name scope `mhc.mix`, the ops `mhc_read` and
`mhc_write`, each once a sublayer: x_in = sum_j H_pre[j] X[j] and X'[i] =
sum_j H_res[i, j] X[j] + H_post[i] y over streams [T, n, C]), forward,
recomputed forward and backward (kind train), from the trace.  None where
the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "mhc.mix")

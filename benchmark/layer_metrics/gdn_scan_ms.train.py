"""Device ms a traced step in the Gated DeltaNet layers' chunk scans (name
scope `gdn.scan`, the op `gated_delta_attention` in its head-decay form: a
head's q and k to unit length, the k k^T and q k^T products under ONE [C, C]
decay mask a head, the triangular inverse, the states carried through the
chunks in fp32, and the backward of its own; q and k at the key heads, read
through the index maps), forward and backward (kind train), from the trace.
None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "gdn.scan")

"""Device ms a traced step under the name scope `moe.router` (kind train),
from the trace: in compressed_decoder.py the WHOLE router (the
down-projection to the router's width, the state carried from the layer
before, the norm, the two hidden maps, and the op's last map, softmax and
top 1), forward, recomputed forward and backward; in the other expert
decoders the scope is the op's alone (one matmul from the stream, the
scores, the top k).  None where the program has no such scope."""

from benchmark.harness import scope_time


def read(obs):
    return scope_time.per_step_ms(obs, "moe.router")

"""Device time in all-reduce operations per step on the first device, from
the trace (several chips only)."""


def read(obs):
    tr = obs.get("trace")
    if obs.get("kind") != "train" or not tr or not tr.get("n_ops") \
            or obs.get("chips", 1) < 2 or not obs.get("trace_steps"):
        return None
    return 1e3 * tr["collective_s"] / obs["trace_steps"]

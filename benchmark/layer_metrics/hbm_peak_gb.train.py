"""The fullest device's memory over the run: the allocator's peak plus the
largest program's temporaries, which the allocator does not see while that
program runs (benchmark/harness/kind_train.py), in GB (kind train)."""


def read(obs):
    if obs.get("kind") != "train" or not obs.get("memory_peak_bytes"):
        return None
    return obs["memory_peak_bytes"] / 1e9

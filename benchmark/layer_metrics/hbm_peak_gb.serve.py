"""The allocator's peak on the chip after the window (the eager loop has no
program temporaries for it to miss)."""


def read(obs):
    if obs.get("kind") != "serve" or not obs.get("memory_peak_bytes"):
        return None
    return obs["memory_peak_bytes"] / 1e9

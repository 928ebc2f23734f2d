"""H, the host's share of the device's gap between two steps: from the end of
step k's `executor.wait` (every fetched value ready) to the start of step
k + 1's `executor.dispatch` (the jit call), two host timestamps; the mean
over the traced window's step boundaries, ms (kind train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "host")

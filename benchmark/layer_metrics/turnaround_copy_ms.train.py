"""From the end of `executor.wait` to the end of `executor.step`: the copy of
the fetched values to the host (`executor.copy`) and the span exits; the
mean over the traced window's step boundaries, ms (kind train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "copy")

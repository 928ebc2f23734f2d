"""From the end of `executor.step` to the end of `executor.run`: the
executor's frames return and let go of the staged and donated argument
arrays; the mean over the traced window's step boundaries, ms (kind
train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "release")

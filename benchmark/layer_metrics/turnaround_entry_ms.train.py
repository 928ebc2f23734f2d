"""From the start of `executor.run` to the start of `executor.dispatch`: the
call's prelude, `executor.plan` and `executor.stage`; the mean over the
traced window's step boundaries, ms (kind train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "entry")

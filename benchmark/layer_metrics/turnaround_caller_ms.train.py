"""From the end of one `executor.run` to the start of the next: the caller's
loop, not the program's; the mean over the traced window's step boundaries,
ms (kind train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "caller")

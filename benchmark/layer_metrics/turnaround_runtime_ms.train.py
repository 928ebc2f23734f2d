"""G - H, the runtime's share of the device's gap between two steps, launch
plus completion notice: G from the first device's module runs on the
device's clock, H between the program's two marks on the host's clock; no
timestamp of one clock meets one of the other.  The mean over the traced
window's step boundaries, ms (kind train)."""

from benchmark.harness import turnaround


def read(obs):
    return turnaround.part_ms(obs, "runtime")

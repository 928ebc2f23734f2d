"""What the stalled steps cost the rate: their periods less the window's
median, summed, over `window_s`, % (kind train).

One key of benchmark/harness/step_log.py::summary, which cuts the program's
always-on step log to the measured window."""

from benchmark.harness import step_log


def read(obs):
    return step_log.reading(obs, "stall_share")

"""The part of `stall_share.train` that lies inside the wait, fetch start to
ready: each stalled step's wait less the window's median wait, at most its
excess, over `window_s`, %.  Equal to `stall_share.train`: the host was
waiting to hear of the step's end (kind train).

One key of benchmark/harness/step_log.py::summary, which cuts the program's
always-on step log to the measured window."""

from benchmark.harness import step_log


def read(obs):
    return step_log.reading(obs, "stall_wait_share")

"""The part of `stall_wait_share.train` during which the process was on no
CPU: the wait's wall excess less the excess of `time.process_time()` (every
thread of the process) over its median, floored at 0, over `window_s`, %.
Equal to `stall_wait_share.train`: the whole process slept, and what it
waited for is outside it (kind train).

One key of benchmark/harness/step_log.py::summary, which cuts the program's
always-on step log to the measured window."""

from benchmark.harness import step_log


def read(obs):
    return step_log.reading(obs, "stall_asleep_share")

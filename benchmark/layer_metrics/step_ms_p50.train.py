"""The median PERIOD of the measured window's steps (the next step's start
less its own), ms: the step as `train_samples_per_s` would read it had no
step stalled; batch / this is the rate a stall cannot move (kind train).

One key of benchmark/harness/step_log.py::summary, which cuts the program's
always-on step log to the measured window."""

from benchmark.harness import step_log


def read(obs):
    return step_log.reading(obs, "step_ms_p50")

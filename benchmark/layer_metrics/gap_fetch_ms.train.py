"""Idle time of the first device inside the traced window, per traced step,
that lies under an `executor.fetch` span (device done to fetched values on the host), from the trace
(kind train)."""

from benchmark.harness import step_spans


def read(obs):
    return step_spans.gap_ms(obs, "fetch")

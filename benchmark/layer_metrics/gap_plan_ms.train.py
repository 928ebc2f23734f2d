"""Idle time of the first device inside the traced window, per traced step,
that lies under an `executor.plan` span (fingerprint, cache lookup, the scope's state values), from the trace
(kind train)."""

from benchmark.harness import step_spans


def read(obs):
    return step_spans.gap_ms(obs, "plan")

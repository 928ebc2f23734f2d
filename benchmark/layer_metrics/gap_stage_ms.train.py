"""Idle time of the first device inside the traced window, per traced step,
that lies under an `executor.stage` span (every jax.device_put before the call, the reshard on a mesh), from the trace
(kind train)."""

from benchmark.harness import step_spans


def read(obs):
    return step_spans.gap_ms(obs, "stage")

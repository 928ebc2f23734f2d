"""All idle time of the first device inside the traced window, per traced
step, less what lies under `executor.plan`, `.stage`, `.dispatch` and
`.fetch`: `executor.commit`, the caller's own loop, and whatever no span
covers (kind train)."""

from benchmark.harness import step_spans


def read(obs):
    return step_spans.gap_ms(obs, None)

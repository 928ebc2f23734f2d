"""Values a step had to place on its devices before the call: `moved` summed
over the traced window's `executor.stage` spans, per traced step; steady
state must read 0.  From the host plane alone (kind train)."""

from benchmark.harness import step_spans


def read(obs):
    return step_spans.values_moved(obs)

"""The fullest device's memory at the fuller of two moments, each a sum of
two numbers of that one moment: the bytes the allocator held when the first
step was launched (reference.FirstStep's copy of the parameters among them)
or when a step of the window was, plus the temporaries of the step's
program, which the allocator does not see while it runs
(benchmark/harness/device.py::StepMemory), in GB (kind train).  A run that
did not fail cannot have held more than the chip has: a reading over the
allocator's limit is no reading, and is left out."""

from benchmark.harness.device import fits


def read(obs):
    peak = obs.get("memory_peak_bytes")
    if obs.get("kind") != "train" or not peak \
            or not fits(peak, obs.get("memory_limit_bytes")):
        return None
    return peak / 1e9

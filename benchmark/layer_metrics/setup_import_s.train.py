"""Process start (`benchmark/run.py::T_START`) to the end of `import paddle_tpu`:
Python, jax, the backend's client, the package (kind train).

One key of benchmark/harness/setup_log.py::summary, which cuts the program's
set-up log at the window's start."""

from benchmark.harness import setup_log


def read(obs):
    return setup_log.reading(obs, "import_s")

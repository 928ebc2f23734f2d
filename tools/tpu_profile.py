"""Capture a device profile of a bench model and print the top time sinks.

Traces ONLY the timed steady-state loop of a bench.py model
(run_model(profile_logdir=...) wraps it in jax.profiler.trace; startup,
compilation, and warmup stay outside the trace) and decodes the resulting
xplane protobuf with the local wire-format reader (tools/xplane.py — the
installed tensorboard_plugin_profile pywrap is incompatible with this tf)
into per-op device-time totals.  The reference analogue is the platform
profiler's aggregated per-op table (paddle/fluid/platform/profiler.cc
EnableProfiler/PrintProfiler) and tools/timeline.py; here the device
timeline comes from XLA's own tracing, correlated to fluid op names via the
named_scope HLO metadata the compiler already attaches (core/compiler.py).

Usage:
    python tools/tpu_profile.py resnet50 [steps]   # env knobs as bench.py
Prints a table of the top-20 device ops by total self time plus a category
rollup (conv/matmul/elementwise/reduce/transpose/other).
"""

from __future__ import annotations

import glob
import json
import os
import sys


def _find_xplane(logdir: str) -> str:
    pbs = glob.glob(
        os.path.join(logdir, "plugins/profile/*/*.xplane.pb"), recursive=True
    )
    if not pbs:
        raise SystemExit(f"no xplane.pb under {logdir}")
    return max(pbs, key=os.path.getmtime)


def _device_op_times_from_logdir(logdir: str) -> dict:
    """xplane.pb -> {op name: total device microseconds} via the local
    wire-format reader (tools/xplane.py — the installed
    tensorboard_plugin_profile pywrap is incompatible with this tf)."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from xplane import device_op_times

    with open(_find_xplane(logdir), "rb") as f:
        data = f.read()
    ops = device_op_times(data)
    async_ops = device_op_times(data, line_name="Async XLA Ops",
                                strict_line=True)
    if async_ops:
        sys.stderr.write(
            "# async (DMA) device time, overlaps compute: "
            f"{sum(async_ops.values())/1e3:.2f} ms\n")
    return ops


CATEGORIES = (
    # order matters: first match wins ("convolution" before the generic
    # "fusion" bucket; plain "conv" would swallow convert_* fusions)
    ("conv", ("convolution", "conv2d", "conv3d")),
    ("matmul", ("dot", "gemm")),
    ("allreduce/collective", ("all-reduce", "all-gather", "collective")),
    ("transpose/copy", ("transpose", "copy", "bitcast")),
    ("reduce", ("reduce",)),
    ("fusion/elementwise", ("fusion", "add", "multiply", "select", "jvp")),
)


def _categorize(name: str) -> str:
    low = name.lower()
    for cat, keys in CATEGORIES:
        if any(k in low for k in keys):
            return cat
    return "other"


def main() -> None:
    model = sys.argv[1] if len(sys.argv) > 1 else "resnet50"
    steps = int(sys.argv[2]) if len(sys.argv) > 2 else 5

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import bench

    # default under chiprun_out/: the one directory a chip call brings back
    logdir = os.environ.get("PROFILE_LOGDIR", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "chiprun_out", "profile"))
    os.makedirs(logdir, exist_ok=True)

    from paddle_tpu.core.compiler import default_compile_cache

    sys.stderr.write(f"# compile cache: {default_compile_cache()}\n")
    # this process holds the chip and runs the traced steps itself
    peak = bench._peak_flops(bench._bench_place().jax_device())
    amp = os.environ.get("BENCH_AMP", "keep")
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")

    # bytes/step from XLA's cost accounting of the exact compiled module
    # (VERDICT r5 item 4: the 65 GB paper floor had never been checked
    # against the compiled program)
    os.environ.setdefault("BENCH_COST", "1")
    r = bench.run_model(model, steps, peak, amp=amp, layout=layout,
                        profile_logdir=logdir)

    sys.stderr.write(f"# measured: {json.dumps(r)}\n")
    if r.get("bytes_per_step"):
        print(f"bytes/step (XLA cost analysis): "
              f"{r['bytes_per_step']/1e9:.2f} GB")
    totals = _device_op_times_from_logdir(logdir)
    if not totals:
        raise SystemExit("no device events captured (host-only trace?)")
    grand = sum(totals.values())
    print(f"device total: {grand/1e3:.2f} ms over {steps} traced steps "
          f"({model}, amp={amp}, layout={layout})")
    print(f"{'us':>12} {'%':>6}  op")
    for name, dur in sorted(totals.items(), key=lambda kv: -kv[1])[:20]:
        print(f"{dur:12.0f} {100*dur/grand:6.2f}  {name[:110]}")
    cats: dict = {}
    for name, dur in totals.items():
        c = _categorize(name)
        cats[c] = cats.get(c, 0.0) + dur
    print("\ncategory rollup:")
    for c, dur in sorted(cats.items(), key=lambda kv: -kv[1]):
        print(f"{dur:12.0f} {100*dur/grand:6.2f}  {c}")


if __name__ == "__main__":
    main()

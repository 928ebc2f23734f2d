"""Chip-less memory_analysis of a training cell's step and of its plain
reference, at the cell's real sizes, on the TPU compiler this sandbox has
(guide on-chip-measurement, section 2): what sizes a new `train`
configuration before a single chip-minute is spent.

    JAX_PLATFORMS=cpu python tools/step_memory.py --workload ouro-train-loop4 \
        [--set num_hidden_layers=6] [--no-reference] [--hlo FILE]

Prints one JSON object: argument, output, temporary and alias bytes of the
step (the state is donated, so `alias` is the state it writes in place), the
same of the reference as benchmark/harness/reference.py::FirstStep calls it,
and `beside_first_step`: the step's peak plus the fp32 copy of every
parameter that FirstStep holds through the first step.  `--hlo FILE` also
writes the step's optimised HLO there (its fusions, each with the compiler's
`estimated_cycles`).  A compile is not a run: nothing here is a time."""

import argparse
import functools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k + "_size_in_bytes"))
           for k in ("argument", "output", "temp", "alias")}
    out["peak"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE", help="override a configuration key")
    ap.add_argument("--no-reference", action="store_true")
    ap.add_argument("--hlo", metavar="FILE",
                    help="write the step's optimised HLO here")
    args = ap.parse_args()

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from benchmark.harness import manifest
    from paddle_tpu import flags
    from paddle_tpu.core import aot_tpu

    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    cfg = dict(cell.config)
    for pair in args.set:
        key, _, value = pair.partition("=")
        cfg[key] = json.loads(value)
    mod = cell.config_module
    rows = int(cell.sizing["per_chip_batch"])
    spec = mod.build(cfg, 0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = mod.make_batch(cfg, spec, rows, 0)
    params = fluid.default_main_program().all_parameters()
    n_params = sum(int(np.prod(p.shape)) for p in params)
    with flags.tpu_trace_scope(True):
        compiled, feed_vals, state_vals, rng = exe.capture_program(
            feed=batch, fetch_list=[spec.loss])
        step = aot_tpu.trace_tpu(
            compiled.raw_fn, feed_vals, state_vals, rng,
            donate_argnums=(1,)).lower().compile()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(step.as_text())
    out = {"workload": args.workload, "set": args.set,
           "parameters": n_params, "step": _bytes(step),
           "first_step_copy": 4 * n_params}
    out["beside_first_step"] = out["step"]["peak"] + out["first_step_copy"]
    if not args.no_reference:
        tol = cfg["reference"]
        ref_mod = manifest.load_py(os.path.join(
            manifest.BENCH, "configs",
            cell.entry["config"] + ".reference.py"))
        scope = fluid.global_scope()
        ref_params = {p.name: scope.find_var(p.name) for p in params}
        ref = functools.partial(
            ref_mod.loss_and_grad, cfg=cfg,
            feed_names=tuple(spec.feed_names),
            trainable=frozenset(p.name for p in params if p.trainable),
            micro=max(1, rows // int(tol.get("rows_per_part", rows))))
        out["reference"] = _bytes(aot_tpu.trace_tpu(
            ref, ref_params, batch).lower().compile())
        # what the reference runs beside: the program's state (parameters
        # and the optimizer's moments; the step's temporaries are free by
        # then) and the gradient read out of the moments
        out["reference_beside_state"] = (
            out["reference"]["peak"] + out["step"]["argument"]
            + 4 * n_params)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The whole step of moonlight-train-ep8share with the router pushed: how the
step time follows the rows routed to the held experts, and what a step costs
in each of the row buffers (ops/moe_ops.py::row_buffers), end to end.

The cell's program is built, started and compiled once, as the benchmark
builds it.  Then, for m = 0, 1, 2, 3 and 6, the selection bias of every
expert layer is set to +1 on the first m held experts (a sigmoid score is
under 1, so every token chooses those m; its other choices stay the
router's own) and 0 elsewhere, and `--steps` steps are timed on the host's
clock, each to its fetched loss and loads.  The bias is state: no setting
compiles anything.  m = 0 is the cell's own start (0.75 T rows a layer, the
usual buffer), m = 1 and 2 land in the middle buffer, m = 3 and 6 (every
choice held here: 6 T rows) in the worst-case one.  One JSON line a
setting, all to --out.

    chiprun --chips 1 -- python3 tools/moonlight_step_probe.py --seed 7
    JAX_PLATFORMS=cpu python3 tools/moonlight_step_probe.py --rehearse
`--rehearse` runs the cell's rehearsal size on whatever jax finds and exits
3: its times are not the chip's.  One process holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "moonlight-train-ep8share"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        "chiprun_out", "moonlight_step_probe.json"))
    args = ap.parse_args()

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from benchmark.harness import device, manifest
    from paddle_tpu.ops import moe_ops

    cell = manifest.Cell(manifest.load_manifest(), CELL,
                         rehearse=args.rehearse)
    devices = device.claim(cell.chips, args.rehearse)
    if devices is None:
        return 2
    cfg, mod = cell.config, cell.config_module
    rows = int(cell.sizing["per_chip_batch"])
    spec = mod.build(cfg, args.seed)
    tpu = devices[0].platform == "tpu"
    exe = fluid.Executor(fluid.TPUPlace() if tpu else fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = jax.device_put(mod.make_batch(cfg, spec, rows, args.seed),
                           devices[0])
    held, total = cfg["n_routed_experts"], cfg["router_experts"]
    top_k, tokens = cfg["num_experts_per_tok"], rows * cfg["max_length"]
    buffers = moe_ops.row_buffers(tokens, top_k, held, total)
    biases = [p.name for p in fluid.default_main_program().all_parameters()
              if p.name.endswith("_router_bias")]
    scope, fetch = fluid.global_scope(), [spec.loss] + spec.extras["loads"]

    def step():
        start = time.perf_counter()
        got = [np.asarray(v) for v in exe.run(feed=batch, fetch_list=fetch)]
        return (time.perf_counter() - start) * 1e3, got

    first_ms, _ = step()                       # compiles
    out = []
    for m in (0, 1, 2, 3, min(top_k, held)):
        bias = np.zeros((total,), np.float32)
        bias[cfg["expert_offset"]:cfg["expert_offset"] + m] = 1.0
        timed = []
        for _ in range(args.steps):
            for name in biases:
                scope.set_var(name, jax.device_put(bias, devices[0]))
            ms, got = step()
            here = [int(load.reshape(-1)[cfg["expert_offset"]:][:held].sum())
                    for load in got[1:]]
            timed.append((ms, here, float(np.ravel(got[0])[0])))
        held_rows = timed[-1][1]
        out.append({
            "forced_experts": m, "rows_held_by_layer": held_rows,
            "rows_over_expected": round(
                max(held_rows) / mod.expected_rows_per_token(cfg) / tokens, 3),
            "row_buffers": list(buffers),
            "row_buffer_by_layer": [int(next(b for b in buffers if r <= b))
                                    for r in held_rows],
            "step_ms": [round(t[0], 3) for t in timed],
            "step_ms_median": round(statistics.median(
                t[0] for t in timed[1:]), 3),
            "loss": timed[-1][2]})
        print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"seed": args.seed, "device": device.describe(devices),
                   "rehearsal": bool(args.rehearse), "tokens": tokens,
                   "first_step_ms": round(first_ms, 1), "settings": out},
                  f, indent=1)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())

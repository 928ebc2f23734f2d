"""The benchmark's one command (BENCHMARK.json `command`):

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process: it checks the device first (no TPU, or fewer chips than the
cell asks for: exit 2, nothing built, no CPU fallback), loads, warms up every
shape of the cell, measures for --seconds, checks the outputs and prints one
JSON object as the last line of its standard output.  --rehearse runs the
cell at its files' tiny `rehearsal` sizes on whatever jax finds, marks the
line `"rehearsal": true` and exits 3: a rehearsal proves paths, never speed.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--parked", default=None,
                    help="also read benchmark/parked/<name>.manifest.json")
    args = ap.parse_args()

    from benchmark.harness import device, manifest

    man = manifest.load_manifest(args.parked)
    cell = manifest.Cell(man, args.workload, rehearse=args.rehearse)
    if args.seconds is None:
        args.seconds = float(man["run_seconds"])
    args.trace_dir = os.path.join(ROOT, "bench_out", "trace")

    devices = device.claim(cell.chips, args.rehearse)
    if devices is None:
        return 2
    # the program comes after the device check: nothing is built without one
    # a rehearsal leaves no CPU executables in the chip's cache
    cache = None if args.rehearse else device.compile_cache()
    print(f"[bench] {cell.name}: {device.describe(devices)}, compile cache "
          f"{cache}, seed {args.seed}, {args.seconds} s", flush=True)

    res = cell.runner().run(cell, args, devices, T_START)
    obs = res["obs"]
    for p in res["problems"]:
        print(f"[bench] NOT CORRECT: {p}", flush=True)
    print("[bench] " + json.dumps(
        {k: v for k, v in obs.items()
         if k not in ("trace", "end_to_end")}), flush=True)

    dev = device.describe(devices)
    dev["memory_peak_bytes"] = obs["memory_peak_bytes"]
    line = {"correct": bool(res["correct"]), "attempted": res["attempted"],
            "failed": res["failed"], "device": dev}
    if args.trace:
        line["metrics"] = manifest.read_layer_metrics(cell, obs)
        tr = obs.get("trace") or {}
        dev["busy_s"] = tr.get("busy_s", 0.0)
        dev["window_s"] = tr.get("window_s", 0.0)
        line["breakdown"] = {"device_ops": tr.get("device_ops", []),
                             "idle_gaps": tr.get("idle_gaps", [])}
    else:
        units = {m["name"]: m["unit"] for m in cell.metrics("end_to_end")}
        line["metrics"] = {
            name: {"value": float(obs["end_to_end"][name]), "unit": unit}
            for name, unit in units.items()
            if obs["end_to_end"].get(name) is not None}
    if args.rehearse:
        line["rehearsal"] = True
    # each number `correct` compared beside its limit: the run's last lines
    # on stderr, and the line's last key
    line["compared"] = res.get("compared", {})
    for name, (value, limit) in line["compared"].items():
        sys.stderr.write(f"[bench] compared {name}: {value} limit {limit}\n")
    print(json.dumps(line), flush=True)
    return 3 if args.rehearse else 0


if __name__ == "__main__":
    sys.exit(main())

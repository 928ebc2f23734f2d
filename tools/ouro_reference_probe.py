"""What the tolerances of benchmark/configs/ouro-2.6b.json are measured
against: the first step of `ouro-train-loop4`, as the benchmark takes it,
held to the plain reference and to mutants of the reference, each of which a
tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/ouro_reference_probe.py \
        [--seed N] [--rehearse]

One process (the one that holds the chip), one compile of the step, one of
the reference and one of each mutant.  Writes the readings to
chiprun_out/ouro_reference_probe.json and prints them.  The mutants:

  three_trips          total_ut_steps 3 in place of 4
  last_trip_gradient   a tied weight's gradient taken from the last trip only
  entropy_dropped      the -beta H(p) term left out of the loss
  gate_dropped         no exit distribution: the last trip's cross entropy
  a_norm_left_out      N2 of the first layer is the identity
  fp8_matmuls          every weight matmul's operands rounded to float8_e4m3fn:
                       the nearest precision below the bf16 the cell computes in
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "ouro-train-loop4"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "ouro-2.6b.reference.py")
MUTANTS = ("three_trips", "last_trip_gradient", "entropy_dropped",
           "gate_dropped", "a_norm_left_out", "fp8_matmuls")


def mutant(name: str):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    by_config = {"three_trips": {"total_ut_steps": 3},
                 "entropy_dropped": {"entropy_beta": 0.0},
                 "gate_dropped": {"exit_gate": False}}
    cfg_over = by_config.get(name, {})
    if name == "a_norm_left_out":
        whole = mod._layer

        def layer(p, h, i, cfg):
            if i:
                return whole(p, h, i, cfg)
            eps = cfg["rms_norm_eps"]
            a = h + mod._attention(
                p, mod._rms_norm(h, p["l0_n1_scale"], eps), "l0_attn", cfg)
            return a + mod._rms_norm(
                mod._mlp(p, mod._rms_norm(a, p["l0_n3_scale"], eps),
                         "l0_mlp"), p["l0_n4_scale"], eps)

        mod._layer = layer
    elif name == "last_trip_gradient":
        whole, losses, calls = mod._layer, mod._token_losses, [0]

        def layer(p, h, i, cfg):
            trip = calls[0] // cfg["num_hidden_layers"]
            calls[0] += 1
            if trip < cfg["total_ut_steps"] - 1:
                p = {k: jax.lax.stop_gradient(v) if k.startswith(f"l{i}_")
                     else v for k, v in p.items()}
            return whole(p, h, i, cfg)

        def token_losses(*args):
            calls[0] = 0
            return losses(*args)

        mod._layer, mod._token_losses = layer, token_losses
    elif name == "fp8_matmuls":
        def mm(x, w):
            def f8(a):
                return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return jnp.matmul(f8(x), f8(w))

        mod._mm = mm
    elif name is not None and name not in by_config:
        raise KeyError(name)

    def loss_and_grad(params, batch, cfg, **kw):
        return mod.loss_and_grad(params, batch, {**cfg, **cfg_over}, **kw)

    return loss_and_grad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from benchmark.harness import device, manifest, reference

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
    first = reference.FirstStep(cell, spec)
    params = first.params
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    out = {"seed": args.seed, "device": device.describe(devices),
           "tolerances": {k: v for k, v in first.tol.items()
                          if isinstance(v, (int, float))}, "readings": {}}
    for name in (None,) + MUTANTS:
        first.params = params
        first.module = types.SimpleNamespace(loss_and_grad=mutant(name))
        found, problems = first.compare(loss, batch, rows)
        out["readings"][name or "reference"] = {
            **found, "refused_by": [p.split(":")[0][:60] for p in problems]}
        print(f"[probe] {name or 'reference'}: {found}\n"
              f"[probe]   refused by {len(problems)}: {problems}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "ouro_reference_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    ok = not out["readings"]["reference"]["refused_by"] and all(
        out["readings"][m]["refused_by"] for m in MUTANTS)
    print(json.dumps({"ok": ok, "passed_though_wrong": [
        m for m in MUTANTS if not out["readings"][m]["refused_by"]]}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

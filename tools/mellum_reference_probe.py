"""What the tolerances of benchmark/configs/mellum2-12b-a2.5b.json are
measured against: the first step of `mellum-train-swa16k`, as the benchmark
takes it, held to the plain reference and to mutants of the reference, each
of which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/mellum_reference_probe.py \\
        [--seed N] [--only window512,fp8_matmuls] [--as-the-cell-starts] \\
        [--rehearse]

One process (the one that holds the chip), one compile of the step, one of
the reference and one of each mutant.  Before the step the norms' scales are
moved off their starts by seeded values and the router's weights made five
times as large (the cell starts scales at 1, where one left out could not
show, and a router on N(0, 0.02) weights scores every expert alike, where
its rule could hardly show); --as-the-cell-starts leaves every parameter
where the cell's own first step finds it.  Writes the readings to
chiprun_out/mellum_reference_probe.json and prints them.  The mutants:

  window512            a sliding layer's query sees 512 keys, not 1024
  no_window            the sliding layers see every causal key
  plain_rope_on_full   the full layers' rotary plain, like the sliding ones'
  attention_factor_left_out
                       YaRN's frequencies, cos and sin not multiplied
  yarn_on_sliding      the sliding layers' rotary under YaRN too
  kv_head_mod          query head j reads key/value head j % 4, not j // 8
  sigmoid_router       sigmoid over the router's logits in place of softmax
  fp8_matmuls          every weight matmul's operands rounded to float8_e4m3fn,
                       the nearest precision below the cell's bf16
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "mellum-train-swa16k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "mellum2-12b-a2.5b.reference.py")
MUTANTS = ("window512", "no_window", "plain_rope_on_full",
           "attention_factor_left_out", "yarn_on_sliding", "kv_head_mod",
           "sigmoid_router", "fp8_matmuls")


def _rope(cfg, **kinds):
    """cfg's rope_parameters with `kinds` (sliding_attention /
    full_attention) replaced."""
    return {"rope_parameters": {**cfg["rope_parameters"], **kinds}}


def mutant(name):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    by_config = {
        "window512": lambda c: {"sliding_window": c["sliding_window"] // 2},
        "no_window": lambda c: {"sliding_window": c["max_length"]},
        "plain_rope_on_full": lambda c: _rope(
            c, full_attention=c["rope_parameters"]["sliding_attention"]),
        "attention_factor_left_out": lambda c: _rope(c, full_attention={
            **c["rope_parameters"]["full_attention"],
            "attention_factor": 1.0}),
        "yarn_on_sliding": lambda c: _rope(
            c, sliding_attention=c["rope_parameters"]["full_attention"]),
    }
    if name == "kv_head_mod":
        mod._to_query_heads = lambda x, share: jnp.tile(x, (share, 1, 1))
    elif name == "sigmoid_router":
        mod._scores = jax.nn.sigmoid
    elif name == "fp8_matmuls":
        def mm(x, w):
            def f8(a):
                return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return jnp.matmul(f8(x), f8(w))

        mod._mm = mm
    elif name is not None and name not in by_config:
        raise KeyError(name)

    def loss_and_grad(params, batch, cfg, **kw):
        over = by_config[name](cfg) if name in by_config else {}
        return mod.loss_and_grad(params, batch, {**cfg, **over}, **kw)

    return loss_and_grad


def move_off_starts(scope, rng, put):
    """Norm scales off 1, the router's weights times 5 (module
    docstring)."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            scope.set_var(p.name, put((v + 0.1 * rng.standard_normal(
                v.shape)).astype(np.float32)))
        elif p.name.endswith("_router_w"):
            scope.set_var(p.name, put((v * 5).astype(np.float32)))


def main(cell_name=CELL, mutants=MUTANTS, make=mutant, move=move_off_starts,
         doc=__doc__, out_name="mellum_reference_probe") -> int:
    """This file's probe; with arguments, another cell's
    (tools/zaya_reference_probe.py): its mutants' names, `make(name)` the
    reference's `loss_and_grad` with that one thing wrong, `move(scope,
    rng, put)` its parameters off their starts."""
    ap = argparse.ArgumentParser(description=doc.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--only", default=None,
                    help="comma-separated mutants; default all")
    ap.add_argument("--as-the-cell-starts", action="store_true",
                    help="leave every parameter at the cell's own start")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import numpy as np
    import paddle_tpu as fluid
    from benchmark.harness import device, manifest, reference

    cell = manifest.Cell(manifest.load_manifest(), cell_name,
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
    if not args.as_the_cell_starts:
        move(fluid.global_scope(), np.random.default_rng(args.seed),
             lambda v: jax.device_put(v, devices[0]))
    batch = jax.device_put(mod.make_batch(cfg, spec, rows, args.seed),
                           devices[0])
    first = reference.FirstStep(cell, spec)
    params = first.params
    out = {"seed": args.seed, "device": device.describe(devices),
           "as_the_cell_starts": args.as_the_cell_starts,
           "tolerances": {k: v for k, v in first.tol.items()
                          if isinstance(v, (int, float))},
           "readings": {}}
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    names = (None,) + tuple(mutants)
    if args.only:
        names = (None,) + tuple(args.only.split(","))
    for name in names:
        first.params = params
        first.module = types.SimpleNamespace(loss_and_grad=make(name))
        found, problems = first.compare(loss, batch, rows)
        out["readings"][name or "reference"] = {
            **found, "refused_by": [p.split(":")[0][:60] for p in problems]}
        print(f"[probe] {name or 'reference'}: {found}\n"
              f"[probe]   refused by {len(problems)}: {problems}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tail = "_as_the_cell_starts" if args.as_the_cell_starts else ""
    with open(os.path.join(ROOT, "chiprun_out",
                           f"{out_name}{tail}.json"), "w") as f:
        json.dump(out, f, indent=1)
    wrong = [m for m in mutants if m in out["readings"]]
    ok = not out["readings"]["reference"]["refused_by"] and all(
        out["readings"][m]["refused_by"] for m in wrong)
    print(json.dumps({"ok": ok, "passed_though_wrong": [
        m for m in wrong if not out["readings"][m]["refused_by"]]}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

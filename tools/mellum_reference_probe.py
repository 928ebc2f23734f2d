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
import functools
import json
import os
import sys

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


def held_to(first, loss_and_grad, loss, batch, rows):
    """(findings, problems, {parameter: (g.r, g.g, r.r)}) of the step that
    `first` (the harness's FirstStep) saw against `loss_and_grad` on the
    parameters the step started from: FirstStep.compare's numbers, by the
    harness's own `judge` and `problems`, for a reference that may be a
    mutant, with the products a name that the judge adds up kept beside
    them (a by-name `listing` reads those)."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import reference

    micro = max(1, rows // int(first.tol.get("rows_per_part", rows)))
    ref_loss, ref_grad = jax.jit(functools.partial(
        loss_and_grad, cfg=first.cell.config,
        feed_names=tuple(first.spec.feed_names), trainable=first.trainable,
        micro=micro))(first.params, batch)

    def f32(x):
        return x.astype(jnp.float32)

    prods = jax.jit(lambda a, b: {
        k: (jnp.vdot(f32(a[k]), f32(b[k])), jnp.vdot(f32(a[k]), f32(a[k])),
            jnp.vdot(f32(b[k]), f32(b[k]))) for k in b})(
        first.program_gradient(), ref_grad)
    prods = {k: tuple(float(x) for x in v) for k, v in prods.items()}
    found = reference.judge(float(loss), float(ref_loss), prods)
    return found, reference.problems(found, first.tol), prods


def main(cell_name=CELL, mutants=MUTANTS, make=mutant, move=move_off_starts,
         doc=__doc__, out_name="mellum_reference_probe", controls=None,
         fetch=(), listing=None) -> int:
    """This file's probe; with arguments, another cell's
    (tools/zaya_reference_probe.py): its mutants' names, `make(name)` the
    reference's `loss_and_grad` with that one thing wrong, `move(scope,
    rng, put)` its parameters off their starts.  `controls` {name: a
    context manager}: controls on THE PROGRAM's side, each the cell's
    program built and run once more inside its context and held to the
    reference as it is; a tolerance has to refuse it like a mutant.
    `listing(name, prods, first, batch, fetched)` -> {key: value} goes
    into each reading beside the harness's numbers: `prods` held_to's
    products a name, `fetched` the step's values of `spec.extras[k]` for k
    in `fetch`."""
    ap = argparse.ArgumentParser(description=doc.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--only", default=None,
                    help="comma-separated mutants and controls; default all")
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
    controls = dict(controls or {})
    names = tuple(mutants) + tuple(controls)
    if args.only:
        names = tuple(args.only.split(","))
    out = {"seed": args.seed, "device": device.describe(devices),
           "as_the_cell_starts": args.as_the_cell_starts,
           "tolerances": {k: v for k, v in cfg["reference"].items()
                          if isinstance(v, (int, float))},
           "readings": {}}

    def first_step():
        """The cell's program built anew and its first step run:
        (FirstStep, batch, loss, the fetched extras)."""
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
        loss, *more = exe.run(feed=batch, fetch_list=[spec.loss] + [
            spec.extras[k] for k in fetch])
        return (first, batch, float(np.ravel(np.asarray(loss))[0]),
                dict(zip(fetch, more)))

    def read(name, step, wrong=None):
        """One reading: `step` held to the reference with `wrong` wrong."""
        first, batch, loss, fetched = step
        found, problems, prods = held_to(first, make(wrong), loss, batch,
                                         rows)
        more = listing(wrong, prods, first, batch, fetched) \
            if listing else {}
        out["readings"][name] = {
            **found, "refused_by": [p.split(":")[0][:60] for p in problems],
            **more}
        print(f"[probe] {name}: {found}\n"
              f"[probe]   refused by {len(problems)}: {problems}", flush=True)

    step = first_step()
    read("reference", step)
    for name in names:
        if name not in controls:
            read(name, step, wrong=name)
    # the chip has no room for two programs' states: the first goes first
    del step
    for name in names:
        if name in controls:
            with controls[name]:
                read(name, first_step())
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tail = "_as_the_cell_starts" if args.as_the_cell_starts else ""
    with open(os.path.join(ROOT, "chiprun_out",
                           f"{out_name}{tail}.json"), "w") as f:
        json.dump(out, f, indent=1)
    passed = [n for n in names if not out["readings"][n]["refused_by"]]
    ok = not out["readings"]["reference"]["refused_by"] and not passed
    print(json.dumps({"ok": ok, "passed_though_wrong": passed}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

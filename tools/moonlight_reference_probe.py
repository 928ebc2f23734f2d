"""What the tolerances of benchmark/configs/moonlight-16b-a3b.json are
measured against: the first step of `moonlight-train-ep8share`, as the
benchmark takes it, held to the plain reference and to mutants of the
reference, each of which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/moonlight_reference_probe.py \
        [--seed N] [--rehearse]

One process (the one that holds the chip), one compile of the step, one of
the reference and one of each mutant.  Before the step every router's
selection bias is set to seeded values in +-0.1 (the cell starts them at 0,
where a bias that wrongly entered the weights could not show), so the
reading under `reference` is also the proof, at the real size, that the
bias moves the selection and nothing else.  Writes the readings to
chiprun_out/moonlight_reference_probe.json and prints them.  The mutants:

  top5                 the 5 largest of s + b chosen in place of 6
  scaling_left_out     routed_scaling_factor 1 in place of 2.446
  bias_in_weights      the gates from s + b, not from s
  softmax_scores       softmax over the 64 router logits in place of sigmoid
  no_rotary_on_k       k_rope enters the scores unrotated
  kv_norm_left_out     N_kv is the identity
  scores_by_sqrt128    scores scaled by 1/sqrt(128), the nope width alone
  expert_dropped       the last held expert's term left out
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

CELL = "moonlight-train-ep8share"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "moonlight-16b-a3b.reference.py")
MUTANTS = ("top5", "scaling_left_out", "bias_in_weights", "softmax_scores",
           "no_rotary_on_k", "kv_norm_left_out", "scores_by_sqrt128",
           "expert_dropped", "fp8_matmuls")


def mutant(name: str):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    by_config = {"top5": lambda c: {"num_experts_per_tok":
                                    c["num_experts_per_tok"] - 1},
                 "scaling_left_out": lambda c: {"routed_scaling_factor": 1.0}}
    if name == "bias_in_weights":
        def gates(p, x, name, cfg):
            choice = mod._scores(mod._mm(x, p[name + "_router_w"])) \
                + p[name + "_router_bias"]
            kth = jnp.sort(choice, axis=-1)[..., -cfg["num_experts_per_tok"]]
            g = jnp.where(choice >= kth[..., None], choice, 0.0)
            return g / jnp.sum(g, axis=-1, keepdims=True) \
                * cfg["routed_scaling_factor"]

        mod._gates = gates
    elif name == "softmax_scores":
        mod._scores = lambda logits: jax.nn.softmax(logits, axis=-1)
    elif name in ("no_rotary_on_k", "kv_norm_left_out"):
        def latent(p, x, name_, cfg):
            r = cfg["kv_lora_rank"]
            kva = mod._mm(x, p[name_ + "_kva_w"])
            c, k_rope = kva[..., :r], kva[..., r:]
            if name == "no_rotary_on_k":
                return mod._rms_norm(c, p[name_ + "_kvn_scale"],
                                     cfg["rms_norm_eps"]), k_rope
            return c, mod._rotary(k_rope, cfg["rope_theta"])

        mod._latent = latent
    elif name == "scores_by_sqrt128":
        mod._score_scale = lambda cfg: cfg["qk_nope_head_dim"] ** -0.5
    elif name == "expert_dropped":
        mod._held_experts = lambda cfg: range(cfg["n_routed_experts"] - 1)
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
    scope, rng = fluid.global_scope(), np.random.default_rng(args.seed)
    for p in fluid.default_main_program().all_parameters():
        if p.name.endswith("_router_bias"):
            scope.set_var(p.name, jax.device_put(
                rng.uniform(-0.1, 0.1, p.shape).astype(np.float32),
                devices[0]))
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
                           "moonlight_reference_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    ok = not out["readings"]["reference"]["refused_by"] and all(
        out["readings"][m]["refused_by"] for m in MUTANTS)
    print(json.dumps({"ok": ok, "passed_though_wrong": [
        m for m in MUTANTS if not out["readings"][m]["refused_by"]]}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

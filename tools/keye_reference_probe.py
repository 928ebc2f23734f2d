"""What the tolerances of benchmark/configs/keye-vl-2.0-30b-a3b.json are
measured against: the first step of `keye-train-dsa16k`, as the benchmark
takes it, held to the plain reference, to the reference with its index
scored from bf16 operands (what the program's stated index precision costs),
and to mutants of the reference, each of which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/keye_reference_probe.py \\
        [--seed N] [--only top1024,dense] [--rehearse]

One process (the one that holds the chip), one compile of the step, one of
the reference and one of each mutant.  Before the step the norms' scales,
the index's LayerNorm shift and the index's weights are moved off their
starts by seeded values (the cell starts scales at 1 and the shift at 0,
where one left out could not show; an index on N(0, 0.02) weights scores
every key alike, and a wrong index could not show).  Writes the readings to
chiprun_out/keye_reference_probe.json and prints them.  Also counted, in
layer 0 (whose input both sides share exactly): the queries whose chosen set
differs between the index scored in fp32 and scored from bf16 operands, and
the chosen keys that differ.  The mutants:

  top1024              the 1024 largest index scores chosen in place of 2048
  dense                the selection dropped: every causal key attended to
  relu_left_out        I = sum_j w q_i.k_i, no ReLU
  w_left_out           the index's head weights all 1 / sqrt(16 x 64)
  kl_over_causal       the index loss's softmax over all causal keys, not S_t
  kv_head_mod          query head j reads key/value head j % 4, not j // 8
  qk_norms_left_out    no RMSNorm on the q and k heads
  one_stream           every rotary pair turned by the first position stream
                       (shows only where the streams differ: the CPU test;
                       on the cell's text it must read as the reference)
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

CELL = "keye-train-dsa16k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "keye-vl-2.0-30b-a3b.reference.py")
MUTANTS = ("top1024", "dense", "relu_left_out", "w_left_out",
           "kl_over_causal", "kv_head_mod", "qk_norms_left_out",
           "one_stream", "sigmoid_router", "fp8_matmuls")
VARIANTS = ("index_from_bf16",)      # not wrong: the program's own precision


def mutant(name):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    by_config = {"top1024": lambda c: {"sa_config": {
        **c["sa_config"], "topk": c["sa_config"]["topk"] // 2}}}
    if name == "dense":
        def chosen(scores, first, topk):
            T, S = scores.shape
            return jnp.arange(S)[None, :] <= first + jnp.arange(T)[:, None]

        mod._chosen = chosen
    elif name == "relu_left_out":
        mod._index_scores = lambda q_i, k_i, w: jnp.einsum(
            "tj,jts->ts", w, jnp.einsum("jtd,sd->jts", q_i, k_i))
    elif name == "w_left_out":
        scores = mod._index_scores
        mod._index_scores = lambda q_i, k_i, w: scores(
            q_i, k_i, jnp.full_like(w, (w.shape[1] * q_i.shape[2]) ** -0.5))
    elif name == "kl_over_causal":
        mod._index_support = lambda mask, first: (
            jnp.arange(mask.shape[1])[None, :]
            <= first + jnp.arange(mask.shape[0])[:, None])
    elif name == "kv_head_mod":
        mod._to_query_heads = lambda x, share: jnp.tile(x, (share, 1, 1))
    elif name == "qk_norms_left_out":
        mod._head_norm = lambda x, scale, eps: x
    elif name == "one_stream":
        rotary = mod._rotary
        mod._rotary = lambda x, positions, cfg: rotary(
            x, jnp.broadcast_to(positions[:1], positions.shape), cfg)
    elif name == "sigmoid_router":
        mod._scores = jax.nn.sigmoid
    elif name == "fp8_matmuls":
        def mm(x, w):
            def f8(a):
                return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return jnp.matmul(f8(x), f8(w))

        mod._mm = mm
    elif name == "index_from_bf16":
        mod._index_operand = lambda x: x.astype(jnp.bfloat16).astype(
            jnp.float32)
    elif name is not None and name not in by_config:
        raise KeyError(name)

    def loss_and_grad(params, batch, cfg, **kw):
        over = by_config[name](cfg) if name in by_config else {}
        return mod.loss_and_grad(params, batch, {**cfg, **over}, **kw)

    return loss_and_grad


def chosen_sets_apart(params, batch, cfg, feed_names) -> dict:
    """In layer 0, the first sequence: queries whose chosen set under the
    index scored from bf16 operands differs from the set under fp32 scores,
    and the chosen keys that differ, a block of queries at a time."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    tokens, _, positions = (batch[n][0] for n in feed_names)
    S = tokens.shape[0]
    block = min(cfg["reference"]["query_block"], S)
    topk = cfg["sa_config"]["topk"]

    @jax.jit
    def count(p, tokens, positions):
        with jax.default_matmul_precision("highest"):
            h = jnp.take(p["embed"], tokens, axis=0)
            u = mod._rms_norm(h, p["l0_n1_scale"], cfg["rms_norm_eps"])
            _, _, _, q_i, k_i, w = mod._projections(p, u, positions,
                                                    "l0_attn", cfg)

            def bf16(x):
                return x.astype(jnp.bfloat16).astype(jnp.float32)

            def rows(acc, xs):
                first, qi_b, w_b = xs
                exact = mod._chosen(mod._index_scores(qi_b, k_i, w_b),
                                    first, topk)
                stated = mod._chosen(mod._index_scores(
                    bf16(qi_b), bf16(k_i), bf16(w_b)), first, topk)
                apart = jnp.sum(exact & ~stated, axis=1)
                return (acc[0] + jnp.sum(apart > 0), acc[1] + jnp.sum(apart),
                        acc[2] + jnp.sum(exact)), None

            return jax.lax.scan(rows, (0, 0, 0), (
                jnp.arange(0, S, block), mod._blocks(q_i, 1, block),
                mod._blocks(w, 0, block)))[0]

    queries, keys, chosen = (int(x) for x in count(p, tokens, positions))
    return {"layer": 0, "queries": S, "queries_whose_set_differs": queries,
            "chosen_keys": chosen, "chosen_keys_that_differ": keys}


def move_off_starts(scope, rng, put):
    """Norm scales and the LayerNorm's shift off 1 and 0, the index's
    weights times 20 (module docstring)."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith(("_scale", "_kn_bias")):
            scope.set_var(p.name, put((v + 0.1 * rng.standard_normal(
                v.shape)).astype(np.float32)))
        elif "_index_" in p.name:
            scope.set_var(p.name, put((v * 20).astype(np.float32)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--only", default=None,
                    help="comma-separated mutants or variants; default all")
    ap.add_argument("--as-the-cell-starts", action="store_true",
                    help="leave every parameter at the cell's own start")
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
    if not args.as_the_cell_starts:
        move_off_starts(fluid.global_scope(), np.random.default_rng(args.seed),
                        lambda v: jax.device_put(v, devices[0]))
    batch = jax.device_put(mod.make_batch(cfg, spec, rows, args.seed),
                           devices[0])
    first = reference.FirstStep(cell, spec)
    params = first.params
    out = {"seed": args.seed, "device": device.describe(devices),
           "as_the_cell_starts": args.as_the_cell_starts,
           "tolerances": {k: v for k, v in first.tol.items()
                          if isinstance(v, (int, float))},
           "chosen_sets_apart": chosen_sets_apart(
               params, batch, cfg, tuple(spec.feed_names)),
           "readings": {}}
    print(f"[probe] chosen sets: {out['chosen_sets_apart']}", flush=True)
    loss = float(np.ravel(np.asarray(
        exe.run(feed=batch, fetch_list=[spec.loss])[0]))[0])
    names = (None,) + VARIANTS + MUTANTS
    if args.only:
        names = (None,) + tuple(args.only.split(","))
    for name in names:
        first.params = params
        first.module = types.SimpleNamespace(loss_and_grad=mutant(name))
        found, problems = first.compare(loss, batch, rows)
        out["readings"][name or "reference"] = {
            **found, "refused_by": [p.split(":")[0][:60] for p in problems]}
        print(f"[probe] {name or 'reference'}: {found}\n"
              f"[probe]   refused by {len(problems)}: {problems}", flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "keye_reference_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    wrong = [m for m in MUTANTS if m in out["readings"] and m != "one_stream"]
    ok = not out["readings"]["reference"]["refused_by"] and all(
        out["readings"][m]["refused_by"] for m in wrong)
    print(json.dumps({"ok": ok, "passed_though_wrong": [
        m for m in wrong if not out["readings"][m]["refused_by"]]}))
    return 0 if ok or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())

"""What the tolerances of benchmark/configs/granite-4.0-h-micro.json are
measured against: the first step of `granite-train-ssd8k`, as the benchmark
takes it, held to the plain reference (the loss and every named gradient's
cosine and relative norm) and to wrong rules, each the reference with one
thing wrong, which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- \\
        python tools/granite_reference_probe.py [--seed N] \\
        [--only fp8_matmuls,state_bf16] [--as-the-cell-starts] [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each wrong rule) with this cell's.
Two modes.  --as-the-cell-starts leaves every parameter where the cell's
own first step finds it: the mode the file's limits are set from.  The
default mode moves them first, so that rules which N(0, 0.02) scores hide
are told apart: W_q's and W_k's columns three times as large (scores nine
times: at 1 / 64 they start at a deviation of ~0.1, a softmax that is all
but uniform), the step's bias dt_bias up by 2 (a state that forgets inside
tens of tokens), the norms' scales off 1 by seeded values.  Writes the
readings to chiprun_out/granite_reference_probe[_as_the_cell_starts].json
and prints them.  (--rehearse on the CPU proves the paths;
tests/test_ssd_hybrid_decoder.py refuses each dropped multiplier at the tiny
size.)  The wrong rules:

  fp8_matmuls             every weight matmul's operands rounded to
                          float8_e4m3fn, the nearest precision below the
                          cell's bf16
  state_bf16              the scan's state rounded to bf16 after every token
  softmax_bf16            attention's scores rounded to bf16 before the
                          softmax and its weights after it
  no_embedding_multiplier h_0 = Emb[x]
  no_residual_multiplier  a = h + Mix, h' = a + MLP
  sqrt_attention_scale    scores times 64^-1/2, every other decoder's
  no_logits_scaling       logits not divided by 8
  norm_before_gate        the norm first, then the gate silu(z)
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "granite-train-ssd8k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "granite-4.0-h-micro.reference.py")
# the configuration's constants a rule replaces
CONSTANTS = {"no_embedding_multiplier": ("embedding_multiplier", 1.0),
             "no_residual_multiplier": ("residual_multiplier", 1.0),
             "sqrt_attention_scale": ("attention_multiplier", 0.125),
             "no_logits_scaling": ("logits_scaling", 1.0)}
MUTANTS = ("fp8_matmuls", "state_bf16", "softmax_bf16") + tuple(CONSTANTS) \
    + ("norm_before_gate",)


def mutant(name):
    """The reference's loss_and_grad with one thing wrong; name None gives
    the reference's own."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)

    def through(dtype):
        return lambda a: a.astype(dtype).astype(jnp.float32)

    f8, bf16 = through(jnp.float8_e4m3fn), through(jnp.bfloat16)

    patches = {
        "fp8_matmuls": {"_mm": lambda x, w: jnp.matmul(f8(x), f8(w))},
        "state_bf16": {"_carried": bf16},
        "softmax_bf16": {"_softmax": lambda s: bf16(jax.nn.softmax(
            bf16(s), axis=-1))},
        "norm_before_gate": {"_normed": lambda y, z, w, cfg: mod._gated_norm(
            y, w, cfg) * jax.nn.silu(z)},
    }
    for attr, fn in patches.get(name, {}).items():
        setattr(mod, attr, fn)
    if name not in CONSTANTS:
        return mod.loss_and_grad
    key, value = CONSTANTS[name]
    return lambda params, batch, cfg, **rest: mod.loss_and_grad(
        params, batch, {**cfg, key: value}, **rest)


def moved(name, v, rng):
    """Parameter `name`'s value v moved off its start (the module
    docstring), None where it stays."""
    if name.endswith("_scale"):
        return v + 0.1 * rng.standard_normal(v.shape)
    if name.endswith(("_attn_q_w", "_attn_k_w")):
        return v * 3.0
    if name.endswith("_ssm_dt_b"):
        return v + 2.0
    return None


def move_off_starts(scope, rng, put):
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        new = moved(p.name, np.asarray(scope.find_var(p.name)), rng)
        if new is not None:
            scope.set_var(p.name, put(new.astype(np.float32)))


def listing(wrong, prods, first, batch, fetched):
    """What a reading says beside the harness's four numbers: every named
    gradient's cosine and relative norm."""
    from xing_reference_probe import by_name

    del wrong, first, batch, fetched
    return {"by_name": by_name(prods)}


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, mutant, move_off_starts, __doc__,
        "granite_reference_probe", listing=listing))

"""What the tolerances of benchmark/configs/qwen3-next-80b-a3b.json are
measured against: the first step of `qwen3next-train-gdn8k`, as the
benchmark takes it, held to the plain reference (the loss and every named
gradient's cosine and relative norm) and to wrong rules, each the reference
with one thing wrong, which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- \\
        python tools/qwen3next_reference_probe.py [--seed N] \\
        [--only fp8_matmuls,state_bf16] [--as-the-cell-starts | --weak-decay]
        [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each wrong rule) with this cell's.
Three modes.  --as-the-cell-starts leaves every parameter where the cell's
own first step finds it: the mode the file's limits are set from.  The
default mode moves them first, so that rules which N(0, 0.02) weights hide
are told apart: W_q's and W_k's columns of the attention layer four times
as large (scores sixteen times: they start at a deviation of ~0.05, a
softmax that is all but uniform, which no rotary moves), the shared
expert's gate vector times 20 (sigmoid(w_s . u) starts at 0.5 +- 0.01 for
every token, a constant factor), layer 0's q~ and k~ columns times 1e-3 (a
head's |q'|^2 starts near 10, where 1e-6 is nothing), the norms' scales off
1 by seeded values.  --weak-decay moves A_log alone, to where a head
forgets by about e^-0.01 a token (the published start forgets by about
e^-10, so the state a chunk hands the next adds nothing to the compared
gradient there): the mode that asks whether a limit sees the precision of
the scan's state and decays once they carry.  Writes the readings to
chiprun_out/qwen3next_reference_probe[_as_the_cell_starts | _weak_decay].json
and prints them.  (--rehearse on the CPU proves the paths;
tests/test_gated_delta_decoder.py refuses every rule of the configuration's
`assumed` at the tiny size.)  The wrong rules:

  fp8_matmuls          every weight matmul's operands rounded to
                       float8_e4m3fn, the nearest precision below the cell's
                       bf16
  state_bf16           the delta rule's state rounded to bf16 after every
                       token
  decay_bf16           a head's log-decay g rounded to bf16
  no_eps_under_root    q and k to unit length without the 1e-6
  sigmoid_norm_gate    the gated norm's gate a sigmoid where the model has
                       a SiLU
  rotary_whole_head    the rotary on all 256 features of a head
  no_shared_gate       the shared expert added without its sigmoid gate
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "qwen3next-train-gdn8k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "qwen3-next-80b-a3b.reference.py")
# the configuration's keys a rule replaces
CONSTANTS = {"rotary_whole_head": ("partial_rotary_factor", 1.0)}
MUTANTS = ("fp8_matmuls", "state_bf16", "decay_bf16", "no_eps_under_root",
           "sigmoid_norm_gate", "rotary_whole_head", "no_shared_gate")


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
    decay = mod._decay
    patches = {
        "fp8_matmuls": {"_mm": lambda x, w: jnp.matmul(f8(x), f8(w))},
        "state_bf16": {"_state": bf16},
        "decay_bf16": {"_decay": lambda *a: bf16(decay(*a))},
        "no_eps_under_root": {"_unit": lambda x: x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True))},
        "sigmoid_norm_gate": {"_norm_gate": lambda o, z, w, eps: mod._rms(
            o, w, eps) * jax.nn.sigmoid(z)},
        "no_shared_gate": {"_shared_gate": lambda p, x, name: 1.0},
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
        return v * 4.0
    if name.endswith("_shared_expert_gate_w"):
        return v * 20.0
    if name == "l0_gdn_qkvz_w":
        v = v.copy()
        v[:, :v.shape[1] // 3] *= 1e-3       # q~ | k~ of q~ | k~ | v~ | z
        return v
    return None


def weakly_decaying(name, v, rng):
    """A_log where g = -exp(A_log) softplus(a + 1) is about -0.01 (a starts
    near 0 and softplus(1) is 1.313), a head a little off the next."""
    import numpy as np

    if name.endswith("_a_log"):
        return np.log(0.01 / 1.313) + 0.3 * rng.standard_normal(v.shape)
    return None


def move(how):
    def every_parameter(scope, rng, put):
        import numpy as np
        import paddle_tpu as fluid

        for p in fluid.default_main_program().all_parameters():
            new = how(p.name, np.asarray(scope.find_var(p.name)), rng)
            if new is not None:
                scope.set_var(p.name, put(new.astype(np.float32)))

    return every_parameter


def listing(wrong, prods, first, batch, fetched):
    """What a reading says beside the harness's four numbers: every named
    gradient's cosine and relative norm."""
    from xing_reference_probe import by_name

    del wrong, first, batch, fetched
    return {"by_name": by_name(prods)}


if __name__ == "__main__":
    import mellum_reference_probe

    weak = "--weak-decay" in sys.argv      # this cell's own third mode
    if weak:
        sys.argv.remove("--weak-decay")
    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, mutant, move(weakly_decaying if weak else moved),
        __doc__, "qwen3next_reference_probe" + "_weak_decay" * weak,
        listing=listing))

"""What the tolerances of benchmark/configs/phi-4-mini-flash.json are measured
against: the first step of `phi4flash-train-sambay`, as the benchmark takes
it, held to the plain reference (the loss and every named gradient's
cosine and relative norm) and to wrong rules, each the reference
with one thing wrong, which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- \\
        python tools/sambay_reference_probe.py [--seed N] \\
        [--only no_d_term,fp8_matmuls] [--as-the-cell-starts] [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each wrong rule) with this cell's.
Two modes.  --as-the-cell-starts leaves every parameter where the cell's
own first step finds it: the mode the file's limits are set from.  The
default mode moves them first, so that rules which N(0, 0.02) scores and
short memories hide are told apart: W_qkv's and W_q's query and key columns
half as large again (scores 2.25 times: a standard deviation of ~2.3 where
N(0, 0.02) gives ~1), the step's bias b_dt up by 2 (steps of 0.007-0.7 for
0.001-0.1: a state that forgets inside tens of tokens and reads its input
strongly), the lambda vectors three times as large, the LayerNorms' and the
pair norm's scales off 1 by seeded values.  (Four times as large, the first
try, seed 3000060202: scores of deviation ~16 under a one-hot softmax, where
bf16's rounding of q and k moves the winner; the exact program itself then
read cosine 0.748 against fp32 and was refused: no verdict on the program,
the mode was softened.)
Writes the readings to
chiprun_out/sambay_reference_probe[_as_the_cell_starts].json and prints
them.  (--rehearse on the CPU proves the paths; tests/test_sambay_decoder.py
refuses every rule at the tiny size with weights that make them show.)  The
wrong rules:

  no_d_term             the scan without D * x
  memory_after_gate     the memory handed out is y * silu(z), after the gate
  first_mamba_memory    the GMU reads the FIRST Mamba layer's scan output
  no_softplus           dt = W_dt r + b_dt, no softplus
  sliding_no_window     the sliding layer sees every causal key
  cross_windowed        the cross layer sees a window of 512 keys
  a1_alone              lambda 0: the first softmax map alone
  no_one_minus_lambda0  the factor (1 - lambda_0) left out
  q1_with_k2            q1 scored against k2 and q2 against k1
  fp8_matmuls           every weight matmul's operands rounded to
                        float8_e4m3fn, the nearest precision below the
                        cell's bf16
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "phi4flash-train-sambay"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "phi-4-mini-flash.reference.py")
MUTANTS = ("no_d_term", "memory_after_gate", "first_mamba_memory",
           "no_softplus", "sliding_no_window", "cross_windowed", "a1_alone",
           "no_one_minus_lambda0", "q1_with_k2", "fp8_matmuls")


def mutant(name):
    """The reference's module, fresh, with one thing wrong; name None
    gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)

    def fp8_mm(x, w):
        def f8(a):
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.matmul(f8(x), f8(w))

    patches = {
        "no_d_term": {"_d_term": lambda d, x: 0.0 * x},
        "memory_after_gate": {"_memory": lambda y, z: y * jax.nn.silu(z)},
        "first_mamba_memory": {"_memory_layer": lambda kinds: 0},
        "no_softplus": {"_step": lambda dt, bias: dt + bias},
        "sliding_no_window": {"_window": lambda kind, cfg: None},
        "cross_windowed": {"_window": lambda kind, cfg: (
            cfg["sliding_window"] if kind in (mod.SLIDING, mod.CROSS)
            else None)},
        "a1_alone": {"_lambda": lambda *a: 0.0},
        "no_one_minus_lambda0": {"_out_scale": lambda lambda_0: 1.0},
        "q1_with_k2": {"_keys_of": lambda k1, k2: (k2, k1)},
        "fp8_matmuls": {"_mm": fp8_mm},
        None: {},
    }
    for attr, fn in patches[name].items():
        setattr(mod, attr, fn)
    return mod


def moved(name, v, rng, width, kv_width):
    """Parameter `name`'s value v moved off its start (the module
    docstring), None where it stays."""
    import numpy as np

    if name.endswith(("_scale", "subln_scale")):
        return v + 0.1 * rng.standard_normal(v.shape)
    if name.endswith("_attn_qkv_w"):
        return v * np.where(np.arange(v.shape[1]) < width + kv_width, 1.5, 1.0)
    if name.endswith("_attn_q_w"):
        return v * 1.5
    if name.endswith("_ssm_dt_b"):
        return v + 2.0
    if "_lambda_" in name:
        return v * 3.0
    return None


def move_off_starts(scope, rng, put):
    import numpy as np
    import paddle_tpu as fluid

    params = fluid.default_main_program().all_parameters()
    width = int(scope.find_var("embed").shape[1])
    kv_width = (int(scope.find_var("l1_attn_qkv_w").shape[1]) - width) // 2
    for p in params:
        new = moved(p.name, np.asarray(scope.find_var(p.name)), rng, width,
                    kv_width)
        if new is not None:
            scope.set_var(p.name, put(new.astype(np.float32)))


def listing(wrong, prods, first, batch, fetched):
    """What a reading says beside the harness's four numbers: every named
    gradient's cosine and relative norm.  (The logits are not fetched: [8192,
    25008] more of them beside the first step's peak would not fit.)"""
    from xing_reference_probe import by_name

    del wrong, first, batch, fetched
    return {"by_name": by_name(prods)}


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, lambda name: mutant(name).loss_and_grad,
        move_off_starts, __doc__, "sambay_reference_probe",
        listing=listing))

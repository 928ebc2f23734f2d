"""What the tolerances of benchmark/configs/kimi-linear-48b-a3b.json are
measured against: the first step of `kimi-train-kda8k`, as the benchmark
takes it, held to the plain reference and to mutants of the reference, each
of which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/kimi_reference_probe.py \\
        [--seed N] [--only decay_a_head,fp8_matmuls] [--as-the-cell-starts] \\
        [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each mutant) with this cell's mutants.
Before the step the norms' scales are moved off 1, the gate's bias off 0,
the maps that decide where a head looks and how fast it forgets (q, k, the
decay's second map, beta, the gate's second map) made larger and the
router's weights five times as large (at N(0, 0.02) the decay is its bias
alone, beta is 1/2 and every expert scores alike, where a rule left out
could hardly show); --as-the-cell-starts leaves every parameter where the
cell's own first step finds it.  Writes the readings to
chiprun_out/kimi_reference_probe.json and prints them.  The mutants:

  decay_a_head         one decay a head, the mean of g over its channels
                       (the nearest published sibling's rule), not one a
                       key channel
  beta_left_out        beta = 1: the whole correction every token
  correction_left_out  M += beta k v^T: what the state already answers to
                       the key is not taken off the value
  not_normalised       q and k as the convolutions leave them, not at unit
                       length
  decay_after_correction
                       the correction reads the state before it decays
  rotary_on            MLA's two 64-wide parts rotated (theta 10000), as
                       moonlight-16b-a3b's are
  fp8_matmuls          every weight matmul's operands rounded to float8_e4m3fn,
                       the nearest precision below the cell's bf16
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "kimi-train-kda8k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "kimi-linear-48b-a3b.reference.py")
MUTANTS = ("decay_a_head", "beta_left_out", "correction_left_out",
           "not_normalised", "decay_after_correction", "rotary_on",
           "fp8_matmuls")


def mutant(name):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)

    def late_decay(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state + (b_t[:, None, None] * k_t[..., None]
                         * mod._lacking(v_t, state, k_t)[:, None, :])
        state = jnp.exp(g_t)[..., None] * state
        return state, jnp.sum(state * q_t[..., None], axis=1)

    def rotary(x, theta=10000.0):
        half = x.shape[-1] // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                             / x.shape[-1])
        angle = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] \
            * inv_freq
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               -1)

    def fp8_mm(x, w):
        def f8(a):
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.matmul(f8(x), f8(w))

    patches = {
        "decay_a_head": {"_channel_decay": lambda g: jnp.broadcast_to(
            jnp.mean(g, axis=-1, keepdims=True), g.shape)},
        "beta_left_out": {"_beta": jnp.ones_like},
        "correction_left_out": {"_lacking": lambda v_t, state, k_t: v_t},
        "not_normalised": {"_unit": lambda x: x},
        "decay_after_correction": {"_token": late_decay},
        "rotary_on": {"_positions": rotary},
        "fp8_matmuls": {"_mm": fp8_mm},
        None: {},
    }
    for attr, fn in patches[name].items():
        setattr(mod, attr, fn)
    return mod.loss_and_grad


def move_off_starts(scope, rng, put):
    """Module docstring: scales off 1, the gate's bias off 0, q, k, the
    decay's, beta's and the gate's maps larger, the router's times 5."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            new = v + 0.1 * rng.standard_normal(v.shape)
        elif p.name.endswith("_gate_bias"):
            new = 0.5 * rng.standard_normal(v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w", "_attn_f_b_w",
                              "_attn_beta_w", "_attn_gate_b_w")):
            new = v * 10
        elif p.name.endswith("_router_w"):
            new = v * 5
        else:
            continue
        scope.set_var(p.name, put(new.astype(np.float32)))


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, mutant, move_off_starts, __doc__,
        "kimi_reference_probe"))

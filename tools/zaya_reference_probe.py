"""What the tolerances of benchmark/configs/zaya1-8b.json are measured
against: the first step of `zaya-train-cca16k`, as the benchmark takes it,
held to the plain reference and to mutants of the reference, each of which a
tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- python tools/zaya_reference_probe.py \\
        [--seed N] [--only taps_swapped,fp8_matmuls] [--as-the-cell-starts] \\
        [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each mutant) with this cell's mutants.
Before the step the norms' scales, the key temperatures and the
convolutions are moved off their starts by seeded values, the router's carry
weights gamma off 0 and the router's last map made five times as large (the
cell starts gamma at 0 and tau at 1, where one left out could not show in
the forward pass, and a router on N(0, 0.02) weights scores every expert
alike); --as-the-cell-starts leaves every parameter where the cell's own
first step finds it.  Writes the readings to
chiprun_out/zaya_reference_probe.json and prints them.  The mutants:

  taps_swapped         convolution A's two taps swapped
  conv_b_depthwise     convolution B one filter a channel, not across a head
  qk_mean_left_out     q = z''[:Lq], k = z''[Lq:]: no mean added
  mean_after_convs     the q-k mean of the convolved values
  value_unshifted      both value halves from the token itself
  shift_on_head0       the token before's value on head 0, its own on head 1
  norm_without_sqrt_d  heads at length 1, not sqrt(D)
  tau_left_out         the keys not multiplied by their temperature
  whole_head_rotary    all 128 features of a head turn, not the first 64
  kv_head_mod          query head j reads key/value head j % 2, not j // 4
  carry_left_out       r = s: gamma * r_prev left out
  relu_router          relu for gelu in the router's two hidden maps
  gate_one             the chosen expert's gate 1, not its probability
  untied_head          the head's gradient does not reach the table
  fp8_matmuls          every weight matmul's operands rounded to float8_e4m3fn,
                       the nearest precision below the cell's bf16
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "zaya-train-cca16k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "zaya1-8b.reference.py")
MUTANTS = ("taps_swapped", "conv_b_depthwise", "qk_mean_left_out",
           "mean_after_convs", "value_unshifted", "shift_on_head0",
           "norm_without_sqrt_d", "tau_left_out", "whole_head_rotary",
           "kv_head_mod", "carry_left_out", "relu_router", "gate_one",
           "untied_head", "fp8_matmuls")


def mutant(name):
    """`loss_and_grad` of a fresh copy of the reference with one thing
    wrong; name None gives the reference itself."""
    import jax
    import jax.numpy as jnp
    from benchmark.harness import manifest

    mod = manifest.load_py(REFERENCE)
    conv_a, shift = mod._conv_a, mod._shift

    def depthwise(z, w, b):
        k, n, D, _ = w.shape
        own = jnp.diagonal(w, axis1=2, axis2=3).reshape(k, n * D)
        return conv_a(z, own, b)

    def shifted_first(v_t, G):
        half = v_t.shape[-1] // 2
        return jnp.concatenate([shift(v_t[:, :half]), v_t[:, half:]],
                               axis=-1).reshape(v_t.shape[0], G, -1)

    def fp8_mm(x, w):
        def f8(a):
            return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        return jnp.matmul(f8(x), f8(w))

    patches = {
        "taps_swapped": {"_conv_a": lambda z, w, b: conv_a(z, w[::-1], b)},
        "conv_b_depthwise": {"_conv_b": depthwise},
        "qk_mean_left_out": {"_qk_mean": lambda q, k, share: (0.0, 0.0)},
        "mean_after_convs": {
            "_mean_inputs": lambda q_t, k_t, q_c, k_c: (q_c, k_c)},
        "value_unshifted": {"_shift": lambda x: x},
        "shift_on_head0": {"_value": shifted_first},
        "norm_without_sqrt_d": {"_unit": lambda x: x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True))},
        "tau_left_out": {"_temperature": lambda k, tau: k},
        "kv_head_mod": {
            "_to_query_heads": lambda x, share: jnp.tile(x, (share, 1, 1))},
        "carry_left_out": {"_carry": lambda s, gamma, r_prev: s},
        "relu_router": {"_act": jax.nn.relu},
        "gate_one": {"_gate": lambda probs, chosen: chosen},
        "untied_head": {
            "_head_table": lambda p: jax.lax.stop_gradient(p["embed"])},
        "fp8_matmuls": {"_mm": fp8_mm},
        "whole_head_rotary": {},
        None: {},
    }
    for attr, fn in patches[name].items():
        setattr(mod, attr, fn)

    def loss_and_grad(params, batch, cfg, **kw):
        if name == "whole_head_rotary":
            rope = cfg["rope_parameters"]
            cfg = {**cfg, "rope_parameters": {**rope, "hybrid": {
                **rope["hybrid"], "partial_rotary_factor": 1.0}}}
        return mod.loss_and_grad(params, batch, cfg, **kw)

    return loss_and_grad


def move_off_starts(scope, rng, put):
    """Norm scales and tau off 1, gamma off 0, the convolutions' weights
    and biases perturbed, the router's last map times 5 (module
    docstring)."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        noise = rng.standard_normal(v.shape)
        if p.name.endswith(("_scale", "_tau")):
            new = v + 0.1 * noise
        elif p.name.endswith("_router_gamma"):
            new = 0.5 * noise
        elif "_conv_" in p.name:
            new = v * (1.0 + 0.5 * noise)
        elif p.name.endswith("_router_w"):
            new = v * 5
        else:
            continue
        scope.set_var(p.name, put(new.astype(np.float32)))


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, mutant, move_off_starts, __doc__,
        "zaya_reference_probe"))

"""What the tolerances of benchmark/configs/evabyte-6.5b.json are measured
against: the first step of `evabyte-train-eva8k`, as the benchmark takes
it, held to the plain reference (the loss, the logits' and every named
gradient's cosine and relative norm) and to wrong rules, each the
reference with one thing wrong, which a tolerance has to refuse.

    chiprun --chips 1 --timeout 3000 -- \\
        python tools/evabyte_reference_probe.py [--seed N] \\
        [--only mean_pooling,fp8_matmuls] [--as-the-cell-starts] [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each wrong rule) with this cell's.
Two modes.  --as-the-cell-starts leaves every parameter where the cell's
own first step finds it: the mode the file's limits are set from, and in
which the five rules ISSUE 56 names and float8 have to be refused.  The
default mode moves them first: the norms' g off 0 by seeded values, W_q and
W_k half as large again (scores 2.25 times: a standard deviation of ~1.5
where N(0, 0.01275) gives ~0.67), mu and phi twice as large, so that a rule
of the SCORES (the rotary, the 1 / sqrt(128), which softmax pools) moves the
result more than at the start.  (Three and four times as large, the first
try, left bf16 scores of deviation ~6 under a peaked softmax: the exact
program itself then read cosine 0.933 against fp32 and was refused.)
Writes the readings to
chiprun_out/evabyte_reference_probe[_as_the_cell_starts].json and prints
them.  (--rehearse on the CPU proves the paths: at hidden 32 the scores are
~0 and `mean_pooling` and `scale_left_out` pass; tests/test_eva_decoder.py
refuses every rule at the tiny size with weights that make them show.)  The
wrong rules:

  own_window_summaries  a query also sees the summaries of its OWN window's
                        chunks (all of them, the ones after it too)
  no_summaries          no query sees a summary: block-diagonal attention
  mean_pooling          a chunk's summary is the plain mean of its keys and
                        of its values, mu and phi unread
  two_softmaxes         a softmax over the window's keys and one over the
                        summaries, their outputs averaged, for ONE softmax
                        over both
  labels_shift_1        every head held to byte t + 1, for t + 1 + i
  no_rotary             q and k not turned
  scale_left_out        scores not divided by sqrt(128)
  fp8_matmuls           every weight matmul's operands rounded to
                        float8_e4m3fn, the nearest precision below the
                        cell's bf16
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "evabyte-train-eva8k"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "evabyte-6.5b.reference.py")
MUTANTS = ("own_window_summaries", "no_summaries", "mean_pooling",
           "two_softmaxes", "labels_shift_1", "no_rotary", "scale_left_out",
           "fp8_matmuls")


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

    def averaged(own, far, v, v_hat):
        near = jnp.einsum("hqs,hsd->hqd", jax.nn.softmax(own, -1), v)
        some = jnp.any(far > -1e29, axis=-1, keepdims=True)
        away = jnp.einsum("hqn,hnd->hqd", jax.nn.softmax(far, -1), v_hat)
        return jnp.where(some, 0.5 * (near + away), near)

    def shift_1(tokens, labels):
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])[:, None]
        return jnp.where(labels != mod.IGNORED, nxt, mod.IGNORED)

    patches = {
        "own_window_summaries": {"_summaries_seen": lambda t, window, chunk:
                                 (t // window + 1) * (window // chunk)},
        "no_summaries": {"_summaries_seen": lambda t, window, chunk: 0 * t},
        "mean_pooling": {"_pool_weights": lambda scores: jnp.full_like(
            scores, 1.0 / scores.shape[-1])},
        "two_softmaxes": {"_attend": averaged},
        "labels_shift_1": {"_labels": shift_1},
        "no_rotary": {"_rotary": lambda x, theta: x},
        "scale_left_out": {"_softmax_scale": lambda head_dim: 1.0},
        "fp8_matmuls": {"_mm": fp8_mm},
        None: {},
    }
    for attr, fn in patches[name].items():
        setattr(mod, attr, fn)
    return mod


def move_off_starts(scope, rng, put):
    """Module docstring: the norms' g off 0, W_q and W_k x 1.5, mu and phi
    x 2."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            new = v + 0.1 * rng.standard_normal(v.shape)
        elif p.name.endswith(("_attn_q_w", "_attn_k_w")):
            new = v * 1.5
        elif p.name.endswith(("_attn_mu", "_attn_phi")):
            new = v * 2
        else:
            continue
        scope.set_var(p.name, put(new.astype(np.float32)))


def listing(wrong, prods, first, batch, fetched):
    """What a reading says beside the harness's four numbers: the logits'
    and every named gradient's cosine and relative norm."""
    import jax
    import jax.numpy as jnp
    from xing_reference_probe import by_name

    cfg, mod = first.cell.config, mutant(wrong)

    @jax.jit
    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in params.items()}
            return jax.lax.map(lambda t: mod._logits(p, t, cfg), tokens)

    def f32(x):
        return jnp.ravel(x).astype(jnp.float32)

    ours, theirs = f32(fetched["logits"]), f32(logits(
        first.params, batch[first.spec.feed_names[0]]))
    cos, ratio, _ = by_name({"logits": tuple(float(jnp.vdot(a, b)) for a, b
                                             in ((ours, theirs), (ours, ours),
                                                 (theirs, theirs)))})["logits"]
    return {"logits_cos": cos, "logits_norm_ratio": ratio,
            "by_name": by_name(prods)}


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, lambda name: mutant(name).loss_and_grad,
        move_off_starts, __doc__, "evabyte_reference_probe",
        fetch=("logits",), listing=listing))

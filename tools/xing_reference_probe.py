"""What the tolerances of benchmark/configs/xing4.0-29b-a4b.json are
measured against: the first step of `xing-train-mhc4`, as the benchmark
takes it, held to the plain reference: the loss, the logits' and every
named gradient's cosine and relative norm with the hyper-connections'
parameters listed apart (at the cell's start they hold under 1e-4 of the
gradient's squared norm and the harness's judge skips them by name;
`mhc_refused_by_name` lists those outside the stated cosine and norm
factor, which at the real size is no verdict: PERF.md 6, PR 50); to mutants
of the reference; and a control on the program's side, the maps computed in
bf16, that a tolerance has to refuse (it moves the gradient's NORM, 0.1%
under the reference's: `grad_norm_rtol` is set between the two).

    chiprun --chips 1 --timeout 3000 -- python tools/xing_reference_probe.py \\
        [--seed N] [--only maps_bf16,fp8_matmuls] [--as-the-cell-starts] \\
        [--rehearse]

tools/mellum_reference_probe.py's probe (one process, one compile of the
step, one of the reference and one of each control) with this cell's
mutants, its control on the program's side and its by-name listing.  Before
the step the norms' scales
are moved off 1, the hyper-connections' scalars made 100 times as large (a
= 1), Phi drawn so that a token's 24 products have a standard deviation of
1.5, b_pre and b_post moved by seeded values, b_res drawn from N(0, 1) with
no large diagonal, and the router's weights made five times as large (at
the cell's start the maps are their biases, H_res is the identity to 1e-3,
the four streams stay equal, and every expert scores alike: a rule left
out could hardly show); --as-the-cell-starts leaves every parameter
where the cell's own first step finds it.  Writes the readings to
chiprun_out/xing_reference_probe.json and prints them.  The controls:

  maps_bf16            THE PROGRAM with every value of the three maps in
                       bf16 (the RMS, the product with Phi, the sigmoids,
                       exp, Sinkhorn), against the reference as it is
  sinkhorn_2           the reference with 2 Sinkhorn iterations, not 20
  write_gate_not_doubled
                       H_post = sigmoid, not 2 sigmoid
  plain_rope           MLA's rotary at the plain frequencies, no YaRN
  scale_left_out       the softmax scale without mscale(mscale_all_dim)^2
  fp8_matmuls          every weight matmul's operands rounded to
                       float8_e4m3fn, the nearest precision below the
                       cell's bf16
"""

import contextlib
import functools
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

CELL = "xing-train-mhc4"
REFERENCE = os.path.join(ROOT, "benchmark", "configs",
                         "xing4.0-29b-a4b.reference.py")
MUTANTS = ("sinkhorn_2", "write_gate_not_doubled", "plain_rope",
           "scale_left_out", "fp8_matmuls")
PROGRAM_CONTROLS = ("maps_bf16",)
MHC = ("_phi", "_a_pre", "_a_post", "_a_res", "_b_pre", "_b_post", "_b_res")


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

    def bare_scale(cfg):
        return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5

    plain, twenty = mod._inv_freq, mod._sinkhorn
    patches = {
        "sinkhorn_2": {"_sinkhorn": lambda m, iters, eps: twenty(m, 2, eps)},
        "write_gate_not_doubled": {"_write_gate": jax.nn.sigmoid},
        "plain_rope": {"_inv_freq": lambda dim, cfg: plain(
            dim, {**cfg, "rope_scaling": None})},
        "scale_left_out": {"_softmax_scale": bare_scale},
        "fp8_matmuls": {"_mm": fp8_mm},
        None: {},
    }
    for attr, fn in patches[name].items():
        setattr(mod, attr, fn)
    return mod


@contextlib.contextmanager
def maps_bf16():
    """The program's side of the control: every value of the three maps in
    bf16 while the program is built and run."""
    import jax.numpy as jnp
    from paddle_tpu.ops import hyper_connection_ops as hc

    exact = hc.maps
    hc.maps = functools.partial(exact, dtype=jnp.bfloat16)
    try:
        yield
    finally:
        hc.maps = exact

def move_off_starts(scope, rng, put):
    """Module docstring: scales off 1, the hyper-connections' scalars x
    100, Phi and b_res drawn anew, b_pre and b_post moved, the router's
    weights x 5."""
    import numpy as np
    import paddle_tpu as fluid

    for p in fluid.default_main_program().all_parameters():
        v = np.asarray(scope.find_var(p.name))
        if p.name.endswith("_scale"):
            new = v + 0.1 * rng.standard_normal(v.shape)
        elif p.name.endswith(("_a_pre", "_a_post", "_a_res")):
            new = v * 100
        elif p.name.endswith("_phi"):
            # a token's 24 products with std 1.5 at any width
            new = rng.standard_normal(v.shape) * 1.5 / math.sqrt(v.shape[0])
        elif p.name.endswith(("_b_pre", "_b_post")):
            new = v + 0.5 * rng.standard_normal(v.shape)
        elif p.name.endswith("_b_res"):
            new = rng.standard_normal(v.shape)
        elif p.name.endswith("_router_w"):
            new = v * 5
        else:
            continue
        scope.set_var(p.name, put(new.astype(np.float32)))


def by_name(prods):
    """{parameter: (cosine, norm over the reference's, the reference
    gradient's share of the whole squared norm)} from the harness's
    products (g.r, g.g, r.r)."""
    whole = sum(p[2] for p in prods.values()) or 1.0
    out = {}
    for name, (dot, gg, rr) in sorted(prods.items()):
        out[name] = (dot / math.sqrt(gg * rr) if gg > 0 and rr > 0 else 0.0,
                     math.sqrt(gg / rr) if rr > 0 else math.inf, rr / whole)
    return out


def mhc_problems(mhc, tol):
    """The hyper-connections' parameters (by_name's triples) that the
    stated tolerances refuse BY NAME: a cosine under grad_cos_min or a norm
    further than param_norm_factor from the reference's.  The harness's
    judge skips a parameter under 1e-4 of the gradient's squared norm,
    which these are; here one is skipped under 1e-10 (the first sublayer's
    stream-to-stream map: its streams are four copies, which any H_res
    whose rows add up to 1 leaves as they are, and what is left of that
    gradient is rounding)."""
    out = []
    for name, (cos, ratio, share) in sorted(mhc.items()):
        if share < 1e-10:
            continue
        far = max(ratio, 1.0 / ratio) if ratio > 0 else math.inf
        if not cos >= tol["grad_cos_min"] or \
                not far <= tol["param_norm_factor"]:
            out.append(f"{name}: cosine {cos:.5f}, norm x {ratio:.4f}")
    return out


def listing(wrong, prods, first, batch, fetched):
    """What a reading says beside the harness's four numbers: the logits'
    and every named gradient's cosine and relative norm, the
    hyper-connections' parameters apart.  `mhc_refused_by_name` is a
    listing, no verdict: at the real size the exact program's scalars read
    norms 2-31% off by name (PERF.md 6, PR 50)."""
    import jax
    import jax.numpy as jnp

    cfg, mod = first.cell.config, mutant(wrong)

    @jax.jit
    def logits(params, tokens):
        with jax.default_matmul_precision("highest"):
            p = {k: v.astype(jnp.float32) for k, v in params.items()}
            return jax.vmap(lambda t: mod._logits(p, t, cfg))(tokens)

    def f32(x):
        return jnp.ravel(x).astype(jnp.float32)

    ours, theirs = f32(fetched["logits"]), f32(logits(
        first.params, batch[first.spec.feed_names[0]]))
    cos, ratio, _ = by_name({"logits": tuple(float(jnp.vdot(a, b)) for a, b
                                             in ((ours, theirs), (ours, ours),
                                                 (theirs, theirs)))})["logits"]
    names = by_name(prods)
    mhc = {k: v for k, v in names.items() if k.endswith(MHC)}
    return {"mhc_refused_by_name": mhc_problems(mhc, cfg["reference"]),
            "logits_cos": cos, "logits_norm_ratio": ratio, "mhc": mhc,
            "others": {k: v for k, v in names.items()
                       if not k.endswith(MHC)}}


if __name__ == "__main__":
    import mellum_reference_probe

    sys.exit(mellum_reference_probe.main(
        CELL, MUTANTS, lambda name: mutant(name).loss_and_grad,
        move_off_starts, __doc__, "xing_reference_probe",
        controls={"maps_bf16": maps_bf16()}, fetch=("logits",),
        listing=listing))

"""kernels/cca_mix.py (the op compressed_conv_qkv's arithmetic as a Pallas
kernel pair over tiles of rows) in the Pallas interpreter on the CPU: its
three outputs and eight gradients against ops/attention_ops.py
::compressed_conv_mix, the jax.numpy form; the first tile's rule and a tile
boundary; a shape that does not tile; a kernel-shaped layer built through
models/compressed_decoder.py against the plain reference
benchmark/configs/zaya1-8b.reference.py, with the mutants of the mixing
refused ON THE KERNEL PATH; and the tiny step as the v5e's compiler leaves
it (chip-less): the kernels a layer, nothing of XLA's old passes in the
scope."""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
if os.path.join(REPO, "tests") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "tests"))

import paddle_tpu as fluid
from paddle_tpu import models
from paddle_tpu.core import amp
from paddle_tpu.kernels import cca_mix, engine
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.attention_ops import compressed_conv_mix

import test_compressed_decoder as tiny_decoder

BASE = 5e6
NAMES = ("q", "k", "v", "a_w", "a_b", "b_w", "b_b", "tau")
# S of three tiles of 32 rows (the interpreter's seconds are the rows';
# 128-row tiles cost 7-13 s a case, PR 54), ISSUE 45's heads first
CASES = {
    "h8_on_g2": dict(),
    "three_taps": dict(H=4, k0=3, k1=3),
    "h_equals_g": dict(H=2, G=2, B=2),
    "one_kv_head_whole_rotary": dict(H=4, G=1, rotary_dim=128),
    "one_tap_and_four": dict(H=3, G=3, k0=1, k1=4, S=64),
}


def _inputs(seed=0, B=1, S=96, H=8, G=2, D=128, k0=2, k1=2,
            dtype=jnp.float32, **_):
    rng, n = np.random.RandomState(seed), H + G

    def normal(*shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    args = (normal(B, S, H * D, dtype=dtype), normal(B, S, G * D, dtype=dtype),
            normal(B, S, G * D, dtype=dtype), normal(k0, n * D, scale=0.5),
            normal(n * D, scale=0.5),
            normal(k1, n, D, D, scale=(k1 * D) ** -0.5),
            normal(n * D, scale=0.5), 1.0 + 0.3 * normal(G))
    cots = tuple(normal(B, m, S, D) for m in (H, G, G))
    return args, cots


def _site(q, k, v, a_w, a_b, b_w, b_b, tau, H, G, rotary_dim, base,
          force="interpret", tile=None):
    """(the three outputs, the geometry) of one site by the engine `force`
    names: as the op chooses it (kernels/engine.py::site), without the
    span."""
    args = (q, k, v, a_w, a_b, b_w, b_b, tau)
    S = q.shape[1]
    geo = engine.tiles_or_none(force, None, lambda: cca_mix.plan(
        S, H, G, q.shape[2] // H, a_w.shape[0], b_w.shape[0], rotary_dim,
        q.dtype, tile))
    if geo is None:
        return compressed_conv_mix(*args, H, G, rotary_dim, base), None
    freq = tuple(attention_ops._inv_freq(rotary_dim, base))
    return cca_mix.cca_mix(*args, geo, freq, force == "interpret"), geo


def _both(args, cots, H=8, G=2, rotary_dim=64, tile=32, force="interpret",
          kernel=True, plain=True, **_):
    """(outputs, gradients, geometry) of the kernel pair and (outputs,
    gradients) of the jax.numpy form, under one loss (or one of the
    two)."""
    geos = []

    def kernels(*xs):
        outs, geo = _site(*xs, H, G, rotary_dim, BASE, force=force,
                          tile=tile)
        geos.append(geo)
        return outs

    def jnp_form(*xs):
        return compressed_conv_mix(*xs, H, G, rotary_dim, BASE)

    def run(fn):
        def loss(*xs):
            outs = fn(*xs)
            return sum(jnp.sum(o.astype(jnp.float32) * c)
                       for o, c in zip(outs, cots)), outs
        (_, outs), grads = jax.jit(jax.value_and_grad(
            loss, argnums=tuple(range(8)), has_aux=True))(*args)
        return outs, grads

    if not plain:
        return run(kernels) + (geos[0],)
    if not kernel:
        return run(jnp_form)
    return run(kernels) + (geos[0],), run(jnp_form)


def _close(got, want, tol, what):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape, what
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * float(np.abs(want).max()),
                               err_msg=what)


@pytest.mark.parametrize("case", sorted(CASES))
def test_outputs_and_all_eight_gradients_are_the_jnp_forms(case):
    """fp32 in, to fp32 rounding: q^, k^, v and the gradients of q~, k~,
    v~, both convolutions' weights and biases and tau."""
    args, cots = _inputs(**CASES[case])
    (outs, grads, geo), (want, want_grads) = _both(args, cots, **CASES[case])
    assert geo is not None and geo.fwd_tile == geo.bwd_tile == 32
    assert args[0].shape[1] // geo.fwd_tile >= 2
    for name, g, w in zip(("q^", "k^", "v"), outs, want):
        _close(g, w, 1e-5, f"{case}: {name}")
    for name, g, w in zip(NAMES, grads, want_grads):
        assert np.abs(np.asarray(w)).max() > 0, name
        _close(g, w, 2e-5, f"{case}: d{name}")


def test_bf16_inputs_on_the_amp_tier_to_the_tiers_tolerance():
    """bf16 q~, k~, v~ under the keep tier, as the chip's step has them:
    the kernels' grouped product on bf16 operands, bf16 out.  Held to the
    jax.numpy form of the same bf16 inputs with fp32 products (XLA's CPU
    backend has no batched bf16 x bf16 = fp32 product to run the form's own
    tier with; tools/cca_mix_probe.py compares the two engines on the
    chip, tier against tier)."""
    args, cots = _inputs(dtype=jnp.bfloat16)
    amp.enable_amp("bfloat16", keep_output=True)
    try:
        outs, grads, geo = _both(args, cots, plain=False)
    finally:
        amp.reset_amp()
    want, want_grads = _both(args, cots, kernel=False)
    assert geo.halo == 16 and all(o.dtype == jnp.bfloat16 for o in outs)
    for name, g, w in zip(("q^", "k^", "v"), outs, want):
        _close(g, w, 2 ** -6, name)
    for name, g, w in zip(NAMES, grads, want_grads):
        assert g.dtype == w.dtype
        _close(g, w, 2e-2, f"d{name}")


def test_a_tile_boundary_row_tile_is_the_untiled_result():
    """Three tiles of 32 rows against ONE tile of 96: the rows a tile
    takes from the one before it (and, backward, hands it) are the rows a
    whole sequence has there."""
    args, cots = _inputs(seed=1)
    outs, grads, geo = _both(args, cots, tile=32, plain=False)
    whole, whole_grads, one = _both(args, cots, tile=96, plain=False)
    assert (geo.fwd_tile, one.fwd_tile) == (32, 96)
    for name, g, w in zip(("q^", "k^", "v"), outs, whole):
        np.testing.assert_array_equal(g[:, :, 31:34], w[:, :, 31:34],
                                      err_msg=name)
        _close(g, w, 1e-6, name)
    for name, g, w in zip(NAMES, grads, whole_grads):
        _close(g, w, 2e-5, f"d{name}")


def test_the_first_tiles_rule_a_bias_not_zero_before_position_0():
    """What convolution B reads before position 0 is A's output on zeros,
    its bias.  Held two ways: to the reference's own latent
    (benchmark/configs/zaya1-8b.reference.py::_latent, identity
    projections), whose row 0 moves when the rule is zero instead; and to
    the kernel itself on the sequence behind a tile of zero rows, where
    the rows before are A on real zeros (the features past `rotary_dim`,
    which no position turns)."""
    H, G, D, S, rotary_dim = 4, 2, 128, 64, 64
    args, _ = _inputs(seed=2, H=H, G=G, S=S)
    q, k, v, a_w, a_b, b_w, b_b, tau = args
    outs, geo = _site(*args, H, G, rotary_dim, 100.0, tile=32)
    assert geo is not None
    lq, lk = H * D, G * D
    eye = jnp.eye(lq + 2 * lk)
    params = {"x_q_w": eye[:, :lq], "x_k_w": eye[:, lq:lq + lk],
              "x_v_w": eye[:, lq + lk:], "x_conv_a_w": a_w, "x_conv_a_b": a_b,
              "x_conv_b_w": b_w, "x_conv_b_b": b_b, "x_tau": tau}
    cfg = {"num_attention_heads": H, "num_key_value_heads": G,
           "cca_time0": 2, "cca_time1": 2, "rope_parameters": {"hybrid": {
               "partial_rotary_factor": rotary_dim / D, "rope_theta": 100.0}}}
    ref = tiny_decoder._reference()
    x = jnp.concatenate([q, k, v], -1)[0]
    with jax.default_matmul_precision("highest"):
        want = ref._latent(params, x, "x", cfg)
        zero_before = ref._latent({**params, "x_conv_a_b": 0 * a_b}, x, "x",
                                  cfg)
    share = H // G
    for got, w, z in zip(outs, want, zero_before):
        w = w if w.shape[0] == got.shape[1] else w[::share]
        _close(got[0], w, 1e-4, "the reference's latent")
    assert np.abs(np.asarray(outs[0][0, :, 0] - zero_before[0][:, 0])).max() \
        > 1e-2
    # behind a tile of zeros: A's output on zeros is what B reads there
    pad = jnp.zeros((1, 32, 1), jnp.float32)
    behind, _ = _site(
        *(jnp.concatenate([pad * t[:, :1], t], 1) for t in (q, k, v)),
        a_w, a_b, b_w, b_b, tau, H, G, rotary_dim, 100.0, tile=32)
    for got, late in zip(outs[:2], behind[:2]):
        _close(got[..., rotary_dim:], late[:, :, 32:, rotary_dim:], 1e-5,
               "behind a tile of zeros")


@pytest.mark.parametrize("why, over", [
    ("head of 64", dict(D=64)), ("S 200", dict(S=200)),
    ("five rows of reach", dict(k0=9, k1=2)), ("force jax", dict(force="jax")),
    ("the CPU's auto", dict(force="auto"))])
def test_a_shape_that_does_not_tile_runs_the_jnp_form(why, over):
    sizes = {**dict(H=4, G=2, S=256), **over}
    args, cots = _inputs(**sizes)
    call = {k: v for k, v in sizes.items() if k in ("H", "G", "rotary_dim",
                                                    "force")}
    (outs, grads, geo), (want, want_grads) = _both(
        args, cots, tile=None, **call)
    assert geo is None, why
    for g, w in zip(outs + grads, want + want_grads):
        np.testing.assert_array_equal(g, w)


def test_plan_reads_the_tile_from_the_shape_and_the_vmem_it_needs():
    """The cell's shape takes the widest tile whose working set fits; a
    sequence that only a narrower tile divides takes that; more heads'
    working set narrows the backward's first; both dtypes' halo is one
    aligned block."""
    cell = cca_mix.plan(16384, 8, 2, 128, 2, 2, 64, jnp.bfloat16)
    assert cell == cca_mix.Geometry(8, 2, 128, 2, 2, 64, 16, 256, 256,
                                    "float32")
    assert cca_mix.plan(384, 8, 2, 128, 2, 2, 64, jnp.float32) \
        == cca_mix.Geometry(8, 2, 128, 2, 2, 64, 8, 128, 128, "float32")
    for tile, backward in ((cell.fwd_tile, False), (cell.bwd_tile, True)):
        assert cca_mix.working_set_bytes(
            tile, 16, 8, 2, 128, 2, jnp.bfloat16, backward) \
            <= engine.PLAN_VMEM_BUDGET
    wide = cca_mix.plan(16384, 32, 8, 128, 2, 2, 64, jnp.bfloat16)
    assert wide is None or wide.bwd_tile <= wide.fwd_tile < cell.fwd_tile, wide
    assert cca_mix.plan(16384, 8, 2, 128, 2, 2, 64, jnp.bfloat16,
                        tile=100) is None
    q, k, v = (jnp.zeros((1, 16384, w), jnp.bfloat16)
               for w in (1024, 256, 256))
    once = 16384 * 1536 * 2
    assert cca_mix.moved_bytes(q, k, v, False) == 5 * once
    assert cca_mix.moved_bytes(q, k, v, True) == 7 * once


# ---------------------------------------------------------------------------
# a kernel-shaped layer through models/compressed_decoder.py
# ---------------------------------------------------------------------------
KERNEL_SHAPED = dict(max_length=256, head_dim=128, rotary_dim=64, n_layer=2,
                     expert_offset=0, experts_held=8)
MIX_MUTANTS = ("taps_swapped", "qk_mean_left_out", "value_unshifted",
               "whole_head_rotary", "tau_left_out")


@pytest.fixture(scope="module")
def kernel_step():
    """tests/test_compressed_decoder.py's tiny model at a head of 128 and
    S 256, one forward-backward pass through the Executor with the op's
    engine turned to the interpreted kernels (the test steers; the program
    has no option for it)."""
    geos = []

    def interpreted(name, fields, mesh, plan, kernels, fallback, **fixed):
        def noting(geo, interpret):
            geos.append(geo)
            return kernels(geo, interpret)
        return site(name, fields, mesh, plan, noting, fallback,
                    force="interpret", **fixed)

    site = engine.site
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine, "site", interpreted)
        step = tiny_decoder._build(**KERNEL_SHAPED)
    return step, geos


def test_the_kernel_shaped_layer_runs_the_kernels(kernel_step):
    _, geos = kernel_step
    assert geos and all(
        g == cca_mix.Geometry(4, 2, 128, 2, 2, 64, 8, 256, 256, "float32")
        for g in geos)


def test_the_kernel_shaped_program_against_the_plain_reference(kernel_step):
    """Loss and every parameter's gradient, the convolutions' and tau's
    among them, as tests/test_compressed_decoder.py holds the tiny one."""
    (spec, params, batch, grads, loss), _ = kernel_step
    ref_loss, ref_grads = tiny_decoder._reference_loss_and_grad(
        spec, params, batch, grads)
    assert loss == pytest.approx(ref_loss, rel=tiny_decoder.RTOL)
    assert set(grads) == set(ref_grads)
    for name in sorted(ref_grads):
        if "_attn_conv_" in name or name.endswith("_attn_tau"):
            assert np.abs(ref_grads[name]).max() > 0, name
        scale = max(np.abs(ref_grads[name]).max(), 1.0)
        np.testing.assert_allclose(
            grads[name], ref_grads[name], rtol=tiny_decoder.RTOL,
            atol=tiny_decoder.ATOL * scale, err_msg=name)


@pytest.mark.parametrize("name", (None,) + MIX_MUTANTS)
def test_the_reference_refuses_each_mutant_of_the_mixing_on_the_kernel_path(
        kernel_step, name):
    """Inside the rehearsal's tolerances against the reference, outside at
    least one against each mutant of what the kernels compute."""
    problems, found = tiny_decoder._refused(kernel_step[0], name)
    assert bool(problems) == (name is not None), (name, found)


# ---------------------------------------------------------------------------
# the step the chip's compiler leaves (chip-less)
# ---------------------------------------------------------------------------
CELL_SHAPED = dict(vocab_size=64, max_length=512, n_layer=2, d_model=64,
                   n_head=4, n_kv_head=2, head_dim=128, rotary_dim=64,
                   router_dim=16, n_routed_experts=8, experts_held=4,
                   d_expert=64)


@pytest.fixture(scope="module")
def compiled_step():
    """zaya-train-cca16k's step at tiny widths, compiled for one v5e as the
    chip's jit compiles it (one device, no mesh)."""
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.core import aot_tpu

    try:
        one = SingleDeviceSharding(aot_tpu.tpu_topology().devices[0])
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e topology can be described here: {e}")
    fluid.reset_default_env()
    try:
        spec = models.compressed_decoder(
            models.CompressedDecoderConfig(**CELL_SHAPED))
        fluid.optimizer.AdamOptimizer(learning_rate=1e-3).minimize(spec.loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        with fluid.flags.tpu_trace_scope(True):
            compiled, *args = exe.capture_program(
                feed=spec.synthetic_batch(1, 0), fetch_list=[spec.loss])
            return jax.jit(
                compiled.raw_fn, in_shardings=one, out_shardings=one,
                donate_argnums=(1,)).trace(*jax.tree_util.tree_map(
                    aot_tpu._abstract, tuple(args))).lower().compile(
                        ).as_text()
    finally:
        fluid.reset_default_env()


def test_a_layer_holds_one_forward_one_recomputed_and_one_backward_kernel(
        compiled_step):
    calls = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="'
        r'([^"]*/cca\.mix/[^"]*pallas_call)"', compiled_step)
    layers_ = CELL_SHAPED["n_layer"]
    again = [op for op in calls if "rematted_computation" in op]
    backward = [op for op in calls if "transpose(" in op and op not in again]
    forward = [op for op in calls if "transpose(" not in op]
    assert len(forward) == len(backward) == layers_, calls
    assert len(again) <= layers_ and len(calls) <= 3 * layers_, calls


def test_nothing_of_the_jnp_forms_passes_is_left_in_the_scope(compiled_step):
    """Inside `cca.mix` no `reduce-precision` (what a rematerialised unit
    puts on a saved float: the residuals are the op's inputs, saved by
    nobody), and no fp32 value of S rows by a latent's width is made,
    copied between layouts or otherwise: what crosses HBM is the op's
    bf16 inputs and outputs."""
    S = CELL_SHAPED["max_length"]
    D, H, G = (CELL_SHAPED[k] for k in ("head_dim", "n_head", "n_kv_head"))
    in_scope = [line for line in compiled_step.splitlines()
                if "/cca.mix/" in line and " = " in line]
    assert in_scope
    assert not [line for line in in_scope if "reduce-precision(" in line]
    big = re.compile(r"= f32\[([\d,]+)\]")
    for line in in_scope:
        m = big.search(line)
        if m is None or " parameter(" in line:
            continue
        dims = [int(d) for d in m.group(1).split(",")]
        rows_by_latent = S in dims and int(np.prod(dims)) >= S * G * D
        planes = dims == [S, D]      # the rotary turn's cos and sin
        assert planes or not rows_by_latent, line

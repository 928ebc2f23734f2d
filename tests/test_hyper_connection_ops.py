"""The ops of manifold-constrained hyper-connections
(ops/hyper_connection_ops.py: mhc_streams, mhc_maps_read, mhc_maps,
mhc_read, mhc_write) against the equations as the plain reference of xing4.0-29b-a4b writes them
(a token's maps [n] and [n, n], its own Sinkhorn loop), forward and every
gradient by name; what Sinkhorn-Knopp leaves after 20 iterations and after
2; the clamp; the layout of the maps; the span; and latent_attention's
`yarn` and `scale` against a direct softmax."""

import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from benchmark.harness import manifest
from paddle_tpu import layers, observability
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops import hyper_connection_ops as hc
from paddle_tpu.param_attr import ParamAttr
from paddle_tpu.initializer import NumpyArrayInitializer

REFERENCE = manifest.load_py(os.path.join(
    REPO, "benchmark", "configs", "xing4.0-29b-a4b.reference.py"))
NAMES = ("phi", "a_pre", "a_post", "a_res", "b_pre", "b_post", "b_res")
RTOL, ATOL = 2e-4, 2e-5


def _ref_cfg(iters=20, clamp=(-30.0, 30.0), eps=1e-6):
    return {"rms_norm_eps": eps, "hc_sinkhorn_iters": iters, "hc_eps": 1e-6,
            "mhc_h_res_clamp_min": clamp[0], "mhc_h_res_clamp_max": clamp[1]}


def _values(B, S, n, C, seed, a=1.5, phi=0.5):
    """Streams that differ from one another and parameters large enough
    that every map moves with the data: at the model's start (a 0.01, Phi
    N(0, 0.02), H_res ~ identity) the maps are their biases and a mistake
    in Phi's or a scalar's gradient could not show."""
    r = np.random.RandomState(seed)
    p = {"phi": phi * r.randn(n * C, 2 * n + n * n),
         "a_pre": [a], "a_post": [-a], "a_res": [0.8 * a],
         "b_pre": 0.5 * r.randn(n), "b_post": 0.5 * r.randn(n),
         "b_res": 2.0 * np.eye(n) + 0.5 * r.randn(n, n)}
    x = r.randn(B, S, n, C) * (1.0 + np.arange(n)[:, None])
    return ({k: np.asarray(v, np.float32) for k, v in p.items()},
            x.astype(np.float32))


def _reference_sublayer(p, x, w_f, cfg):
    """X' [B, S, n, C] of one hyper-connected sublayer y = tanh(x_in W_f),
    by the reference's functions, a token's maps a row."""
    B, S, n, C = x.shape
    named = {"s_" + k: v for k, v in p.items()}
    flat = x.reshape(B * S, n, C)
    maps = REFERENCE._maps(named, flat, "s", cfg)
    y = jnp.tanh(REFERENCE._read(flat, maps) @ w_f)
    return REFERENCE._write(flat, maps, y).reshape(x.shape), maps


def _program_sublayer(p, x, w_f, iters=20, fused=True):
    """(X', H, the gradient of sum(X' * weight) by name) of the same
    sublayer as a fluid program through the Executor: its maps and its read
    as the one op `mhc_maps_read` (`fused`, the decoder's form) or as
    `mhc_maps` and `mhc_read`."""
    fluid.reset_default_env()
    B, S, n, C = x.shape
    params = {k: layers.create_parameter(
        list(v.shape), "float32", attr=ParamAttr(
            name=k, initializer=NumpyArrayInitializer(v)))
        for k, v in {**p, "x": x, "w_f": w_f}.items()}
    small = [params[k] for k in NAMES]
    if fused:
        h, x_in = layers.mhc_maps_read(params["x"], *small,
                                       sinkhorn_iters=iters)
    else:
        h = layers.mhc_maps(params["x"], *small, sinkhorn_iters=iters)
        x_in = layers.mhc_read(params["x"], h)
    y = layers.tanh(layers.matmul(x_in, params["w_f"]))
    out = layers.mhc_write(params["x"], h, y)
    weight = np.random.RandomState(7).randn(*x.shape).astype(np.float32)
    loss = layers.reduce_sum(layers.elementwise_mul(
        out, layers.assign(weight)))
    pairs = fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got = exe.run(fetch_list=[out, h] + [g for _, g in pairs])
    grads = {p_.name: np.asarray(g) for (p_, _), g in zip(pairs, got[2:])}
    return np.asarray(got[0]), np.asarray(got[1]), grads, weight


@pytest.mark.parametrize("B,S,n,C,fused", [
    (2, 6, 4, 16, True), (1, 5, 2, 8, True), (1, 3, 3, 8, True),
    (2, 6, 4, 16, False)])
def test_the_ops_are_the_equations_forward_and_every_gradient(B, S, n, C,
                                                              fused):
    """X' and the gradients of Phi, the three scalars, the three biases,
    the streams and the sublayer's weight, each by its name; the maps and
    the read as one op and as two."""
    p, x = _values(B, S, n, C, seed=n + C)
    w_f = (np.random.RandomState(3).randn(C, C) / math.sqrt(C)).astype(
        np.float32)
    out, h, grads, weight = _program_sublayer(p, x, w_f, fused=fused)

    def total(p, x, w_f):
        return jnp.sum(_reference_sublayer(p, x, w_f, _ref_cfg())[0]
                       * weight)

    want, maps = _reference_sublayer(p, x, w_f, _ref_cfg())
    np.testing.assert_allclose(out, want, rtol=RTOL, atol=ATOL)
    # the layout: rows 0:n H_pre, n:2n H_post, then H_res row by row, the
    # tokens last
    assert h.shape == (B, 2 * n + n * n, S)
    by_token = np.moveaxis(h, 1, 2).reshape(B * S, -1)
    np.testing.assert_allclose(by_token[:, :n], maps[0], rtol=RTOL)
    np.testing.assert_allclose(by_token[:, n:2 * n], maps[1], rtol=RTOL)
    np.testing.assert_allclose(by_token[:, 2 * n:].reshape(-1, n, n),
                               maps[2], rtol=RTOL, atol=ATOL)
    ref = dict(zip(("p", "x", "w_f"), jax.jit(jax.grad(
        total, argnums=(0, 1, 2)))(
            {k: jnp.asarray(v) for k, v in p.items()}, jnp.asarray(x),
            jnp.asarray(w_f))))
    ref = {**ref["p"], "x": ref["x"], "w_f": ref["w_f"]}
    assert set(grads) == set(NAMES) | {"x", "w_f"}
    for name in sorted(grads):
        r = np.asarray(ref[name])
        assert np.abs(r).max() > 1e-3, f"{name}: no gradient to hold"
        np.testing.assert_allclose(grads[name], r, rtol=RTOL,
                                   atol=ATOL * max(np.abs(r).max(), 1.0),
                                   err_msg=name)


def _h_res(p, x, iters, **kw):
    B, S, n, C = x.shape
    h = hc.maps(jnp.asarray(x), *(jnp.asarray(p[k]) for k in NAMES),
                1e-6, 1e-6, iters, kw.pop("clamp", (-30.0, 30.0)), **kw)
    return np.moveaxis(np.asarray(h, np.float32)[:, 2 * n:], 1, 2).reshape(
        B * S, n, n)


def test_h_res_is_doubly_stochastic_after_20_iterations_and_not_after_2():
    """Rows and columns add up to 1 to 1e-5 after the published 20
    iterations; after 2 the columns are off by more than 1e-3 (a loop cut
    short fails here), the rows, normalised last, still at 1."""
    p, x = _values(2, 8, 4, 16, seed=1, a=0.3, phi=0.1)
    full, short = _h_res(p, x, 20), _h_res(p, x, 2)
    assert np.all(full > 0)
    np.testing.assert_allclose(full.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(full.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(short.sum(-1), 1.0, atol=1e-5)
    assert np.abs(short.sum(-2) - 1.0).max() > 1e-3


def test_the_clamp_bounds_the_exponent():
    """With a_res large Hres~ runs to +-1000: exp overflows unclamped (the
    maps come out nan), and under the published clamp of +-30 every map is
    finite, H_res still doubly stochastic, and equal to the maps of the
    clipped logits."""
    p, x = _values(1, 8, 4, 16, seed=2)
    p["a_res"] = np.asarray([400.0], np.float32)
    assert not np.isfinite(_h_res(p, x, 20, clamp=(-1e9, 1e9))).all()
    clamped = _h_res(p, x, 20)
    assert np.isfinite(clamped).all()
    np.testing.assert_allclose(clamped.sum(-1), 1.0, atol=1e-5)
    flat = x.reshape(8, 4, 16)
    want = REFERENCE._maps({"s_" + k: jnp.asarray(v) for k, v in p.items()},
                           jnp.asarray(flat), "s", _ref_cfg())[2]
    np.testing.assert_allclose(clamped, want, rtol=RTOL, atol=ATOL)
    # a narrower clamp is another map
    assert np.abs(_h_res(p, x, 20, clamp=(-3.0, 3.0)) - clamped).max() > 0.01


def test_maps_in_bf16_are_not_the_maps():
    """The control the probe runs on the chip: every value of the maps in
    bf16 leaves H_res's rows and columns off 1 by more than 1e-3 and the
    maps off the fp32 ones by more than a percent of their size."""
    p, x = _values(2, 8, 4, 16, seed=4)
    exact, half = _h_res(p, x, 20), _h_res(p, x, 20, dtype=jnp.bfloat16)
    assert max(np.abs(half.sum(-1) - 1).max(),
               np.abs(half.sum(-2) - 1).max()) > 1e-3
    assert np.abs(half - exact).max() > 0.01 * np.abs(exact).max()


def test_the_streams_start_as_copies_and_the_span_says_what_a_site_moves():
    """mhc_streams copies; one mhc.lower a mhc_maps_read or mhc_maps op
    with the streams, the iterations, one sublayer and moved_bytes = 5
    passes over the streams + 4 over a [T, C] value + Phi once; the fused
    op's H is mhc_maps' and its x_in is mhc_read's under it."""
    fluid.flags._VALUES["FLAGS_observability"] = True
    try:
        fluid.reset_default_env()
        observability.reset()
        p, x = _values(2, 6, 4, 16, seed=5)
        e = layers.assign(x[:, :, 0])
        streams = layers.mhc_streams(e, 4)
        small = [layers.assign(p[k]) for k in NAMES]
        h = layers.mhc_maps(streams, *small, sinkhorn_iters=7)
        both = layers.mhc_maps_read(streams, *small, sinkhorn_iters=7)
        exe = fluid.Executor(fluid.CPUPlace())
        got, maps, x_in, maps_too, x_in_too = exe.run(fetch_list=[
            streams, h, layers.mhc_read(streams, h), *both])
        spans = [dict(s.args) for s in
                 observability.default_tracer().spans()
                 if s.name == "mhc.lower"]
    finally:
        fluid.flags._VALUES["FLAGS_observability"] = False
        observability.reset()
    assert got.shape == (2, 6, 4, 16)
    for j in range(4):
        np.testing.assert_array_equal(got[:, :, j], x[:, :, 0])
    assert maps.shape == (2, 24, 6) and x_in.shape == (2, 6, 16)
    np.testing.assert_array_equal(maps_too, maps)
    np.testing.assert_array_equal(x_in_too, x_in)
    assert spans == [{"streams": 4, "sinkhorn_iters": 7, "sublayers": 1,
                      "moved_bytes": (5 * 4 + 4) * 12 * 16 * 4
                      + 64 * 24 * 4}] * 2
    assert hc.moved_bytes(4096, 4, 3584, 2, 14336 * 24 * 4) == 706019328


# ---------------------------------------------------------------------------
# latent_attention under YaRN and a softmax scale of its own
# ---------------------------------------------------------------------------
MLA = dict(n_head=2, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
YARN = {"factor": 64.0, "original_length": 16.0, "beta_fast": 32.0,
        "beta_slow": 1.0, "attention_factor": 1.0}


def _mla_inputs(seed=0, B=2, S=48, rank=24):
    r = np.random.RandomState(seed)
    return tuple(np.asarray(a, np.float32) for a in (
        r.randn(B, S, 2 * 24), r.randn(B, S, rank), r.randn(B, S, 8),
        r.randn(rank, 2 * 32) * 0.3))


def _run_mla(args, **kw):
    fluid.reset_default_env()
    out = layers.latent_attention(*(layers.assign(a) for a in args), **MLA,
                                  rope_base=10000.0, **kw)
    ops = [op for op in fluid.default_main_program().global_block().desc.ops
           if op.type == "latent_attention"]
    got = fluid.Executor(fluid.CPUPlace()).run(fetch_list=[out])[0]
    return np.asarray(got), ops[0].attrs


def _direct_mla(args, rope_scaling, theta=10000.0):
    """The reference's parts and softmax, from the op's inputs."""
    q, latent, k_rope, w = (jnp.asarray(a) for a in args)
    cfg = {"rope_scaling": rope_scaling, "rope_theta": theta,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8}
    outs = []
    for b in range(q.shape[0]):
        S = q.shape[1]

        def heads(t):
            return t.reshape(S, 2, -1).transpose(1, 0, 2)

        qh, kv = heads(q[b]), heads(latent[b] @ w)
        qh = jnp.concatenate(
            [qh[..., :16], REFERENCE._positions(qh[..., 16:], cfg)], -1)
        shared = jnp.broadcast_to(
            REFERENCE._positions(k_rope[b], cfg)[None], (2, S, 8))
        k = jnp.concatenate([kv[..., :16], shared], -1)
        outs.append(REFERENCE._attend(qh, k, kv[..., 16:], 0, cfg))
    return np.asarray(jnp.stack(outs))


def test_latent_attention_under_yarn_and_a_scale_is_the_direct_softmax():
    """48 positions from an original length of 16: the 4 pairs of the
    8-wide parts fall on both sides of YaRN's ramp; the softmax scale
    24^-1/2 x (0.1 ln 64 + 1)^2.  Against the reference's own frequencies
    and an explicit mask."""
    args = _mla_inputs(seed=1)
    rs = {"factor": 64, "original_max_position_embeddings": 16,
          "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
    scale = 24 ** -0.5 * (0.1 * math.log(64) + 1) ** 2
    got, attrs = _run_mla(args, yarn=YARN, scale=scale)
    assert attrs["yarn_factor"] == 64.0 and attrs["scale"] == scale
    np.testing.assert_allclose(got, _direct_mla(args, rs), rtol=RTOL,
                               atol=ATOL)
    # each of the two changes the output, and so do cos and sin times a
    # factor
    plain, plain_attrs = _run_mla(args)
    assert not any(k.startswith("yarn_") or k == "scale"
                   for k in plain_attrs)
    np.testing.assert_allclose(plain, _direct_mla(args, None), rtol=RTOL,
                               atol=ATOL)
    only_yarn, _ = _run_mla(args, yarn=YARN)
    only_scale, _ = _run_mla(args, scale=scale)
    grown, _ = _run_mla(args, yarn={**YARN, "attention_factor": 1.2},
                        scale=scale)
    for other in (plain, only_yarn, only_scale, grown):
        assert np.abs(other - got).max() > 1e-3
    rs2 = {**rs, "mscale": 3.0}      # cos, sin x mscale(3) / mscale(1)
    factor = (0.3 * math.log(64) + 1) / (0.1 * math.log(64) + 1)
    np.testing.assert_allclose(
        _run_mla(args, yarn={**YARN, "attention_factor": factor},
                 scale=scale)[0],
        _direct_mla(args, rs2), rtol=RTOL, atol=ATOL)


def test_latent_attention_without_the_new_arguments_lowers_as_it_did():
    """yarn None and scale None append the op moonlight-16b-a3b and
    kimi-linear-48b-a3b have: no new attribute, and the lowering of an op
    without them is the lowering with the default scale spelled out."""
    args = _mla_inputs(seed=2)
    _, attrs = _run_mla(args, yarn=None, scale=None)
    assert sorted(attrs) == sorted(_run_mla(args)[1])
    def lowered(extra):
        ctx = types.SimpleNamespace(mesh=None, kept=0)
        return jax.jit(lambda q, latent, k_rope, w: (
            attention_ops._latent_attention(
                ctx, {"Q": [q], "Latent": [latent], "KRope": [k_rope],
                      "KvUpW": [w]},
                {**MLA, "rope_base": 50000.0, **extra})["Out"][0])).lower(
                    *args).as_text()

    assert lowered({}) == lowered({"scale": 24 ** -0.5})
    assert lowered({}) != lowered({"scale": 0.3})

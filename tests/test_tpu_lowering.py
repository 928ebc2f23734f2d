"""Chip-less TPU lowering gate for every pallas kernel.

Round-5 chip lesson: pallas interpret-mode tests validate numerics but
NEVER see the real TPU's Mosaic constraints — the first healthy chip
window in five rounds was half-lost to a (1, block_q) lse block that
violates the (8, 128) tile rule.  That fails CLIENT-SIDE at lowering
time, which means `jax.export` with platforms=["tpu"] reproduces it on
a CPU host with no TPU attached.

Every pallas kernel in the repo must TPU-lower here, at realistic
shapes (the flagship bench configs), including the shape that caught
that bug; and so must the conv -> batch_norm -> relu chain every ResNet
block is made of.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import flags, layers
from paddle_tpu.core import amp

# the kernels package re-exports the flash_attention FUNCTION under the
# same name as its module; go through importlib for the module itself
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")


def _tpu_lowers(fn, *args):
    """Assert fn TPU-lowers via jax.export (Mosaic runs client-side)."""
    jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


class TestFlashLowering:
    # (B, H, Sq, Sk, D): the transformer bench (256-seq), the longctx
    # bench (2048-seq), a cached-decode shape (Sq < Sk), a ragged
    # shape exercising the padding path, and ouro-train-loop4's (head 128,
    # where the plan's blocks are largest)
    SHAPES = [(16, 16, 256, 256, 64), (4, 16, 2048, 2048, 64),
              (8, 8, 128, 384, 64), (2, 4, 200, 200, 64),
              (2, 16, 2048, 2048, 128)]

    @pytest.mark.parametrize("shape", SHAPES)
    def test_forward_with_lse(self, shape):
        B, H, Sq, Sk, D = shape
        q = jax.ShapeDtypeStruct((B, H, Sq, D), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((B, H, Sk, D), jnp.bfloat16)

        def f(q, k):
            klen = jnp.full((B,), Sk, jnp.float32)
            return fa._pallas_flash(q, k, k, klen, causal=True,
                                    scale=0.125)

        _tpu_lowers(f, q, k)

    def test_forward_no_lse(self):
        B, H, S, D = 16, 16, 256, 64
        q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)

        def f(q):
            klen = jnp.full((B,), S, jnp.float32)
            return fa._pallas_flash(q, q, q, klen, causal=False,
                                    scale=0.125, need_lse=False)[0]

        _tpu_lowers(f, q)

    @pytest.mark.parametrize("shape", [(16, 16, 256, 256, 64),
                                       (4, 16, 2048, 2048, 64),
                                       (2, 16, 2048, 2048, 128),
                                       (2, 4, 200, 200, 64)])
    def test_backward_kernel(self, shape):
        B, H, Sq, Sk, D = shape
        q = jax.ShapeDtypeStruct((B, H, Sq, D), jnp.bfloat16)

        def f(q):
            klen = jnp.full((B,), Sk, jnp.float32)
            out, lse = fa._pallas_flash(q, q, q, klen, causal=True,
                                        scale=0.125)
            return fa._pallas_flash_bwd(q, q, q, klen, out, lse, out,
                                        causal=True, scale=0.125)

        _tpu_lowers(f, q)

    def test_packed_residuals_no_lane_broadcast(self):
        """lse/dvec ride the packed [B*H, nqb, bq] layout: the lowered
        module must contain NO [B*H, Sqp, 128] fp32 operand (the round-5
        layout broadcast every per-row scalar across 128 lanes —
        ~67 MB/tensor at this longcontext shape, 128x the payload)."""
        B, H, Sq, Sk, D = 4, 16, 2048, 2048, 64
        q = jax.ShapeDtypeStruct((B, H, Sq, D), jnp.bfloat16)

        def f(q):
            klen = jnp.full((B,), Sk, jnp.float32)
            out, lse = fa._pallas_flash(q, q, q, klen, causal=True,
                                        scale=0.125)
            return fa._pallas_flash_bwd(q, q, q, klen, out, lse, out,
                                        causal=True, scale=0.125)

        exp = jax.export.export(jax.jit(f), platforms=["tpu"])(q)
        txt = exp.mlir_module()
        assert f"tensor<{B * H}x{Sq}x128xf32>" not in txt
        # the packed residual layout is what flows instead, one row a
        # q-block: of the forward's plan where it leaves the forward, of
        # the backward's own where it enters the backward
        for bq in (fa._plan_blocks(Sq, Sk, D, jnp.bfloat16, True, True)[0],
                   fa._plan_bwd_blocks(Sq, Sk, D, jnp.bfloat16, True)[0]):
            assert bq > 128 and Sq % bq == 0
            assert f"tensor<{B * H}x{Sq // bq}x{bq}xf32>" in txt


class TestConvBnChain:
    """The one formulation of a ResNet block (models/resnet.py): conv2d ->
    batch_norm [-> elementwise_add] -> relu built through `layers`, at
    ResNet-50's block shapes."""

    # (N, H, W, C, F, K, stride, residual): the stage transitions at
    # stride 2 among them, the 7x7 stride-2 stem, a 1x1 stride-2
    # projection shortcut and a bottleneck's widening 1x1 tail
    CASES = [
        (8, 56, 56, 64, 64, 1, 1, False),
        (8, 56, 56, 64, 64, 3, 1, True),
        (8, 56, 56, 128, 128, 3, 2, False),
        (8, 28, 28, 256, 256, 3, 2, False),
        (8, 7, 7, 512, 512, 3, 1, True),
        (8, 224, 224, 3, 64, 7, 2, False),
        (8, 56, 56, 256, 512, 1, 2, False),
        (8, 56, 56, 64, 256, 1, 1, True),
        (8, 14, 14, 512, 512, 3, 2, False),
    ]

    @staticmethod
    def _build(case, batch):
        """(loss, feed, [(parameter, gradient)]) of the chain, its loss the
        mean of the output under a fixed random weighting."""
        _, H, W, C, F, K, s, res = case
        Ho = -(-H // s)
        fluid.reset_default_env()
        fluid.default_startup_program().random_seed = 17
        x = layers.data("x", [C, H, W], dtype="float32")
        m = layers.data("m", [F, Ho, Ho], dtype="float32")
        conv = layers.conv2d(x, num_filters=F, filter_size=K, stride=s,
                             padding=(K - 1) // 2, bias_attr=False)
        out = layers.batch_norm(conv, act=None if res else "relu")
        rng = np.random.RandomState(K * 100 + C)
        feed = {"x": rng.randn(batch, C, H, W).astype("float32"),
                "m": rng.rand(batch, F, Ho, Ho).astype("float32")}
        if res:
            z = layers.data("z", [F, Ho, Ho], dtype="float32")
            out = layers.elementwise_add(z, out, act="relu")
            feed["z"] = rng.randn(batch, F, Ho, Ho).astype("float32")
        loss = layers.mean(layers.elementwise_mul(out, m))
        return loss, feed, fluid.append_backward(loss)

    @pytest.mark.parametrize("case", CASES)
    def test_lowers_for_tpu_in_nhwc(self, case):
        """The chip program of the chain (NHWC and keep-bf16 resolved by
        the TPU trace scope, nothing set) lowers with no TPU attached, its
        convolutions channels-last on bf16 operands."""
        loss, feed, grads = self._build(case, batch=case[0])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        with flags.tpu_trace_scope(True):
            compiled, feed_vals, state_vals, rng = exe.capture_program(
                feed=feed, fetch_list=[loss] + [g for _, g in grads])
            txt = jax.export.export(compiled.fn, platforms=["tpu"])(
                tuple(feed_vals), tuple(state_vals), rng).mlir_module()
        convs = [ln for ln in txt.splitlines()
                 if "stablehlo.convolution" in ln]
        assert len(convs) == 2      # forward and the filter's gradient
        N, H, W, C = case[:4]
        assert "[b, 0, 1, f]x[0, 1, i, o]->[b, 0, 1, f]" in convs[0]
        assert f"(tensor<{N}x{H}x{W}x{C}xbf16>" in convs[0]
        for ln in convs:
            assert "xf32>" not in ln.split(" : (")[1], ln

    @pytest.mark.parametrize("case", CASES)
    def test_nhwc_bf16_matches_nchw_fp32(self, case):
        """Loss and every parameter gradient under the chip's tier (NHWC,
        bf16 kept between ops) against the reference tier (NCHW, fp32),
        batch 2, within what bf16's 8 mantissa bits allow."""
        def run(chip_tier):
            if chip_tier:
                fluid.enable_amp("bfloat16", keep_output=True)
                fluid.set_flags({"FLAGS_conv_layout": "NHWC"})
            try:
                loss, feed, grads = self._build(case, batch=2)
                exe = fluid.Executor(fluid.CPUPlace())
                exe.run(fluid.default_startup_program())
                got = exe.run(feed=feed,
                              fetch_list=[loss] + [g for _, g in grads])
                return [np.asarray(v, dtype=np.float32) for v in got]
            finally:
                amp.reset_amp()
                fluid.set_flags({"FLAGS_conv_layout": "auto"})

        ref, got = run(False), run(True)
        assert len(ref) == 4        # loss; filter, scale and bias gradients
        np.testing.assert_allclose(got[0], ref[0], rtol=2e-2, atol=2e-3)
        for r, g in zip(ref[1:], got[1:]):
            assert np.all(np.isfinite(g))
            cos = float(np.sum(r * g) / (np.linalg.norm(r)
                                         * np.linalg.norm(g) + 1e-30))
            assert cos > 0.98, cos
            assert abs(np.linalg.norm(g) / np.linalg.norm(r) - 1) < 0.05

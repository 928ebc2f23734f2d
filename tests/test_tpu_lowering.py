"""Chip-less TPU lowering gate for every pallas kernel.

Round-5 chip lesson: pallas interpret-mode tests validate numerics but
NEVER see the real TPU's Mosaic constraints — the first healthy chip
window in five rounds was half-lost to a (1, block_q) lse block that
violates the (8, 128) tile rule, and the staged conv-epilogue probe
would have burned a second window on a strided-slice lowering failure.
Both fail CLIENT-SIDE at lowering time, which means `jax.export` with
platforms=["tpu"] reproduces them on a CPU host with no TPU attached.

Every pallas kernel in the repo must TPU-lower here, at realistic
shapes (the flagship bench configs), including the shapes that caught
the two bugs above.
"""

import jax
import jax.numpy as jnp
import pytest

import importlib

# the kernels package re-exports the flash_attention FUNCTION under the
# same name as its module; go through importlib for the module itself
fa = importlib.import_module("paddle_tpu.kernels.flash_attention")
from paddle_tpu.kernels.conv_epilogue import conv_bn_act


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
                                       (4, 16, 2048, 2048, 64)])
    def test_backward_pair(self, shape):
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
        # q-block of the forward's plan
        bq, _ = fa._plan_blocks(Sq, Sk, D, jnp.bfloat16, True, True)
        assert bq > 128 and Sq % bq == 0
        assert f"tensor<{B * H}x{Sq // bq}x{bq}xf32>" in txt


class TestConvEpilogueLowering:
    # ResNet-50 block shapes (NHWC), incl. the stride-2 stage
    # transitions that Mosaic's strided-slice limitation used to kill
    CASES = [
        (8, 56, 56, 64, 64, 1, 1, False),
        (8, 56, 56, 64, 64, 3, 1, True),
        (8, 56, 56, 128, 128, 3, 2, False),
        (8, 28, 28, 256, 256, 3, 2, False),
        (8, 7, 7, 512, 512, 3, 1, True),
    ]

    @pytest.mark.parametrize("case", CASES)
    def test_conv_bn_act(self, case):
        N, H, W, C, F, K, s, res = case
        x = jax.ShapeDtypeStruct((N, H, W, C), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((K, K, C, F), jnp.bfloat16)
        g = jax.ShapeDtypeStruct((F,), jnp.float32)
        Ho = -(-H // s)
        args = (x, w, g, g)
        if res:
            args += (jax.ShapeDtypeStruct((N, Ho, Ho, F), jnp.bfloat16),)

        def f(x, w, gamma, beta, z=None):
            return conv_bn_act(x, w, gamma, beta, z, stride=s,
                               padding="SAME")

        _tpu_lowers(f, *args)

"""The share of xing4.0-29b-a4b tied to the model twice: the eight head
shares' output-map terms add up to the uncut latent attention, and the
eight expert shares' terms with the shared expert counted once add up to
the uncut expert block; each share as models/hyper_expert_decoder.py's
builder computes it, and as the plain reference computes it given the same
share."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import paddle_tpu as fluid
from paddle_tpu import layers, models

sys.path.insert(0, os.path.join(REPO, "tests"))
from test_hyper_expert_decoder import (  # noqa: E402
    TINY, _ref_cfg, hyper, probe)

SHARES, D, S = 8, 32, 40
REF = probe.mutant(None)


class _Block:
    """One block of the model's builder as a program of its own, built and
    compiled once: `run(weights)` sets its parameters by name and runs it
    on the one input."""

    def __init__(self, build, x):
        fluid.reset_default_env()
        self.out = build(layers.assign(x))
        self.program = fluid.default_main_program()
        self.scope = fluid.global_scope()
        self.exe = fluid.Executor(fluid.CPUPlace())
        self.exe.run(fluid.default_startup_program())

    def run(self, weights):
        for p in self.program.all_parameters():
            want = weights[p.name]
            assert tuple(p.shape) == want.shape, (p.name, p.shape)
            self.scope.set_var(p.name, np.asarray(want, np.float32))
        return np.asarray(self.exe.run(
            self.program, scope=self.scope, fetch_list=[self.out])[0])[0]


def _f32(weights):
    return {k: np.asarray(v, np.float32) for k, v in weights.items()}


# ---------------------------------------------------------------------------
# the heads
# ---------------------------------------------------------------------------
HEADS, QK, NV = 8, 16 + 8, 16 + 16
MLA_OVER = dict(TINY, max_length=S)


@functools.lru_cache(None)
def _mla():
    rng = np.random.RandomState(3)
    w = _f32({"l0_attn_qa_w": rng.randn(D, 16) * 0.3,
              "l0_attn_qn_scale": 1 + 0.3 * rng.randn(16),
              "l0_attn_qb_w": rng.randn(16, HEADS * QK) * 0.6,
              "l0_attn_kva_w": rng.randn(D, 24 + 8) * 0.4,
              "l0_attn_kvn_scale": 1 + 0.3 * rng.randn(24),
              "l0_attn_kvb_w": rng.randn(24, HEADS * NV) * 0.4,
              "l0_attn_o_w": rng.randn(HEADS * 16, D) * 0.3})
    return w, rng.randn(1, S, D).astype(np.float32)


def _heads(first, count):
    """The parameters of heads first .. first + count of 8: W_qb's and
    W_kvb's columns, W_o's rows; the down-maps and the norms whole."""
    w = _mla()[0]

    def cols(m, width):
        return m.reshape(m.shape[0], HEADS, width)[
            :, first:first + count].reshape(m.shape[0], -1)

    return {**w, "l0_attn_qb_w": cols(w["l0_attn_qb_w"], QK),
            "l0_attn_kvb_w": cols(w["l0_attn_kvb_w"], NV),
            "l0_attn_o_w": w["l0_attn_o_w"].reshape(HEADS, 16, D)[
                first:first + count].reshape(-1, D)}


@functools.lru_cache(None)
def _mla_program(held):
    """The model's latent attention at `held` heads of 8."""
    cfg = models.HyperExpertDecoderConfig(**{**MLA_OVER, "n_head": held})
    return _Block(lambda x: hyper._HyperBuilder(cfg).latent_attention(
        x, "l0_attn"), _mla()[1])


@functools.lru_cache(None)
def _mla_share(first, count):
    return _mla_program(count).run(_heads(first, count))


def _mla_reference(first, count):
    cfg = _ref_cfg(models.HyperExpertDecoderConfig(
        **{**MLA_OVER, "n_head": count}))
    p = {k: jnp.asarray(v) for k, v in _heads(first, count).items()}
    u = jnp.asarray(_mla()[1][0])
    k, v = REF._mla_keys(p, u, 0, "l0_attn", cfg)
    return np.asarray(REF._attend(REF._mla_queries(p, u, 0, "l0_attn", cfg),
                                  k, v, 0, cfg) @ p["l0_attn_o_w"])


@pytest.mark.parametrize("first,count", [(0, 8), (0, 1), (5, 1), (7, 1),
                                         (2, 2)])
def test_a_share_of_the_heads_is_the_reference_given_the_same_share(first,
                                                                    count):
    """The model's builder on heads first .. first + count of 8 (all of
    them: the uncut layer) against the reference on the same parameters."""
    np.testing.assert_allclose(_mla_share(first, count),
                               _mla_reference(first, count), rtol=1e-4,
                               atol=1e-5)


def test_the_eight_head_shares_add_up_to_the_uncut_latent_attention():
    """8 shares of one layer's latent attention, each as THE MODEL'S
    builder computes it on its 1 head of 8 (the output map's rows of that
    head, so its term of the sum over heads), add up to the uncut layer;
    so do four shares of 2 heads, the cell's kind."""
    whole = _mla_share(0, HEADS)
    np.testing.assert_allclose(
        sum(_mla_share(first, 1) for first in range(HEADS)), whole,
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        sum(_mla_share(first, 2) for first in (0, 2, 4, 6)), whole,
        rtol=1e-4, atol=1e-5)
    assert np.abs(_mla_share(0, 2) - whole).max() > 1e-2


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------
EXPERTS, HELD, F = 16, 2, 24
MOE_OVER = dict(TINY, max_length=S, n_routed_experts=EXPERTS, top_k=4,
                routed_scaling_factor=2.0)


@functools.lru_cache(None)
def _moe():
    rng = np.random.RandomState(4)
    w = _f32({"l1_router_w": rng.randn(D, EXPERTS) * 0.5,
              "l1_router_bias": rng.uniform(-0.2, 0.2, EXPERTS),
              "l1_experts_gate_w": rng.randn(EXPERTS, D, F) * 0.2,
              "l1_experts_up_w": rng.randn(EXPERTS, D, F) * 0.2,
              "l1_experts_down_w": rng.randn(EXPERTS, F, D) * 0.2,
              "l1_shared_gate_w": rng.randn(D, F) * 0.2,
              "l1_shared_up_w": rng.randn(D, F) * 0.2,
              "l1_shared_down_w": rng.randn(F, D) * 0.2})
    return w, rng.randn(1, S, D).astype(np.float32)


def _experts(first, count):
    return {k: v[first:first + count] if "_experts_" in k else v
            for k, v in _moe()[0].items()}


def _moe_cfg(first, count):
    return models.HyperExpertDecoderConfig(
        **{**MOE_OVER, "experts_held": count, "expert_offset": first})


@functools.lru_cache(None)
def _moe_share(first, count):
    """The model's expert block on experts first .. first + count of 16,
    the router, its bias and the shared expert whole."""
    cfg = _moe_cfg(first, count)
    return _Block(lambda x: hyper._HyperBuilder(cfg).expert_block(
        x, "l1")[0], _moe()[1]).run(_experts(first, count))


def _moe_reference(first, count):
    return np.asarray(jax.jit(functools.partial(
        REF._expert_block, name="l1",
        cfg=_ref_cfg(_moe_cfg(first, count))))(
            {k: jnp.asarray(v) for k, v in _experts(first, count).items()},
            jnp.asarray(_moe()[1][0])))


@pytest.mark.parametrize("first,count", [(0, 16), (0, 2), (6, 2), (14, 2)])
def test_a_share_of_the_experts_is_the_reference_given_the_same_share(
        first, count):
    np.testing.assert_allclose(_moe_share(first, count),
                               _moe_reference(first, count), rtol=1e-4,
                               atol=1e-5)


def test_the_eight_expert_shares_add_up_to_the_uncut_expert_block():
    """8 shares of one expert layer at a router 16 wide and 4 a token,
    each as THE MODEL'S builder computes it on its 2 of 16 experts, with
    the shared expert counted once, are the uncut block."""
    w, x = _moe()
    shared = np.asarray(REF._mlp({k: jnp.asarray(v) for k, v in w.items()},
                                 jnp.asarray(x[0]), "l1_shared"))
    total = shared + sum(_moe_share(first, HELD) - shared
                         for first in range(0, EXPERTS, HELD))
    whole = _moe_share(0, EXPERTS)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert np.abs(whole - shared).max() > 1e-2

"""Decoder-only causal language model whose attention runs in a compressed
latent (compressed convolutional attention, CCA, with grouped key/value
heads: Zyphra, arXiv:2510.04476) and whose every MLP is a top-1 expert block
behind a small router network that carries a state from the layer before, as
one chip of an expert-parallel group runs it; embedding and head are one
table (ZAYA1-8B's language model; benchmark/configs/zaya1-8b.json).

Layer l, pre-norm:    a  = h + CCA_l(N1(h))
                      (m, r_l) = Moe_l(N2(a), r_{l-1});  h' = a + m
CCA(u):               q~ = W_q u -> H x D, k~ = W_k u -> G x D, v~ = W_v u
                      -> G x D: the latent, H D + G D wide where the stream
                      is d.  layers.compressed_conv_qkv: two causal
                      convolutions along the sequence over [q~ ; k~], the
                      q-k mean added, heads normalised to sqrt(D), the keys
                      times tau, rotary on the first `rotary_dim` features,
                      the second half of v~ from the token before (for a
                      TPU, at a head of 128 and an S of whole tiles of
                      rows, ONE Pallas kernel forward and one backward,
                      kernels/cca_mix.py, which keep nothing but the three
                      projections through a layer's recomputation; the
                      same arithmetic in jax.numpy at any other shape or
                      backend).  Query
                      head j reads key/value head j // (H/G); causal softmax
                      of q.k / sqrt(D); o = W_o concat(P v), W_o [H D, d]
Moe(x, r_prev):       s = W_dn x + b_dn -> R;  r = s + gamma * r_prev (gamma
                      [R] starts at 0; layer 0 has r_prev = 0);
                      logits = W_3 gelu(W_2 gelu(W_1 N(r) + b_1) + b_2);
                      p = softmax(logits) over all experts; the top_k of p;
                      m = sum over the HELD chosen experts of p_e E_e(x)
                      (p_e itself under norm_topk_prob False); r is handed on
Output:               logits = N_f(h_L) T^t, T the embedding's table; mean
                      cross entropy

The chip's share is expert_decoder.py's: `experts_held` experts from
`expert_offset` on of `n_routed_experts`, the router whole.  The norm, the
linear map and the head with its cross entropy are looped_decoder.py's, the
parameter maker expert_decoder.py's, the held experts and the scaled start
of the stream's writers common.SoftmaxExpertShare's.  Every layer is a
one-trip layers.Recurrence, the unit of recomputation, which carries two
values: the stream and the router's state (common.one_trip_layer).  The last
linear map of the router is `moe_router`'s weight, so the op runs on x of
width R.  Name scopes: `cca.mix` (the op above), `cca.attend` (the
`fused_attention` call alone), `moe.router` (here around the WHOLE router,
from the down-projection on, not the op's last matmul alone) and
ops/moe_ops.py's `moe.dispatch` and `moe.experts`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..param_attr import ParamAttr
from .common import (ModelSpec, SoftmaxExpertShare, one_trip_layer,
                     packed_batch)
from .expert_decoder import _ExpertBuilder
from .looped_decoder import _heads_and_loss

__all__ = ["CompressedDecoderConfig", "compressed_decoder"]


@dataclasses.dataclass
class CompressedDecoderConfig:
    vocab_size: int = 32784
    max_length: int = 16384
    n_layer: int = 4
    d_model: int = 2048
    n_head: int = 8
    n_kv_head: int = 2
    head_dim: int = 128
    conv_time0: int = 2             # convolution A's taps (depthwise)
    conv_time1: int = 2             # convolution B's taps (by head)
    rotary_dim: int = 64            # features of a head that turn
    rope_theta: float = 5e6
    rms_norm_eps: float = 1e-5
    n_routed_experts: int = 16      # the router's outputs
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 1
    d_expert: int = 2048
    router_dim: int = 256           # R: the router network's width
    norm_topk_prob: bool = False
    # False: the router's network takes no gradient
    # (common.SoftmaxExpertShare)
    train_router: bool = True
    use_recompute: bool = True
    init_std: float = 0.02
    residual_init_layers: int = 0   # common.SoftmaxExpertShare
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False


class _CompressedBuilder(SoftmaxExpertShare, _ExpertBuilder):
    def attention(self, u, name):
        cfg = self.cfg
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        C, k0, k1 = (H + G) * D, cfg.conv_time0, cfg.conv_time1
        q, k, v = layers.compressed_conv_qkv(
            self.linear(u, cfg.d_model, H * D, f"{name}_q"),
            self.linear(u, cfg.d_model, G * D, f"{name}_k"),
            self.linear(u, cfg.d_model, G * D, f"{name}_v"),
            self.conv_param([k0, C], f"{name}_conv_a_w", k0),
            self.conv_param([C], f"{name}_conv_a_b", k0),
            self.conv_param([k1, H + G, D, D], f"{name}_conv_b_w", k1 * D),
            self.conv_param([C], f"{name}_conv_b_b", k1 * D),
            self.constant([G], f"{name}_tau", 1.0),
            heads=H, kv_heads=G, rotary_dim=cfg.rotary_dim,
            rope_base=cfg.rope_theta)
        with name_scope("cca.attend"):
            ctx = layers.fused_attention(q, k, v, causal=True, rope="partial")
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, H * D])
        return layers.matmul(ctx, self.residual_param(
            [H * D, cfg.d_model], f"{name}_o_w"))

    def router(self, x, r_prev, name):
        """(the chosen experts, their gates, the state handed on)."""
        cfg = self.cfg
        R, trained = cfg.router_dim, cfg.train_router

        def affine(t, d_in, d_out, part, act=None):
            w = self.param([d_in, d_out], f"{name}_router_{part}_w",
                           trainable=trained)
            b = self.constant([d_out], f"{name}_router_{part}_b", 0.0,
                              trainable=trained)
            return layers.elementwise_add(layers.matmul(t, w), b, act=act)

        r = layers.elementwise_add(
            affine(x, cfg.d_model, R, "down"),
            layers.elementwise_mul(r_prev, self.constant(
                [R], f"{name}_router_gamma", 0.0, trainable=trained)))
        n = layers.rms_norm(
            r, begin_norm_axis=-1, epsilon=cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{name}_router_norm_scale",
                                 trainable=trained))
        hidden = affine(affine(n, R, R, "fc1", "gelu"), R, R, "fc2", "gelu")
        idx, weight, _ = layers.moe_router(
            hidden, self.param([R, cfg.n_routed_experts],
                               f"{name}_router_w", trainable=trained),
            None, top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scoring="softmax", carried=R)
        if not trained:
            weight = layers.detach(weight)
        return idx, weight, r

    def layer(self, h, r_prev, i):
        """(h', r) of layer i."""
        name = f"l{i}"
        attn = self.attention(self.norm(h, f"{name}_n1"), f"{name}_attn")
        a = layers.elementwise_add(h, attn)
        x = self.norm(a, f"{name}_n2")
        with name_scope("moe.router"):
            idx, weight, r = self.router(x, r_prev, name)
        out = self.held_experts(x, idx, weight, name)
        return layers.elementwise_add(a, out), r


def compressed_decoder(cfg: Optional[CompressedDecoderConfig] = None,
                       tokens=None, labels=None) -> ModelSpec:
    cfg = cfg or CompressedDecoderConfig()
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _CompressedBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    table = h.block.program.global_block().var("embed")
    # layer 0's r_prev
    r = layers.fill_constant_batch_size_like(
        h, shape=[-1, S, cfg.router_dim], dtype="float32", value=0.0)
    for i in range(cfg.n_layer):
        (h, r), _ = one_trip_layer(
            (h, r), lambda carried, i=i: (b.layer(*carried, i), []),
            cfg.use_recompute)
    states = layers.unsqueeze(b.norm(h, "final"), axes=[0])   # one "trip"
    loss, logits, _ = _heads_and_loss(b, states, labels, table=table)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name="compressed_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits, "router_state": r},
    )

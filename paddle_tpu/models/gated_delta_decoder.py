"""Decoder-only causal language model whose layers mix the sequence either
by Gated DeltaNet (a gated delta rule with ONE decay a head, its key heads
serving several value heads: Yang, Kautz, Hatamizadeh, arXiv:2412.06464) or
by gated grouped-query attention (a per-head q/k norm, a rotary on part of
the head, a sigmoid gate on the context), `full_attention_interval` - 1 of
the first kind to one of the second, every mixer followed by a
softmax-routed expert block with a shared expert behind a gate of its own,
as one chip of an expert-parallel group runs it (Qwen3-Next-80B-A3B;
benchmark/configs/qwen3-next-80b-a3b.json).

d = `d_model`; RMS = RMSNorm with a weight that starts at 1 (the published
1 + w with w from 0: the same function and the same Adam step).

h_0 = Emb[x]
Layer i (from 0): a  = h + Mix_i(RMS1(h));  h' = a + Moe(RMS2(a))
Mix_i:        gated attention where (i + 1) % `full_attention_interval` is
              0, else Gated DeltaNet
GDN(u):       Hk = `linear_key_heads` key heads, Hv = `linear_value_heads`
              value heads of D = `linear_head_dim`; (q~, k~, v~, z) = W_qkvz
              u of widths Hk D, Hk D, Hv D, Hv D; (b, a) = W_ba u, [Hv]
              each; no bias.  (q', k', v) = split(silu(conv([q~ | k~ |
              v~]))): ONE layers.short_conv1d over the 2 Hk D + Hv D
              channels, `conv_kernel` taps, zeros before the row's start;
              beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias) [S,
              Hv], fp32 (layers.gated_delta_decay);
              o = layers.gated_delta_attention(q', k', v, g, beta) in its
              head-decay form: q', k' to unit length a head, value head j on
              key head j // (Hv / Hk), state M [D, D] from 0, M~ = exp(g_t)
              M, M = M~ + beta_t k_t (v_t - M~^T k_t)^T, o_t = D^-1/2 M^T
              q_t;  GDN(u) = W_o (RMS_D(o) w_n * silu(z)): the norm a head
              first, then the gate (layers.kda_gated_norm, gate_activation
              silu)
GAttn(u):     H = `n_head` query heads over G = `n_kv_head` key/value heads
              of D = `head_dim`; (q, gate) = W_q u, [H x 2 D] split a head
              into D | D; k = W_k u, v = W_v u [G D]; q = RMS_D(q), k =
              RMS_D(k) a head; rotary at `rope_theta` on the first
              `rotary_dim` features of a head (half-split pairs), the
              others pass; causal softmax of q k^T / sqrt(D), query head j
              on key/value head j // (H / G) (layers.fused_attention);
              GAttn(u) = W_o (ctx * sigmoid(gate))
Moe(u):       common.SoftmaxExpertShare's block (the softmax over all
              `n_routed_experts`, the `top_k` largest, their weights over
              their sum; the HELD experts' terms) + sigmoid(w_s . u)
              Shared(u), Shared a gated MLP of width `d_shared_expert`
Output:       logits = W_head RMS_f(h_L) (untied); mean cross entropy
              (looped_decoder._heads_and_loss)

The chip's share: `experts_held` experts from `expert_offset` on of
`n_routed_experts` (the router keeps its width and its top_k), `vocab_size`
rows of both tables from row 0; the mixers, the shared expert and its gate
whole.  What the other chips' experts would add is left out: no code stands
in for them.

Every layer is a one-trip layers.Recurrence, the unit of recomputation
(common.one_trip_layer, prevent_cse as sambay_decoder's); what its
recomputation does not make again is what the kernels keep (the scan's
output and group states, a flash site's output and logsumexp).  Name
scopes: `gdn.mix` (the convolution with its SiLU, the decay, beta, the
gated norm), `gdn.scan` (the op gated_delta_attention's own), `attn.gate`
(the head norms, the rotary, the output gate), `attn.full` (the op
fused_attention alone), `moe.shared` and ops/moe_ops.py's `moe.router`,
`moe.dispatch`, `moe.experts`, `loop.heads`.  The projections lie outside
the mixers' scopes, as every other attention's do.  Spans at lowering:
`gdn.lower`, `kda.mix.lower` (the gated norm), `attn.lower`, `flash.plan` /
`flash.bwd_plan`, `moe.lower`, `router.lower`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..initializer import Initializer, UniformInitializer
from ..param_attr import ParamAttr
from .common import (ModelSpec, SoftmaxExpertShare, one_trip_layer,
                     packed_batch)
from .expert_decoder import _ExpertBuilder
from .looped_decoder import _heads_and_loss

__all__ = ["GatedDeltaDecoderConfig", "gated_delta_decoder"]


@dataclasses.dataclass
class GatedDeltaDecoderConfig:
    vocab_size: int = 18992         # rows of both tables held here
    max_length: int = 8192
    n_layer: int = 4
    full_attention_interval: int = 4
    d_model: int = 2048
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_head_dim: int = 128      # a key's and a value's
    conv_kernel: int = 4
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    rotary_dim: int = 64            # partial_rotary_factor x head_dim
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 512     # the router's width
    experts_held: int = 32          # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 10
    d_expert: int = 512
    d_shared_expert: int = 512
    norm_topk_prob: bool = True
    # False: the router takes no gradient (common.SoftmaxExpertShare)
    train_router: bool = False
    use_recompute: bool = True
    init_std: float = 0.02
    residual_init_layers: int = 0   # common.SoftmaxExpertShare
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False


# where A = exp(A_log) and dt_bias start (the family's open modelling code,
# `Qwen3NextGatedDeltaNet`: A_log = log(uniform(0, 16)), dt_bias = ones)
_A_MAX = 16.0
_DT_BIAS = 1.0


class _LogOfUniform(Initializer):
    """log(A), A uniform on (0, high]: high times (1 - a draw from [0, 1)),
    drawn by the start-up program's own generator."""

    def __init__(self, high: float):
        self.high = float(high)

    def __call__(self, var, block):
        UniformInitializer(0.0, 1.0)(var, block)
        same = dict(inputs={"X": [var.name]}, outputs={"Out": [var.name]})
        block.append_op(type="scale", attrs={"scale": -self.high,
                                             "bias": self.high}, **same)
        return block.append_op(type="log", attrs={}, **same)


class _GatedDeltaBuilder(SoftmaxExpertShare, _ExpertBuilder):
    def delta_net(self, u, name):
        cfg = self.cfg
        Hk, Hv, D = (cfg.linear_key_heads, cfg.linear_value_heads,
                     cfg.linear_head_dim)
        keys, values, taps = Hk * D, Hv * D, cfg.conv_kernel
        qkv, z = layers.split(
            self.linear(u, cfg.d_model, 2 * keys + 2 * values,
                        f"{name}_qkvz"), [2 * keys + values, values], dim=-1)
        b, a = layers.split(
            self.linear(u, cfg.d_model, 2 * Hv, f"{name}_ba"), 2, dim=-1)
        with name_scope("gdn.mix"):
            q, k, v = layers.split(layers.short_conv1d(
                qkv, self.conv_param([taps, 2 * keys + values],
                                     f"{name}_conv_w", taps), "silu"),
                [keys, keys, values], dim=-1)
            g = layers.gated_delta_decay(
                a, self.param([Hv], f"{name}_a_log",
                              initializer=_LogOfUniform(_A_MAX)),
                self.constant([Hv], f"{name}_dt_bias", _DT_BIAS))
            beta = layers.sigmoid(layers.cast(b, "float32"))
        o = layers.gated_delta_attention(q, k, v, g, beta, heads=Hv)
        with name_scope("gdn.mix"):
            o = layers.kda_gated_norm(
                o, z, None, self.constant([D], f"{name}_on_scale", 1.0),
                heads=Hv, epsilon=cfg.rms_norm_eps, gate_activation="silu")
        return self.linear(o, values, cfg.d_model, f"{name}_o")

    def heads(self, t, n, name=None):
        """[B, S, n, D] -> [B, n, S, D]; with `name` each head normalised
        (the scale's name) and its first `rotary_dim` features turned."""
        cfg = self.cfg
        if name is None:
            return layers.transpose(t, perm=[0, 2, 1, 3])
        t = layers.transpose(self.norm(t, name), perm=[0, 2, 1, 3])
        return layers.rotary_embedding(t, base=cfg.rope_theta,
                                       rotary_dim=cfg.rotary_dim)

    def gated_attention(self, u, name):
        cfg = self.cfg
        H, G, D, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_model
        q, gate = layers.split(layers.reshape(
            self.linear(u, d, 2 * H * D, f"{name}_q"), shape=[0, 0, H, 2 * D]),
            2, dim=-1)
        k = layers.reshape(self.linear(u, d, G * D, f"{name}_k"),
                           shape=[0, 0, G, D])
        v = layers.reshape(self.linear(u, d, G * D, f"{name}_v"),
                           shape=[0, 0, G, D])
        with name_scope("attn.gate"):
            q = self.heads(q, H, f"{name}_qn")
            k = self.heads(k, G, f"{name}_kn")
        with name_scope("attn.full"):
            ctx = layers.fused_attention(q, k, self.heads(v, G), causal=True,
                                         rope="partial")
        with name_scope("attn.gate"):
            ctx = layers.elementwise_mul(
                layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                               shape=[0, 0, H * D]),
                layers.sigmoid(layers.reshape(gate, shape=[0, 0, H * D])))
        return self.linear(ctx, H * D, d, f"{name}_o")

    def expert_block(self, x, name):
        cfg = self.cfg
        routed = super().expert_block(x, name)
        with name_scope("moe.shared"):
            shared = layers.elementwise_mul(
                self.mlp(x, f"{name}_shared", cfg.d_shared_expert),
                layers.sigmoid(self.linear(x, cfg.d_model, 1,
                                           f"{name}_shared_expert_gate")))
        return layers.elementwise_add(routed, shared)

    def layer(self, h, i):
        name = f"l{i}"
        u = self.norm(h, f"{name}_n1")
        mixed = self.gated_attention(u, f"{name}_attn") \
            if (i + 1) % self.cfg.full_attention_interval == 0 \
            else self.delta_net(u, f"{name}_gdn")
        a = layers.elementwise_add(h, mixed)
        return layers.elementwise_add(
            a, self.expert_block(self.norm(a, f"{name}_n2"), name))


def gated_delta_decoder(cfg: Optional[GatedDeltaDecoderConfig] = None,
                        tokens=None, labels=None) -> ModelSpec:
    cfg = cfg or GatedDeltaDecoderConfig()
    if cfg.linear_value_heads % cfg.linear_key_heads \
            or cfg.n_head % cfg.n_kv_head or cfg.rotary_dim > cfg.head_dim:
        raise ValueError(
            f"{cfg.linear_value_heads} value heads over "
            f"{cfg.linear_key_heads} key heads, {cfg.n_head} query heads "
            f"over {cfg.n_kv_head} key/value heads, a rotary of "
            f"{cfg.rotary_dim} on a head of {cfg.head_dim}")
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _GatedDeltaBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    for i in range(cfg.n_layer):
        h, _ = one_trip_layer(
            h, lambda carried, i=i: (b.layer(carried, i), ()),
            cfg.use_recompute, prevent_cse=True)
    with name_scope("loop.heads"):
        states = layers.unsqueeze(b.norm(h, "final"), axes=[0])
        loss, logits, _ = _heads_and_loss(b, states, labels)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name="gated_delta_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits, "states": states},
    )

"""Decoder-only causal language model that alternates state-space-dual
mixers with grouped-query attention, every mixer followed by a gated MLP,
with constant multipliers on the stream, the scores and the logits (Mamba-2:
Dao & Gu, arXiv:2405.21060, as IBM Granite 4.0-H's modelling code runs it;
benchmark/configs/granite-4.0-h-micro.json).  No positional encoding
anywhere.

d = `d_model`, H = `ssm_heads` heads of P = `ssm_head_dim` (inner width E =
H P), N = `d_state` over G = `n_groups` groups, Hq = `n_head` query heads of
D = `head_dim` over Hk = `n_kv_head`, F = `d_inner`.  RMS = RMSNorm with a
weight.

h_0 = `embedding_multiplier` Emb[x]
Layer l:      a  = h + `residual_multiplier` Mix_l(RMS1(h))
              h' = a + `residual_multiplier` W2(silu(g) * u),
              (g, u) = split(W1 RMS2(a)), no bias
Mix_l is by `layer_types[l]`:
mamba(u):     (z, xBC, dt) = split(W_in u) of E, E + 2 G N, H;
              xBC = silu(conv(xBC) + b_c) (layers.short_conv1d: depthwise,
              causal, `d_conv` taps); (x, B, C) = split(xBC) of E, G N, G N;
              y = layers.ssd_scan(x [H, P], dt, A = -exp(A_log), B, C, D,
              dt_bias): dt = softplus(dt + dt_bias) [H], s_t = exp(dt_t A)
              s_(t-1) + dt_t x_t (x) B_t from 0, y_t = s_t C_t + D x_t, ONE
              decay a head; n = layers.gated_rms_norm(y, z, w_n): the gate
              silu(z) FIRST, then one mean square a group of E / G channels;
              mamba(u) = W_out n
attention(u): q = W_q u [Hq D], k = W_k u, v = W_v u [Hk D], no bias, no
              rotary; layers.fused_attention, causal, scores times
              `attention_multiplier` (NOT D^-1/2), query head j reads
              key/value head j // (Hq / Hk); W_o concat(heads)
Output:       logits = RMS_f(h_L) Emb^T / `logits_scaling` (tied, no bias);
              mean cross entropy (looped_decoder._heads_and_loss with the
              table; the division is of the normed states, the same number)

The chip's share: `vocab_size` rows of the tied table, from row 0; `ssm_heads`
of the state-space heads (the in-projection's z, x and dt columns, the
convolution's x channels, A_log, D, dt_bias, the norm's weight and the
out-projection's rows by heads; the B and C columns whole) and `n_head` |
`n_kv_head` of the attention heads, each from head 0; the norms and the MLP
whole.  What the chips that hold the other heads would add to a mixer's
output, and to the gated norm's sum of squares, is left out: no code stands
in for them.

Every layer is a one-trip layers.Recurrence, the unit of recomputation
(common.one_trip_layer, prevent_cse as sambay_decoder's).  What a layer's
recomputation does NOT make again is what the kernels keep (the scan's
output and chunk starts, a flash site's output and logsumexp) and the two
widest products' outputs, tagged with layers.kept: W1's [B, S, 2 F] in every
layer (common.kept_gated_mlp, the function sambay_decoder's layers call)
and a Mamba-2 mixer's in-projection's [B, S, 2 E + 2 G N + H].  The stream
after the mixer IS made again (the out-projection, for the norm W1 reads):
kept too it would put the cell's first step at 16.16 of the chip's 16.91 GB.
Name scopes: `ssd.mix` (the convolution with its SiLU, the step's softplus,
the gate and the norm), `ssd.scan` (the op ssd_scan's own), `attn.full`
(the op fused_attention; the projections outside), `mlp`, `loop.heads`.
Spans at lowering: `ssd.lower`, `attn.lower`, `flash.plan` / `flash.bwd_plan`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..initializer import NumpyArrayInitializer
from ..param_attr import ParamAttr
from .common import (ModelSpec, kept_gated_mlp, one_trip_layer,
                     packed_batch)
from .expert_decoder import _ExpertBuilder
from .looped_decoder import _heads_and_loss
from .sambay_decoder import _DT_RANGE, _InverseSoftplusOfLogUniform

__all__ = ["SsdHybridDecoderConfig", "ssd_hybrid_decoder"]

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass
class SsdHybridDecoderConfig:
    vocab_size: int = 12544         # rows of the tied table held here
    max_length: int = 8192
    d_model: int = 2048
    d_inner: int = 8192
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    ssm_heads: int = 32             # state-space heads held here
    ssm_head_dim: int = 64
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    n_head: int = 16                # query heads held here
    n_kv_head: int = 4              # key/value heads held here
    head_dim: int = 64
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    use_recompute: bool = True
    init_std: float = 0.02
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)


class _SsdHybridBuilder(_ExpertBuilder):
    # W1's output survives the layer's recomputation
    mlp = kept_gated_mlp

    def mamba(self, u, name):
        cfg = self.cfg
        H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state,
                      cfg.n_groups)
        E, taps = H * P, cfg.d_conv
        z, xbc, dt = layers.split(layers.kept(
            self.linear(u, cfg.d_model, 2 * E + 2 * G * N + H, f"{name}_in")),
            [E, E + 2 * G * N, H], dim=-1)
        with name_scope("ssd.mix"):
            xbc = layers.short_conv1d(
                xbc, self.conv_param([taps, E + 2 * G * N],
                                     f"{name}_conv_w", taps),
                "silu", bias=self.conv_param([E + 2 * G * N],
                                             f"{name}_conv_b", taps))
        x, b, c = layers.split(xbc, [E, G * N, G * N], dim=-1)
        a = layers.scale(layers.exp(self.param(
            [H], f"{name}_a_log", initializer=NumpyArrayInitializer(
                np.log(np.arange(1, H + 1, dtype=np.float32))))), scale=-1.0)
        y = layers.ssd_scan(
            layers.reshape(x, shape=[0, 0, H, P]), dt, a,
            layers.reshape(b, shape=[0, 0, G, N]),
            layers.reshape(c, shape=[0, 0, G, N]),
            self.constant([H], f"{name}_d", 1.0),
            dt_bias=self.param([H], f"{name}_dt_b", initializer=(
                _InverseSoftplusOfLogUniform(*_DT_RANGE))))
        with name_scope("ssd.mix"):
            normed = layers.gated_rms_norm(
                layers.reshape(y, shape=[0, 0, E]), z,
                self.constant([E], f"{name}_norm_scale", 1.0), groups=G,
                epsilon=cfg.rms_norm_eps)
        return self.linear(normed, E, cfg.d_model, f"{name}_out")

    def attention(self, u, name):
        cfg = self.cfg
        d, D = cfg.d_model, cfg.head_dim
        q = self.linear(u, d, cfg.n_head * D, f"{name}_q")
        k = self.linear(u, d, cfg.n_kv_head * D, f"{name}_k")
        v = self.linear(u, d, cfg.n_kv_head * D, f"{name}_v")
        with name_scope("attn.full"):
            ctx = layers.fused_attention(
                q, k, v, causal=True, scale=cfg.attention_multiplier,
                n_head=cfg.n_head)
        return self.linear(ctx, cfg.n_head * D, d, f"{name}_o")

    def layer(self, h, i, kind):
        cfg = self.cfg
        name = f"l{i}"
        u = self.norm(h, f"{name}_n1")
        mixed = self.mamba(u, f"{name}_ssm") if kind == MAMBA \
            else self.attention(u, f"{name}_attn")
        a = layers.elementwise_add(
            h, layers.scale(mixed, scale=cfg.residual_multiplier))
        return layers.elementwise_add(a, layers.scale(
            self.mlp(self.norm(a, f"{name}_n2"), f"{name}_mlp"),
            scale=cfg.residual_multiplier))


def ssd_hybrid_decoder(cfg: Optional[SsdHybridDecoderConfig] = None,
                       tokens=None, labels=None) -> ModelSpec:
    cfg = cfg or SsdHybridDecoderConfig()
    wrong = set(cfg.layer_types) - {MAMBA, ATTENTION}
    if wrong:
        raise ValueError(f"layer_types holds {sorted(wrong)}: a layer is "
                         f"{MAMBA} or {ATTENTION}")
    if cfg.n_head % cfg.n_kv_head or cfg.ssm_heads % cfg.n_groups:
        raise ValueError(
            f"{cfg.n_head} query heads over {cfg.n_kv_head} key/value heads, "
            f"{cfg.ssm_heads} state-space heads over {cfg.n_groups} groups")
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _SsdHybridBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    table = h.block.program.global_block().var("embed")
    h = layers.scale(h, scale=cfg.embedding_multiplier)
    for i, kind in enumerate(cfg.layer_types):
        h, _ = one_trip_layer(
            h, lambda carried, i=i, kind=kind: (b.layer(carried, i, kind),
                                                ()),
            cfg.use_recompute, prevent_cse=True)
    with name_scope("loop.heads"):
        # the logits divided by `logits_scaling`: the division is taken on
        # the normed states, before the product with the table
        states = layers.unsqueeze(layers.scale(
            b.norm(h, "final"), scale=1.0 / cfg.logits_scaling), axes=[0])
        loss, logits, _ = _heads_and_loss(b, states, labels, table=table)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name="ssd_hybrid_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits, "states": states},
    )

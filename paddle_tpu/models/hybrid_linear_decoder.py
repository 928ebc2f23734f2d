"""Decoder-only causal language model whose layers mix the sequence either
by a linear attention with a state of fixed size (Kimi Delta Attention,
KDA: a gated delta rule with a decay for every key channel, arXiv:
2510.26692) or by latent attention without positions (MLA, no rotary: the
model takes its positions from the KDA layers), three of the first kind to
one of the second, with expert_decoder.py's sparse expert block after the
leading dense layers, as one chip of an expert-parallel group runs it
(Kimi-Linear-48B-A3B; benchmark/configs/kimi-linear-48b-a3b.json).

Layer l (numbered from 1, as `kda_layers` and `full_attn_layers` are; the
program reads the entries up to its depth), pre-norm:
                      a  = h + Mix_l(N1(h));  h' = a + F_l(N2(a))
Mix_l:                KDA for l in kda_layers, MLA for l in full_attn_layers
KDA(u), H heads of D: q~ = W_q u, k~ = W_k u, v~ = W_v u [S, H D];
                      q' = silu(conv_q(q~)), k', v likewise (layers.
                      short_conv1d's convolution: causal, depthwise,
                      `short_conv_kernel_size` taps, zeros before the first
                      position);
                      g = -exp(A_log_h) softplus(W_f2 W_f1 u + dt_bias),
                      fp32, one for every key channel; beta = sigmoid(W_b
                      u) [S, H];  o = layers.gated_delta_attention(q', k',
                      v, g, beta): q', k' to unit length a head, state M
                      [D, D] from 0, M~ = diag(exp(g_t)) M, M = M~ + beta_t
                      k_t (v_t - M~^T k_t)^T, o_t = D^-1/2 M^T q_t;
                      KDA(u) = W_o (RMSNorm_D(o) * sigmoid(W_g2 W_g1 u +
                      b_g)): a norm a head with one learned scale [D], a
                      gate of rank D
MLA(u):               expert_decoder.py's, `mla_rope` "none": neither
                      qk_rope_head_dim-wide part is turned
F_l:                  the gated MLP in the first `first_k_dense` layers,
                      expert_decoder.py's expert block (sigmoid scores, the
                      top_k of s + b, the held experts' terms, the shared
                      expert, b stepped after the step) in every later one
Output:               logits = W_head N_f(h_L); mean cross entropy

Everything but the KDA mixer is expert_decoder.py's builder and model
function.  Name scopes: `kda.mix` (what streams [S, H D] values through the
vector unit, as two ops: layers.kda_conv_decay before the scan, the three
convolutions with SiLU and the decay g, and layers.kda_gated_norm after it,
the norm a head times the gate; each a Pallas kernel pair over tiles of
rows where the program is for a TPU and the shape tiles,
kernels/kda_mix.py, and jax.numpy elsewhere; the span `kda.mix.lower` says
which; beside them, in the same scope, the rank-D maps W_f, W_g and beta),
`kda.scan` (the op gated_delta_attention, forward and backward; the scope
is the op's own), `mla`, `moe.shared` and ops/moe_ops.py's `moe.router`,
`moe.dispatch`, `moe.experts`.  The four [d, H D] projections lie outside
both `kda.*` scopes, as every other attention's do.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from .. import layers
from ..core.framework import name_scope
from ..initializer import UniformInitializer
from .common import ModelSpec
from .expert_decoder import _ExpertBuilder, _decoder

__all__ = ["HybridLinearDecoderConfig", "hybrid_linear_decoder"]


@dataclasses.dataclass
class HybridLinearDecoderConfig:
    vocab_size: int = 20480
    max_length: int = 8192
    n_layer: int = 5
    first_k_dense: int = 1
    d_model: int = 2304
    d_inner: int = 9216             # the dense layers' MLP
    # the layers of each kind, numbered from 1
    kda_layers: Tuple[int, ...] = (1, 2, 3, 5)
    full_attn_layers: Tuple[int, ...] = (4,)
    kda_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    n_head: int = 32                # MLA
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    mla_rope: str = "none"          # rotary | none
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    n_routed_experts: int = 256     # the router's width
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 8
    d_expert: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    bias_update_gamma: float = 0.001
    use_recompute: bool = True
    init_std: float = 0.02
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False


# where A_log and dt_bias start (the family's open modelling code): a head's
# A between 1 and 16, a channel's softplus(dt_bias) between 0.001 and 0.1
_A_RANGE = (0.0, math.log(16.0))
_DT_RANGE = tuple(math.log(math.expm1(dt)) for dt in (0.001, 0.1))


class _HybridBuilder(_ExpertBuilder):
    def uniform(self, shape, name, low, high):
        return self.param(shape, name,
                          initializer=UniformInitializer(low, high))

    def low_rank(self, u, rank, d_out, name):
        return self.linear(self.linear(u, self.cfg.d_model, rank,
                                       f"{name}_a"), rank, d_out,
                           f"{name}_b")

    def delta_attention(self, u, name):
        cfg = self.cfg
        H, D, d = cfg.kda_heads, cfg.kda_head_dim, cfg.d_model
        taps = cfg.short_conv_kernel_size
        projected = [self.linear(u, d, H * D, f"{name}_{p}") for p in "qkv"]
        with name_scope("kda.mix"):
            filters = [self.conv_param([taps, H * D], f"{name}_conv_{p}_w",
                                       taps) for p in "qkv"]
            q, k, v, g = layers.kda_conv_decay(
                *projected, self.low_rank(u, D, H * D, f"{name}_f"), *filters,
                self.uniform([H * D], f"{name}_dt_bias", *_DT_RANGE),
                self.uniform([H], f"{name}_a_log", *_A_RANGE), heads=H)
            beta = layers.sigmoid(layers.cast(
                self.linear(u, d, H, f"{name}_beta"), "float32"))
        o = layers.gated_delta_attention(q, k, v, g, beta, heads=H)
        with name_scope("kda.mix"):
            o = layers.kda_gated_norm(
                o, self.low_rank(u, D, H * D, f"{name}_gate"),
                self.constant([H * D], f"{name}_gate_bias", 0.0),
                self.constant([D], f"{name}_on_scale", 1.0), heads=H,
                epsilon=cfg.rms_norm_eps)
        return self.linear(o, H * D, d, f"{name}_o")

    def mixer(self, h, i):
        cfg = self.cfg
        if i + 1 in cfg.full_attn_layers:
            return super().mixer(h, i)
        if i + 1 not in cfg.kda_layers:
            raise ValueError(f"layer {i + 1} is in neither kda_layers "
                             f"{cfg.kda_layers} nor full_attn_layers "
                             f"{cfg.full_attn_layers}")
        return self.delta_attention(self.norm(h, f"l{i}_n1"), f"l{i}_attn")


def hybrid_linear_decoder(cfg: Optional[HybridLinearDecoderConfig] = None,
                          tokens=None, labels=None) -> ModelSpec:
    return _decoder(_HybridBuilder(cfg or HybridLinearDecoderConfig()),
                    "hybrid_linear_decoder", tokens, labels)

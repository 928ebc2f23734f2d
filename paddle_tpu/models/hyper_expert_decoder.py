"""Decoder-only causal language model whose layers carry n residual streams
a token and mix them around every sublayer by manifold-constrained
hyper-connections (mHC: arXiv:2512.24880, on hyper-connections, arXiv:
2409.19606), with latent attention behind a low-rank query under YaRN and
expert_decoder.py's sparse expert block after the leading dense layers, as
one chip of a group that shares heads, experts and vocabulary runs it
(Xing4.0-29B-A4B; benchmark/configs/xing4.0-29b-a4b.json).

Streams:              X_0[j] = E[token] for each of the n = `hc_mult`
                      streams; h_L = sum_j X_L[j]; logits = W_head N_f(h_L)
Layer, two sublayers: X  <- HC(X, x -> MLA(N1(x)))
                      X  <- HC(X, x -> F(N2(x)))
HC(X, G), a token:    the maps H_pre [n], H_post [n], H_res [n, n] from X
                      itself (layers.mhc_maps: RMS-normalised vec(X) times
                      Phi [nC, 2n + n^2], a learned scalar and bias a map,
                      sigmoid, 2 sigmoid, exp and `hc_sinkhorn_iters`
                      Sinkhorn-Knopp iterations: H_res doubly stochastic);
                      x_in = sum_j H_pre[j] X[j];  y = G(x_in);
                      X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
MLA:                  q = W_qb N_q(W_qa x) (rank `q_lora_rank`), the rest
                      expert_decoder.py's; the two rope-wide parts turn at
                      YaRN's frequencies (`rope_scaling`: factor,
                      original_max_position_embeddings, beta_fast,
                      beta_slow), cos and sin times mscale(mscale) /
                      mscale(mscale_all_dim), the softmax scale (nope +
                      rope)^-1/2 x mscale(mscale_all_dim)^2, mscale(m) =
                      0.1 m ln(factor) + 1 (DeepSeek-V3's YaRN)
F:                    the gated MLP in the first `first_k_dense` layers,
                      expert_decoder.py's expert block in every later one

The chip's share: `n_head` attention heads of the group's (tensor-parallel
by heads: W_qb, W_kvb and the output map hold the held heads' columns and
rows; the two down-maps, their norms and the latent are whole; the output
map's sum over the held heads goes on as it is, the other chips' terms
left out), `experts_held` experts of `n_routed_experts`, the tables' held
rows; the router, the shared expert, the dense MLP, every norm and all of
mHC are whole.  No code stands in for the absent chips or their
all-reduce.

Everything but the hyper-connections, the query and YaRN is
expert_decoder.py's builder and model function; a layer carries ONE value,
the streams [B, S, n, C], through its one-trip recurrence.  Name scopes:
`mhc.maps` and `mhc.mix` (ops/hyper_connection_ops.py; the span
`mhc.lower` a sublayer), `mla`, `moe.shared` and ops/moe_ops.py's
`moe.router`, `moe.dispatch`, `moe.experts`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from .. import layers
from ..initializer import NumpyArrayInitializer
from .common import ModelSpec
from .expert_decoder import ExpertDecoderConfig, _decoder, _ExpertBuilder

__all__ = ["HyperExpertDecoderConfig", "hyper_expert_decoder"]


@dataclasses.dataclass
class HyperExpertDecoderConfig(ExpertDecoderConfig):
    vocab_size: int = 16384
    max_length: int = 4096
    d_model: int = 3584
    d_inner: int = 9216
    n_head: int = 4                 # the heads HELD here, of the group's
    q_lora_rank: int = 768
    rope_theta: float = 10000.0
    # the published group: factor, original_max_position_embeddings,
    # beta_fast, beta_slow, mscale, mscale_all_dim; None: plain rotary
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-6
    top_k: int = 4
    d_expert: int = 1024
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4                # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    # the start: the three scalars, and H_res near the identity
    hc_alpha_init: float = 0.01
    hc_res_diag_init: float = 8.0


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


class _HyperBuilder(_ExpertBuilder):
    def array(self, name, value):
        value = np.asarray(value, np.float32)
        return self.param(list(value.shape), name,
                          initializer=NumpyArrayInitializer(value))

    def query(self, x, name):
        cfg = self.cfg
        low = self.norm(self.linear(x, cfg.d_model, cfg.q_lora_rank,
                                    f"{name}_qa"), f"{name}_qn")
        return self.linear(
            low, cfg.q_lora_rank, cfg.n_head
            * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), f"{name}_qb")

    def rope_scaling(self):
        cfg = self.cfg
        rs = cfg.rope_scaling
        if not rs:
            return {}
        over_all = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        return {
            "yarn": {
                "factor": rs["factor"],
                "original_length": rs["original_max_position_embeddings"],
                "beta_fast": rs["beta_fast"], "beta_slow": rs["beta_slow"],
                "attention_factor":
                    yarn_mscale(rs["factor"], rs["mscale"]) / over_all},
            "scale": (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * over_all ** 2}

    def connected(self, streams, name, sublayer):
        """(X', what `sublayer` returns besides its output) of one
        hyper-connected sublayer: `sublayer(x_in)` gives (y, ...)."""
        cfg = self.cfg
        n = cfg.hc_mult
        near_identity = cfg.hc_res_diag_init * np.eye(n)
        h, x_in = layers.mhc_maps_read(
            streams,
            self.param([n * cfg.d_model, 2 * n + n * n], f"{name}_phi"),
            *(self.constant([1], f"{name}_a_{m}", cfg.hc_alpha_init)
              for m in ("pre", "post", "res")),
            # H_pre 1 / n a stream (x_in starts as the streams' mean),
            # H_post 1 (a plain residual add), H_res near the identity
            self.constant([n], f"{name}_b_pre", -math.log(n - 1.0)),
            self.constant([n], f"{name}_b_post", 0.0),
            self.array(f"{name}_b_res", near_identity),
            sinkhorn_iters=cfg.hc_sinkhorn_iters, epsilon=cfg.rms_norm_eps,
            hc_eps=cfg.hc_eps, clamp_min=cfg.hc_clamp_min,
            clamp_max=cfg.hc_clamp_max)
        y, *rest = sublayer(x_in)
        return (layers.mhc_write(streams, h, y), *rest)

    def layer(self, streams, i):
        """(X', load or None, the router's bias or None) of layer i."""
        streams, = self.connected(streams, f"l{i}_hc_attn",
                                  lambda x: (self.mixer(x, i),))
        return self.connected(streams, f"l{i}_hc_ffn",
                              lambda x: self.feed_forward(x, i))

    def enter(self, h):
        return layers.mhc_streams(h, self.cfg.hc_mult)

    def leave(self, streams):
        return layers.reduce_sum(streams, dim=2)


def hyper_expert_decoder(cfg: Optional[HyperExpertDecoderConfig] = None,
                         tokens=None, labels=None) -> ModelSpec:
    return _decoder(_HyperBuilder(cfg or HyperExpertDecoderConfig()),
                    "hyper_expert_decoder", tokens, labels)

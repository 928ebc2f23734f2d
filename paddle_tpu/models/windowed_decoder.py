"""Decoder-only causal language model whose layers are of two kinds in a
pattern: grouped-query attention over a sliding window of keys with plain
rotary positions, and over every causal key with YaRN-scaled ones; every
MLP a softmax-routed expert block, as one chip of an expert-parallel group
runs it (Mellum2-12B-A2.5B-Instruct's language model;
benchmark/configs/mellum2-12b-a2.5b.json).

Layer i, pre-norm:    a  = h + Attn_i(N1(h))
                      h' = a + Moe(N2(a))
Attention:            q = W_q u -> H x D, k = W_k u -> G x D, v = W_v u ->
                      G x D; rotary on q and k; query head j reads
                      key/value head j // (H/G); softmax of q.k / sqrt(D)
                      over the keys the layer's kind lets a query see;
                      o = W_o concat(P v)
Kind, `layer_types[i]`:
  "sliding"           query t sees keys s with 0 <= t - s < sliding_window;
                      rotary at rope_theta, plain
  "full"              every s <= t; rotary at rope_theta under `yarn`
                      (factor, original_length, beta_fast, beta_slow,
                      attention_factor: ops/attention_ops.py::_rotate), or
                      plain where `yarn` is None
Expert block:         common.SoftmaxExpertShare (sparse_decoder.py's)
Output:               logits = W_head N_f(h_L); mean cross entropy

The chip's share is expert_decoder.py's: `experts_held` experts from
`expert_offset` on of `n_routed_experts`, the router whole.  The norm, the
linear map and the head with its cross entropy are looped_decoder.py's, the
parameter maker expert_decoder.py's; every layer is a one-trip
layers.Recurrence, the unit of recomputation (common.one_trip_layer).  One
op, `fused_attention` with `window`, is both kinds' attention: the Pallas
flash kernels on a TPU (K and V at G heads, never repeated), jax.numpy
elsewhere.  Name scopes `attn.sliding` and `attn.full` (the attention op
alone, the projections and the rotary outside) and ops/moe_ops.py's `moe.*`
group the device's time in a trace.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..param_attr import ParamAttr
from .common import (ModelSpec, SoftmaxExpertShare, one_trip_layer,
                     packed_batch)
from .expert_decoder import _ExpertBuilder
from .looped_decoder import _heads_and_loss

__all__ = ["WindowedDecoderConfig", "windowed_decoder"]

SLIDING, FULL = "sliding", "full"


@dataclasses.dataclass
class WindowedDecoderConfig:
    vocab_size: int = 12288
    max_length: int = 16384
    d_model: int = 2304
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    # a layer's kind, one entry a layer: the depth is their count
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL)
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    # the full layers' rotary: factor, original_length, beta_fast,
    # beta_slow, attention_factor (None: plain, like the sliding layers')
    yarn: Optional[Dict[str, float]] = None
    rms_norm_eps: float = 1e-6
    n_routed_experts: int = 64      # the router's width
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 8
    d_expert: int = 896
    norm_topk_prob: bool = True
    # False: the router takes no gradient (common.SoftmaxExpertShare)
    train_router: bool = True
    use_recompute: bool = True
    init_std: float = 0.02
    residual_init_layers: int = 0   # common.SoftmaxExpertShare
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)


class _WindowedBuilder(SoftmaxExpertShare, _ExpertBuilder):
    def heads(self, t, n, yarn=None, rotate=True):
        """[B, S, n * D] -> [B, n, S, D], rotated."""
        cfg = self.cfg
        t = layers.transpose(
            layers.reshape(t, shape=[0, 0, n, cfg.head_dim]),
            perm=[0, 2, 1, 3])
        if not rotate:
            return t
        return layers.rotary_embedding(t, base=cfg.rope_theta, yarn=yarn)

    def attention(self, u, name, kind):
        cfg = self.cfg
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        yarn = cfg.yarn if kind == FULL else None
        q = self.heads(self.linear(u, cfg.d_model, H * D, f"{name}_q"), H,
                       yarn)
        k = self.heads(self.linear(u, cfg.d_model, G * D, f"{name}_k"), G,
                       yarn)
        v = self.heads(self.linear(u, cfg.d_model, G * D, f"{name}_v"), G,
                       rotate=False)
        with name_scope(f"attn.{kind}"):
            ctx = layers.fused_attention(
                q, k, v, causal=True,
                window=cfg.sliding_window if kind == SLIDING else None,
                rope="yarn" if yarn else "plain")
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, H * D])
        return layers.matmul(ctx, self.residual_param(
            [H * D, cfg.d_model], f"{name}_o_w"))

    def layer(self, h, i):
        name, kind = f"l{i}", self.cfg.layer_types[i]
        attn = self.attention(self.norm(h, f"{name}_n1"), f"{name}_attn",
                              kind)
        a = layers.elementwise_add(h, attn)
        out = self.expert_block(self.norm(a, f"{name}_n2"), name)
        return layers.elementwise_add(a, out)


def windowed_decoder(cfg: Optional[WindowedDecoderConfig] = None,
                     tokens=None, labels=None) -> ModelSpec:
    cfg = cfg or WindowedDecoderConfig()
    if set(cfg.layer_types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types {cfg.layer_types}: a layer is "
                         f"'{SLIDING}' or '{FULL}'")
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _WindowedBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    for i in range(cfg.n_layer):
        h, _ = one_trip_layer(
            h, lambda carried, i=i: (b.layer(carried, i), []),
            cfg.use_recompute)
    states = layers.unsqueeze(b.norm(h, "final"), axes=[0])   # one "trip"
    loss, logits, _ = _heads_and_loss(b, states, labels)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name="windowed_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits},
    )

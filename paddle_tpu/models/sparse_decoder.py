"""Decoder-only causal language model with grouped-query attention under a
learned sparse index and softmax-routed expert layers, as one chip of an
expert-parallel group runs it (Qwen3-MoE's block with DeepSeek-V3.2-Exp's
sparse attention: the language model of Keye-VL-2.0-30B-A3B;
benchmark/configs/keye-vl-2.0-30b-a3b.json).

Layer, pre-norm:      a  = h + Attn(N1(h))
                      h' = a + Moe(N2(a))
Attention:            q = W_q u -> H x D, k = W_k u -> G x D, v = W_v u ->
                      G x D; RMSNorm over the D features of every q and k
                      head; rotary by `mrope_section` from three position
                      streams; query head j reads key/value head j // (H/G);
                      softmax of q.k / sqrt(D) over the keys S_t the index
                      chose; o = W_o concat(P v)
Index (on u' = stop_gradient(u)):
                      q_i = W_iq u' -> Hi x Di, k_i = LayerNorm(W_ik u') ->
                      Di (one a token), w = W_iw u' / sqrt(Hi Di); the same
                      rotary on q_i and k_i; I[t, s] = sum_j w[t, j]
                      relu(q_i[t, j].k_i[s]); S_t the `index_topk` positions
                      s <= t of largest I
Index loss:           L_I = mean over t of KL(the heads' mean probabilities
                      over S_t, detached || softmax of I over S_t), summed
                      over the layers; the step fetches cross entropy + L_I
Expert block:         s = softmax(W_r x) over all the experts; the top_k
                      chosen; weights s over the chosen / their sum;
                      y = sum over the HELD chosen experts of g_i E_i(x)
Output:               logits = W_head N_f(h_L); mean cross entropy

The chip's share is expert_decoder.py's: `experts_held` experts from
`expert_offset` on of `n_routed_experts`, the router whole.  The norm, the
linear map and the head with its cross entropy are looped_decoder.py's, the
parameter maker expert_decoder.py's; every layer is a one-trip
layers.Recurrence, the unit of recomputation.  Name scopes `gqa` (the
attention block), `dsa.index` (the index's projections; its scoring, and
`dsa.select`, `dsa.attend`, `dsa.kl`, come from kernels/sparse_attention.py)
and ops/moe_ops.py's `moe.*` group the device's time in a trace.
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

__all__ = ["SparseDecoderConfig", "sparse_decoder"]


@dataclasses.dataclass
class SparseDecoderConfig:
    vocab_size: int = 18992
    max_length: int = 16384
    n_layer: int = 4
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: Tuple[int, ...] = (16, 24, 24)   # pairs a position stream
    rms_norm_eps: float = 1e-6
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    q_chunk: int = 512
    kv_chunk: int = 512
    n_routed_experts: int = 128     # the router's width
    experts_held: int = 16          # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 8
    d_expert: int = 768
    norm_topk_prob: bool = True
    use_recompute: bool = True
    init_std: float = 0.02
    # the projections that write into the residual stream (attention's o,
    # the experts' down) start at init_std / sqrt(2 x this many layers):
    # the published depth, whatever depth is held (0: init_std like the rest)
    residual_init_layers: int = 0
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False


def _sections(sections, pairs: int) -> list:
    """`sections` scaled to `pairs` rotary pairs in the same proportions
    (the index's heads are narrower than the attention's)."""
    total = sum(sections)
    if any(n * pairs % total for n in sections):
        raise ValueError(f"sections {sections} do not scale to {pairs} pairs")
    return [n * pairs // total for n in sections]


class _SparseBuilder(SoftmaxExpertShare, _ExpertBuilder):
    def heads(self, t, n, width, positions, norm=None):
        """[B, S, n * width] -> [B, n, S, width], each head normalised
        (`norm` names the scale) and rotated."""
        t = layers.reshape(t, shape=[0, 0, n, width])
        if norm:
            t = self.norm(t, norm)
        t = layers.transpose(t, perm=[0, 2, 1, 3])
        if positions is None:
            return t
        return layers.rotary_embedding(
            t, base=self.cfg.rope_theta, positions=positions,
            sections=_sections(self.cfg.mrope_section, width // 2))

    def index(self, u, name, positions):
        """(q_i [B, Hi, S, Di], k_i [B, S, Di], w [B, S, Hi]) from the
        layer's normalised input, which takes no gradient from them."""
        cfg = self.cfg
        hi, di = cfg.index_heads, cfg.index_dim
        u = layers.detach(u)
        q_i = self.heads(self.linear(u, cfg.d_model, hi * di, f"{name}_q"),
                         hi, di, positions)
        k_i = layers.layer_norm(
            self.linear(u, cfg.d_model, di, f"{name}_k"), begin_norm_axis=2,
            epsilon=cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{name}_kn_scale"),
            bias_attr=ParamAttr(name=f"{name}_kn_bias"))
        k_i = layers.rotary_embedding(
            k_i, base=cfg.rope_theta, positions=positions,
            sections=_sections(cfg.mrope_section, di // 2))
        w = layers.scale(self.linear(u, cfg.d_model, hi, f"{name}_w"),
                         scale=float(hi * di) ** -0.5)
        return q_i, k_i, w

    def sparse_attention(self, u, name, positions):
        """(the block's output, the index's loss)."""
        cfg = self.cfg
        H, G, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = self.heads(self.linear(u, cfg.d_model, H * D, f"{name}_q"),
                       H, D, positions, norm=f"{name}_qn")
        k = self.heads(self.linear(u, cfg.d_model, G * D, f"{name}_k"),
                       G, D, positions, norm=f"{name}_kn")
        v = self.heads(self.linear(u, cfg.d_model, G * D, f"{name}_v"),
                       G, D, None)
        with name_scope("dsa.index"):
            q_i, k_i, w = self.index(u, f"{name}_index", positions)
        ctx, index_loss = layers.sparse_attention(
            q, k, v, q_i, k_i, w, topk=cfg.index_topk, q_chunk=cfg.q_chunk,
            kv_chunk=cfg.kv_chunk)
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, H * D])
        out = layers.matmul(ctx, self.residual_param(
            [H * D, cfg.d_model], f"{name}_o_w"))
        return out, index_loss

    def layer(self, h, i, positions):
        """(h', the layer's index loss)."""
        name = f"l{i}"
        with name_scope("gqa"):
            attn, index_loss = self.sparse_attention(
                self.norm(h, f"{name}_n1"), f"{name}_attn", positions)
        a = layers.elementwise_add(h, attn)
        out = self.expert_block(self.norm(a, f"{name}_n2"), name)
        return layers.elementwise_add(a, out), index_loss


def sparse_decoder(cfg: Optional[SparseDecoderConfig] = None, tokens=None,
                   labels=None, positions=None) -> ModelSpec:
    cfg = cfg or SparseDecoderConfig()
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    if positions is None:   # a token's temporal, height and width position
        positions = layers.data("positions", [len(cfg.mrope_section), S],
                                dtype="int32")
    b = _SparseBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    index_losses = []
    for i in range(cfg.n_layer):
        def body(carried, i=i):
            out, index_loss = b.layer(carried, i, positions)
            return out, [index_loss]

        h, rec = one_trip_layer(h, body, cfg.use_recompute)
        index_losses.append(layers.reduce_sum(rec()))
    states = layers.unsqueeze(b.norm(h, "final"), axes=[0])   # one "trip"
    cross_entropy, logits, _ = _heads_and_loss(b, states, labels)
    index_loss = layers.sums(index_losses)
    loss = layers.elementwise_add(cross_entropy, index_loss)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        """Packed text: ids uniform over the vocabulary held here, the
        labels the ids shifted by one, the three position streams equal."""
        pos = np.broadcast_to(np.arange(S, dtype=np.int32),
                              (batch_size, len(cfg.mrope_section), S))
        return {**packed_batch(cfg.vocab_size, S, batch_size, seed,
                               tokens.name, labels.name),
                positions.name: np.ascontiguousarray(pos)}

    return ModelSpec(
        name="sparse_decoder",
        feed_names=[tokens.name, labels.name, positions.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits,
                "cross_entropy": cross_entropy, "index_loss": index_loss},
    )

"""Decoder-only causal language model with multi-head latent attention (MLA)
and sparse expert layers, as one chip of an expert-parallel group runs it
(DeepSeek-V3's family; benchmark/configs/moonlight-16b-a3b.json).

Layer, pre-norm:      a  = h + MLA(N1(h))
                      h' = a + F(N2(a))
F:                    a gated MLP in the first `first_k_dense` layers, the
                      expert block in every later one
MLA:                  q = W_q x -> H x (nope | rope);  W_kva x -> c | k_rope
                      (one rotary key part a token);  W_kvb N_kv(c) -> H x
                      (k_nope | v);  k = [k_nope | k_rope]; causal softmax of
                      q.k / sqrt(nope + rope);  o = W_o concat(P v)
Expert block:         s = sigmoid(W_r x); the top_k of s + b chosen; weights
                      s over the chosen / their sum * scaling;
                      y = sum over the HELD experts of g_i E_i(x) + Sh(x)
b:                    state, no gradient; after a step b_i += gamma *
                      sign(mean load - load_i)
Output:               logits = W_head N_f(h_L); mean cross entropy

The chip's share: `experts_held` experts from `expert_offset` on of
`n_routed_experts`; the router keeps its width and its top_k; the shared
experts, attention and the dense layers are whole.  The norm, the linear
map, the gated MLP and the head with its cross entropy are
looped_decoder.py's; every layer is a one-trip layers.Recurrence, so that
under `use_recompute` the layer is the unit of recomputation.  Name scopes
`mla` and `moe.shared` (here) and `moe.router`, `moe.dispatch`,
`moe.experts` (ops/moe_ops.py) group the device's time in a trace.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..initializer import ConstantInitializer, UniformInitializer
from ..param_attr import ParamAttr
from .common import ModelSpec, one_trip_layer, packed_batch
from .looped_decoder import _Builder, _heads_and_loss

__all__ = ["ExpertDecoderConfig", "expert_decoder"]


@dataclasses.dataclass
class ExpertDecoderConfig:
    vocab_size: int = 20480
    max_length: int = 2048
    n_layer: int = 5
    first_k_dense: int = 1
    d_model: int = 2048
    d_inner: int = 11264            # the dense layers' MLP
    n_head: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 50000.0
    mla_rope: str = "rotary"        # rotary | none (no positions)
    rms_norm_eps: float = 1e-5
    n_routed_experts: int = 64      # the router's width
    experts_held: int = 8           # this chip's experts ...
    expert_offset: int = 0          # ... from this one on
    top_k: int = 6
    d_expert: int = 1408
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True
    bias_update_gamma: float = 0.001
    use_recompute: bool = True
    init_std: float = 0.02
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False


class _ExpertBuilder(_Builder):
    def param(self, shape, name, **attr):
        return layers.create_parameter(
            shape, "float32",
            attr=ParamAttr(name=name, **{"initializer": self.init, **attr}))

    def conv_param(self, shape, name, fan_in):
        """A convolution's weight or bias: U(+-1 / sqrt(fan_in))."""
        bound = fan_in ** -0.5
        return self.param(shape, name,
                          initializer=UniformInitializer(-bound, bound))

    def constant(self, shape, name, value, **attr):
        return self.param(shape, name,
                          initializer=ConstantInitializer(value), **attr)

    def query(self, x, name):
        """The heads' queries [B, S, H * (nope + rope)] of x: one map."""
        cfg = self.cfg
        return self.linear(
            x, cfg.d_model, cfg.n_head
            * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim), f"{name}_q")

    def rope_scaling(self):
        """layers.latent_attention's `yarn` and `scale`: none here."""
        return {}

    def latent_attention(self, x, name):
        cfg = self.cfg
        H, dn, dr, dv = (cfg.n_head, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        q = self.query(x, name)
        latent, k_rope = layers.split(
            self.linear(x, cfg.d_model, cfg.kv_lora_rank + dr,
                        f"{name}_kva"),
            [cfg.kv_lora_rank, dr], dim=-1)
        ctx = layers.latent_attention(
            q, self.norm(latent, f"{name}_kvn"), k_rope,
            self.param([cfg.kv_lora_rank, H * (dn + dv)], f"{name}_kvb_w"),
            n_head=H, qk_nope_head_dim=dn, qk_rope_head_dim=dr,
            v_head_dim=dv, rope_base=cfg.rope_theta,
            rope=cfg.mla_rope, **self.rope_scaling())
        return self.linear(ctx, H * dv, cfg.d_model, f"{name}_o")

    def expert_block(self, x, name):
        """(the block's output, the tokens each expert was chosen by, the
        router's selection bias)."""
        cfg = self.cfg
        held, d, f = cfg.experts_held, cfg.d_model, cfg.d_expert
        bias = layers.create_parameter(
            [cfg.n_routed_experts], "float32",
            attr=ParamAttr(name=f"{name}_router_bias", trainable=False),
            default_initializer=ConstantInitializer(0.0))
        bias.stop_gradient = True   # state: it follows the load, no gradient
        idx, weight, load = layers.moe_router(
            x, self.param([d, cfg.n_routed_experts], f"{name}_router_w"),
            bias, top_k=cfg.top_k, scaling=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob)
        routed = layers.moe_experts(
            x, idx, weight,
            self.param([held, d, f], f"{name}_experts_gate_w"),
            self.param([held, d, f], f"{name}_experts_up_w"),
            self.param([held, f, d], f"{name}_experts_down_w"),
            experts_total=cfg.n_routed_experts,
            expert_offset=cfg.expert_offset)
        with name_scope("moe.shared"):
            shared = self.mlp(x, f"{name}_shared",
                              cfg.n_shared_experts * f)
        return layers.elementwise_add(routed, shared), load, bias

    def mixer(self, h, i):
        """Mix_i(N1(h)): what layer i adds to the stream first."""
        with name_scope("mla"):
            return self.latent_attention(self.norm(h, f"l{i}_n1"),
                                         f"l{i}_attn")

    def feed_forward(self, a, i):
        """(F_i(N2(a)), load or None, the router's bias or None): what
        layer i adds to the stream second."""
        name = f"l{i}"
        x = self.norm(a, f"{name}_n2")
        if i < self.cfg.first_k_dense:
            return self.mlp(x, f"{name}_mlp"), None, None
        return self.expert_block(x, name)

    def layer(self, h, i):
        """(h', load or None, the router's bias or None) of layer i."""
        a = layers.elementwise_add(h, self.mixer(h, i))
        out, load, bias = self.feed_forward(a, i)
        return layers.elementwise_add(a, out), load, bias

    def enter(self, h):
        """What the layers carry, of the embedded tokens: the one stream."""
        return h

    def leave(self, h):
        """What the final norm reads, of what the layers carried."""
        return h


def expert_decoder(cfg: Optional[ExpertDecoderConfig] = None, tokens=None,
                   labels=None) -> ModelSpec:
    return _decoder(_ExpertBuilder(cfg or ExpertDecoderConfig()),
                    "expert_decoder", tokens, labels)


def _decoder(b: _ExpertBuilder, name: str, tokens=None,
             labels=None) -> ModelSpec:
    """The model of the builder `b`: its `layer` a one-trip recurrence a
    layer, each router's bias stepped after the step's routing read it,
    looped_decoder's head."""
    cfg = b.cfg
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")

    h = b.enter(layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.d_model],
        param_attr=ParamAttr(name="embed", initializer=b.init)))
    loads = []
    for i in range(cfg.n_layer):
        routed = []

        def body(carried, i=i, routed=routed):
            out, load, bias = b.layer(carried, i)
            routed.append((load, bias))
            return out, [] if load is None else [load]

        h, rec = one_trip_layer(h, body, cfg.use_recompute)
        load, bias = routed[0]
        if load is not None:
            # after the step's routing has read it: the bias follows the
            # load, outside the gradient
            loads.append(rec())
            layers.moe_bias_update(bias, loads[-1], cfg.bias_update_gamma)
    states = layers.unsqueeze(b.norm(b.leave(h), "final"),
                              axes=[0])                       # one "trip"
    loss, logits, _ = _heads_and_loss(b, states, labels)

    def synthetic_batch(batch_size: int, seed: int = 0) -> Dict[str, np.ndarray]:
        """Packed sequences: ids uniform over the vocabulary held here, the
        labels the ids shifted by one, no padding."""
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name=name,
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits, "loads": loads},
    )

"""Decoder-only causal language model in the decoder-hybrid-decoder layout
(SambaY: Ren et al., arXiv:2507.06607, as Phi-4-mini-flash-reasoning's
modelling code runs it; benchmark/configs/phi-4-mini-flash.json): a
self-decoder that alternates Mamba-1 mixers with sliding-window attention,
one Mamba layer that hands its scan's output on as a MEMORY, one
full-attention layer that hands its keys and values on, and a cross-decoder
that alternates Gated Memory Units reading that memory with cross-attention
layers reading those keys and values.  Every attention is differential
attention; there is no positional encoding anywhere.

d = `d_model`, E = `expand` d channels, N = `d_state`, R = `dt_rank`, H =
`n_head` heads of D = d / H over G = `n_kv_head`, w = `sliding_window`.
LN = LayerNorm with weight and bias.

Layer l:      a  = h + Mix_l(LN1(h))
              h' = a + W2(silu(g) * u), (g, u) = split(W1 LN2(a)), no bias
The layout (`layer_kinds`): `self_periods` x (mamba, sliding), then (memory,
full), then `cross_periods` x (gmu, cross); the published 32 layers are 8
and 7 periods (9 Mamba : 8 sliding : 1 full : 7 GMU : 7 cross), which
`layer_kinds(32)` gives from the depth alone (a multiple of 4).
mamba(u):     (x, z) = split(W_in u), each E; x = silu(conv(x) + b_c)
              (layers.short_conv1d: depthwise, causal, `d_conv` taps);
              (r, B, C) = split(W_x x) of R, N, N; y = layers.
              selective_scan(x, W_dt r, A = -exp(A_log), B, C, D, b_dt):
              dt = softplus(W_dt r + b_dt), s_t = exp(dt_t A) s_(t-1) +
              dt_t x_t B_t from 0, y_t = s_t C_t + D x_t;
              mamba(u) = W_out(y * silu(z))
memory:       a mamba layer that also hands out m = y, the scan's output
              with its D term, BEFORE the gate
gmu(u):       W_o(m * silu(W_i u)), m the memory at the same position
DiffAttn:     layers.differential_attention: heads in adjacent pairs, two
              softmax maps a pair at scale D^-1/2 over the pair's two value
              heads side by side, (1 - lambda_0) RMSNorm(A1 - lambda A2)
              with lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_0,
              lambda_0 = 0.8 - 0.6 exp(-0.3 l), l the layer's index here
sliding, full: (q, k, v) = W_qkv u + b_qkv; causal, in a sliding layer
              also t - s < w; W_o DiffAttn + b_o.  The full layer hands
              out its k and v (there is nothing to rotate)
cross(u):     q = W_q u + b_q alone; k, v the full layer's; causal; its own
              lambdas, scale, W_o, b_o
Output:       logits = LN_f(h_L) Emb^T (tied, no bias); mean cross entropy
              (looped_decoder._heads_and_loss with the table)

The chip's share: `vocab_size` rows of the tied table, from row 0 (the
tables vocabulary-parallel: ids, logits and loss over the rows held); every
layer whole.

Every layer is a one-trip layers.Recurrence, the unit of recomputation
(common.one_trip_layer).  The memory and the key/value pair are values one
unit hands out (`rec.output`) and LATER units read from outside their
bodies, as a body reads a parameter: each is computed once a step, a
reader's recomputation takes it as an input and never makes it again, and
core/backward.py sums the readers' cotangents into the ONE cotangent the
producer's unit is differentiated with (tests/test_sambay_decoder.py holds
all three).  What a layer's recomputation does NOT make again: what the
kernels keep (the scan's output and chunk starts, a flash site's output and
logsumexp) and W1's output (layers.kept, common.kept_gated_mlp).  Name
scopes: `ssm.mix` (the convolution with its SiLU, the step's softplus, the
gate), `ssm.scan` (the op selective_scan's own),
`gmu` (the whole mixer), `attn.sliding`, `attn.full`, `attn.cross` (the op
differential_attention; the projections outside), `mlp` (both products and
what lies between them), `loop.heads`.  Spans at
lowering: `ssm.lower`, `attn.lower`, `flash.plan` / `flash.bwd_plan`, and
`shared.lower` (`what` memory | kv, `bytes`, `readers`) where a value is
handed on.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..initializer import (Initializer, NormalInitializer,
                           NumpyArrayInitializer, UniformInitializer)
from ..param_attr import ParamAttr
from .common import (ModelSpec, kept_gated_mlp, one_trip_layer,
                     packed_batch)
from .expert_decoder import _ExpertBuilder
from .looped_decoder import _heads_and_loss

__all__ = ["SambaYDecoderConfig", "sambay_decoder", "layer_kinds"]

MAMBA, MEMORY, SLIDING, FULL, GMU, CROSS = (
    "mamba", "memory", "sliding", "full", "gmu", "cross")


def layer_kinds(n_layer: Optional[int] = None,
                self_periods: Optional[int] = None,
                cross_periods: Optional[int] = None) -> Tuple[str, ...]:
    """A layer's kind, one entry a layer.  From the periods, or from the
    published rule and the depth alone: a multiple of 4, whose first half
    is the self-decoder's (mamba, sliding) periods, then the layer that
    hands out the memory and the one that hands out K and V, then (gmu,
    cross) to the end."""
    if self_periods is None or cross_periods is None:
        if not n_layer or n_layer % 4:
            raise ValueError(f"the published layout needs a depth that is "
                             f"a multiple of 4, not {n_layer}")
        self_periods, cross_periods = n_layer // 4, n_layer // 4 - 1
    kinds = (MAMBA, SLIDING) * self_periods + (MEMORY, FULL) \
        + (GMU, CROSS) * cross_periods
    if n_layer is not None and len(kinds) != n_layer:
        raise ValueError(f"{self_periods} + 1 + {cross_periods} periods are "
                         f"{len(kinds)} layers, not {n_layer}")
    return kinds


@dataclasses.dataclass
class SambaYDecoderConfig:
    vocab_size: int = 25008         # rows of the tied table held here
    max_length: int = 8192
    d_model: int = 2560
    d_inner: int = 10240
    n_head: int = 40
    n_kv_head: int = 20
    sliding_window: int = 512
    # (mamba, sliding) periods before and (gmu, cross) periods after the
    # two layers that hand values on
    self_periods: int = 1
    cross_periods: int = 1
    expand: int = 2
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    layer_norm_eps: float = 1e-5
    use_recompute: bool = True
    init_std: float = 0.02
    lambda_std: float = 0.1
    # what looped_decoder's head reads: one trip, no exit gate
    loop_steps: int = 1
    exit_gate: bool = False

    @property
    def kinds(self) -> Tuple[str, ...]:
        return layer_kinds(None, self.self_periods, self.cross_periods)

    @property
    def n_layer(self) -> int:
        return len(self.kinds)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# the step a channel starts with: log-uniform between these
_DT_RANGE = (1e-3, 1e-1)


class _InverseSoftplusOfLogUniform(Initializer):
    """b with softplus(b) log-uniform in [low, high]: exp of a uniform
    draw, then log(exp(.) - 1), drawn by the start-up program's own
    generator."""

    def __init__(self, low: float, high: float):
        self.low, self.high = math.log(low), math.log(high)

    def __call__(self, var, block):
        UniformInitializer(self.low, self.high)(var, block)
        same = dict(inputs={"X": [var.name]}, outputs={"Out": [var.name]})
        block.append_op(type="exp", attrs={}, **same)
        block.append_op(type="exp", attrs={}, **same)
        block.append_op(type="scale", attrs={"scale": 1.0, "bias": -1.0},
                        **same)
        return block.append_op(type="log", attrs={}, **same)


class _SambaYBuilder(_ExpertBuilder):
    def __init__(self, cfg):
        super().__init__(cfg)
        self.channels = cfg.expand * cfg.d_model
        self.head_dim = cfg.d_model // cfg.n_head
        self.kv_width = cfg.n_kv_head * self.head_dim
        self.lambda_start = NormalInitializer(0.0, cfg.lambda_std)
        # the later layers that read what a layer hands out
        self.readers = {k: cfg.kinds.count(k) for k in (GMU, CROSS)}

    def norm(self, x, name):
        return layers.layer_norm(
            x, begin_norm_axis=2, epsilon=self.cfg.layer_norm_eps,
            param_attr=ParamAttr(name=f"{name}_scale"),
            bias_attr=ParamAttr(name=f"{name}_bias"))

    def affine(self, x, d_in, d_out, name):
        return layers.elementwise_add(
            self.linear(x, d_in, d_out, name),
            self.constant([d_out], f"{name}_b", 0.0))

    def product(self, x, d_in, d_out, name):
        """x W with the fp32 accumulator handed out, whatever the AMP tier
        makes of the operands."""
        return layers.matmul(x, self.param([d_in, d_out], f"{name}_w"),
                             out_dtype="float32")

    # W1's output survives the layer's recomputation
    mlp = kept_gated_mlp

    def mamba(self, u, name):
        """(Mamba(u), the scan's output y)."""
        cfg = self.cfg
        E, N, R = self.channels, cfg.d_state, cfg.dt_rank
        x, z = layers.split(self.linear(u, cfg.d_model, 2 * E, f"{name}_in"),
                            2, dim=-1)
        with name_scope("ssm.mix"):
            x = layers.short_conv1d(
                x, self.conv_param([cfg.d_conv, E], f"{name}_conv_w",
                                   cfg.d_conv),
                "silu", bias=self.conv_param([E], f"{name}_conv_b",
                                             cfg.d_conv))
        # what the scan reads of these two products is their fp32
        # accumulator, not rounded on the way (the kernels take fp32)
        r, b, c = layers.split(self.product(x, E, R + 2 * N, f"{name}_x"),
                               [R, N, N], dim=-1)
        a = layers.scale(layers.exp(self.param(
            [E, N], f"{name}_a_log", initializer=NumpyArrayInitializer(
                np.log(np.tile(np.arange(1, N + 1, dtype=np.float32),
                               (E, 1)))))), scale=-1.0)
        y = layers.selective_scan(
            x, self.product(r, R, E, f"{name}_dt"), a, b, c,
            self.constant([E], f"{name}_d", 1.0),
            dt_bias=self.param([E], f"{name}_dt_b", initializer=(
                _InverseSoftplusOfLogUniform(*_DT_RANGE))))
        with name_scope("ssm.mix"):
            gated = layers.elementwise_mul(y, layers.swish(z))
        return self.linear(gated, E, cfg.d_model, f"{name}_out"), y

    def gmu(self, u, memory, name):
        cfg = self.cfg
        with name_scope("gmu"):
            gate = layers.swish(self.linear(u, cfg.d_model, self.channels,
                                            f"{name}_in"))
            return self.linear(layers.elementwise_mul(memory, gate),
                               self.channels, cfg.d_model, f"{name}_out")

    def attention(self, u, name, kind, layer, shared=None):
        """(Attn(u), (k, v) of this layer or None under `shared`, which is
        the (k, v) a cross layer reads)."""
        cfg = self.cfg
        d, D = cfg.d_model, self.head_dim
        if shared is None:
            q, k, v = layers.split(
                self.affine(u, d, d + 2 * self.kv_width, f"{name}_qkv"),
                [d, self.kv_width, self.kv_width], dim=-1)
        else:
            q, (k, v) = self.affine(u, d, d, f"{name}_q"), shared
        vectors = [self.param([D], f"{name}_lambda_{m}",
                              initializer=self.lambda_start)
                   for m in ("q1", "k1", "q2", "k2")]
        with name_scope(f"attn.{kind}"):
            ctx = layers.differential_attention(
                q, k, v, *vectors,
                self.constant([2 * D], f"{name}_subln_scale", 1.0),
                n_head=cfg.n_head, lambda_init=lambda_init(layer),
                window=cfg.sliding_window if kind == SLIDING else None,
                epsilon=cfg.layer_norm_eps)
        return self.affine(ctx, d, d, f"{name}_o"), \
            (None if shared is not None else (k, v))

    def layer(self, h, i, kind, memory=None, shared=None):
        """(h', the values layer i hands out: the memory of a `memory`
        layer, k and v of a `full` one)."""
        name = f"l{i}"
        u = self.norm(h, f"{name}_n1")
        handed = []
        if kind in (MAMBA, MEMORY):
            mixed, y = self.mamba(u, f"{name}_ssm")
            if kind == MEMORY:
                handed = [layers.handed_on(y, "memory", self.readers[GMU])]
        elif kind == GMU:
            mixed = self.gmu(u, memory, f"{name}_gmu")
        else:
            mixed, own = self.attention(
                u, f"{name}_attn", kind, i,
                shared if kind == CROSS else None)
            if kind == FULL:
                handed = [layers.handed_on(t, "kv", self.readers[CROSS])
                          for t in own]
        a = layers.elementwise_add(h, mixed)
        out = layers.elementwise_add(
            a, self.mlp(self.norm(a, f"{name}_n2"), f"{name}_mlp"))
        return out, handed


def sambay_decoder(cfg: Optional[SambaYDecoderConfig] = None, tokens=None,
                   labels=None) -> ModelSpec:
    cfg = cfg or SambaYDecoderConfig()
    if cfg.n_head % 2 or cfg.n_kv_head % 2 or cfg.n_head % cfg.n_kv_head:
        raise ValueError(f"{cfg.n_head} query heads over {cfg.n_kv_head} "
                         "key/value heads do not pair")
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _SambaYBuilder(cfg)
    kinds = cfg.kinds

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    table = h.block.program.global_block().var("embed")
    memory = shared = None
    for i, kind in enumerate(kinds):
        h, rec = one_trip_layer(
            h, lambda carried, i=i, kind=kind: b.layer(
                carried, i, kind, memory, shared), cfg.use_recompute,
            prevent_cse=True)
        # one trip: the leading axis of what a unit hands out is 1
        if kind == MEMORY:
            memory = layers.squeeze(rec(), axes=[0])
        elif kind == FULL:
            shared = tuple(layers.squeeze(t, axes=[0]) for t in rec())
    with name_scope("loop.heads"):
        states = layers.unsqueeze(b.norm(h, "final"), axes=[0])
        loss, logits, _ = _heads_and_loss(b, states, labels, table=table)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        return packed_batch(cfg.vocab_size, S, batch_size, seed,
                            tokens.name, labels.name)

    return ModelSpec(
        name="sambay_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits, "states": states,
                "memory": memory, "shared": shared},
    )

"""Decoder-only byte-level language model whose attention is EVA in its
chunked form and whose one trunk feeds several byte-prediction heads, as one
chip of a group that shares the attention heads runs it (EvaByte;
benchmark/configs/evabyte-6.5b.json).

d = `d_model`, D = `head_dim`, w = `window_size`, c = `chunk_size`, P =
`pred_heads`, V = `vocab_size`.

Norm:         N(u) = u rsqrt(mean(u^2) + eps) (1 + g), g started at 0
Layer:        a  = h + EVA(N1(h))
              h' = a + W_down(silu(W_gate N2(a)) * W_up N2(a))
              the stream h and both adds in fp32, whatever the AMP tier
              makes of the sublayers (under the keep tier they hand back
              bf16, which is cast up before the add)
EVA(u):       q = W_q u, k = W_k u, v = W_v u, a head D wide; rotary at
              `rope_theta` on q and k (half-split pairs, absolute
              positions); a head's learned mu, phi in R^D pool every c
              rotated keys into one summary key (weights softmax(mu . k)
              over the chunk) and their values into one summary value
              (weights softmax(phi . k)); query t runs ONE softmax, scale
              D^-1/2, over the exact keys s <= t of its own window
              (s // w = t // w) and the summaries of every chunk of every
              window before it; EVA(u) = W_o concat_heads(o)
              (layers.eva_attention; kernels/eva_attention.py)
Output:       z = W_head N_f(h_L) in fp32 (the product's fp32 accumulator,
              not rounded), W_head [d, P V]; head i at position t is
              held to byte x_{t+1+i}, a position past the sequence's end
              left out; the loss the mean cross entropy over every target
              there is (looped_decoder._several_heads_loss).  Embedding and
              head untied.

The chip's share: `heads_held` attention heads from `head_offset` on of the
group's `n_head` (tensor-parallel by heads: W_q, W_k, W_v, mu and phi hold
the held heads' columns and rows, W_o their rows; the output map's sum over
the held heads goes on as it is, the other chips' terms left out); the MLP,
the norms, the embedding and the head are whole.  No code stands in for the
absent chips or their all-reduce.

Every layer is a one-trip layers.Recurrence, the unit of recomputation
(common.one_trip_layer).  Name scopes: `eva.pool` and `eva.attend` (inside
the op; projections and rotary outside), `loop.heads` (the head product and
the cross entropy).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import name_scope
from ..initializer import Initializer, NormalInitializer
from ..param_attr import ParamAttr
from .common import ModelSpec, one_trip_layer
from .expert_decoder import _ExpertBuilder
from .looped_decoder import IGNORED_LABEL, _several_heads_loss

__all__ = ["EvaDecoderConfig", "eva_decoder", "shifted_labels"]


@dataclasses.dataclass
class EvaDecoderConfig:
    vocab_size: int = 320
    max_length: int = 8192
    n_layer: int = 4
    d_model: int = 4096
    d_inner: int = 11008
    n_head: int = 32                # the group's heads
    heads_held: int = 8             # this chip's ...
    head_offset: int = 0            # ... from this one on
    head_dim: int = 128
    window_size: int = 2048
    chunk_size: int = 16
    pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    use_recompute: bool = True


def shifted_labels(ids: np.ndarray, heads: int) -> np.ndarray:
    """[B, S, heads] int64 of ids [B, S]: head i's target at position t is
    ids[t + 1 + i], IGNORED_LABEL where that lies past the end."""
    B, S = ids.shape
    out = np.full((B, S, heads), IGNORED_LABEL, np.int64)
    for i in range(heads):
        out[:, :max(S - 1 - i, 0), i] = ids[:, 1 + i:]
    return out


class _ClippedNormal(Initializer):
    """N(0, 1) clipped to +-1, times `scale`, drawn by the start-up
    program's own generator like every other weight (values baked into
    that program as a constant would make its executable another one for
    every seed: a compile a run, and an entry a seed in the compile
    cache)."""

    def __init__(self, scale: float):
        self.scale = float(scale)

    def __call__(self, var, block):
        NormalInitializer(0.0, 1.0)(var, block)
        same = dict(inputs={"X": [var.name]}, outputs={"Out": [var.name]})
        block.append_op(type="clip", attrs={"min": -1.0, "max": 1.0}, **same)
        return block.append_op(type="scale", attrs={"scale": self.scale},
                               **same)


class _EvaBuilder(_ExpertBuilder):
    def norm(self, x, name):
        return layers.rms_norm(
            x, begin_norm_axis=-1, epsilon=self.cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{name}_scale"), unit_offset=True)

    def pooling_vector(self, name):
        """A head's mu or phi [heads_held, D]: N(0, 1) clipped to +-1,
        times D^-1/2."""
        cfg = self.cfg
        return self.param([cfg.heads_held, cfg.head_dim], name,
                          initializer=_ClippedNormal(cfg.head_dim ** -0.5))

    def heads(self, t, rotate):
        """[B, S, held * D] -> [B, held, S, D], rotated."""
        cfg = self.cfg
        t = layers.transpose(
            layers.reshape(t, shape=[0, 0, cfg.heads_held, cfg.head_dim]),
            perm=[0, 2, 1, 3])
        return layers.rotary_embedding(t, base=cfg.rope_theta) if rotate \
            else t

    def attention(self, u, name):
        cfg = self.cfg
        wide = cfg.heads_held * cfg.head_dim
        q, k, v = (self.heads(self.linear(u, cfg.d_model, wide,
                                          f"{name}_{m}"), m != "v")
                   for m in "qkv")
        ctx = layers.eva_attention(
            q, k, v, self.pooling_vector(f"{name}_mu"),
            self.pooling_vector(f"{name}_phi"), window=cfg.window_size,
            chunk=cfg.chunk_size)
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, wide])
        return self.linear(ctx, wide, cfg.d_model, f"{name}_o")

    def add(self, stream, sublayer_out):
        """The residual add in fp32: the keep tier would take the fp32
        stream down to the sublayer's bf16 (core.amp.match_kept)."""
        return layers.elementwise_add(
            stream, layers.cast(sublayer_out, "float32"))

    def layer(self, h, i):
        name = f"l{i}"
        a = self.add(h, self.attention(self.norm(h, f"{name}_n1"),
                                       f"{name}_attn"))
        return self.add(a, self.mlp(self.norm(a, f"{name}_n2"),
                                    f"{name}_mlp"))


def eva_decoder(cfg: Optional[EvaDecoderConfig] = None, tokens=None,
                labels=None) -> ModelSpec:
    cfg = cfg or EvaDecoderConfig()
    if not 0 < cfg.heads_held <= cfg.n_head - cfg.head_offset:
        raise ValueError(f"heads {cfg.head_offset}..+{cfg.heads_held} are "
                         f"not among the group's {cfg.n_head}")
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S, cfg.pred_heads], dtype="int64")
    b = _EvaBuilder(cfg)

    h = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                         param_attr=ParamAttr(name="embed",
                                              initializer=b.init))
    for i in range(cfg.n_layer):
        h, _ = one_trip_layer(
            h, lambda carried, i=i: (b.layer(carried, i), []),
            cfg.use_recompute)
    with name_scope("loop.heads"):
        loss, logits = _several_heads_loss(b, b.norm(h, "final"), labels)

    def synthetic_batch(batch_size: int,
                        seed: int = 0) -> Dict[str, np.ndarray]:
        """Packed rows of bytes: ids uniform over the vocabulary, head i's
        labels the ids shifted by 1 + i, no padding."""
        ids = np.random.RandomState(seed).randint(
            0, cfg.vocab_size, size=(batch_size, S)).astype(np.int64)
        return {tokens.name: ids,
                labels.name: shifted_labels(ids, cfg.pred_heads)}

    return ModelSpec(
        name="eva_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "logits": logits},
    )

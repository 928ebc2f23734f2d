"""Decoder-only causal language model, optionally looped: one stack of layers
whose weights are read on every one of `loop_steps` trips (a recurrent-depth
/ universal-transformer decoder), with a learned exit gate after each trip and
the expected loss over the exit distribution as the training objective
(Ouro / LoopLM, ByteDance 2025; benchmark/configs/ouro-2.6b.json).

With `loop_steps=1` and `exit_gate=False` this is a plain decoder LM (RMSNorm,
rotary positions, SwiGLU, untied embedding and head), so the file is the
repo's decoder-only trainer and not one model's script.

One layer, on h:      a  = h + N2(Attn(N1(h)))          (sandwich norms: one
                      h' = a + N4(MLP(N3(a)))            before, one after)
One trip:             h_t = N_f(Stack(h_{t-1})), the same weights every trip
Heads, every trip:    logits_t = W_head h_t;  lambda_t = sigmoid(w_g.h_t + b_g)
Exit distribution:    p_t = lambda_t prod_{j<t}(1 - lambda_j) for t < R,
                      p_R = prod_{j<R}(1 - lambda_j)
Loss:                 mean over tokens of sum_t p_t CE(logits_t, y) - beta H(p)

The trips are a layers.Recurrence: the stack is lowered once, a tied weight
has one gradient and one optimizer op, and under `use_recompute` a trip is
the unit of recomputation.  Name scopes `loop.body` (the stack) and
`loop.heads` (head matmul, cross entropy, gate and loss) group the device's
time in a profiler trace.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import name_scope, recompute_scope
from ..initializer import ConstantInitializer, NormalInitializer
from ..param_attr import ParamAttr
from .common import ModelSpec

__all__ = ["LoopedDecoderConfig", "looped_decoder"]

# the label of a position that a prediction head has no target for
IGNORED_LABEL = -100


@dataclasses.dataclass
class LoopedDecoderConfig:
    vocab_size: int = 49152
    max_length: int = 2048          # positions a sequence, all of them real
    n_layer: int = 4
    n_head: int = 16
    head_dim: int = 128
    d_model: int = 2048
    d_inner: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # trips through the one stack (1: an ordinary decoder)
    loop_steps: int = 4
    # the learned exit gate and the expected loss over its distribution;
    # off, the loss is the last trip's cross entropy
    exit_gate: bool = True
    entropy_beta: float = 0.05
    # recompute each trip in the backward pass, keeping only its carry
    use_recompute: bool = True
    init_std: float = 0.02


class _Builder:
    def __init__(self, cfg: LoopedDecoderConfig):
        self.cfg = cfg
        self.init = NormalInitializer(0.0, cfg.init_std)

    def linear(self, x, d_in, d_out, name):
        w = layers.create_parameter(
            [d_in, d_out], "float32",
            attr=ParamAttr(name=f"{name}_w", initializer=self.init))
        return layers.matmul(x, w)

    def norm(self, x, name):
        return layers.rms_norm(
            x, begin_norm_axis=-1, epsilon=self.cfg.rms_norm_eps,
            param_attr=ParamAttr(name=f"{name}_scale"))

    def attention(self, x, name):
        cfg = self.cfg
        h, dh = cfg.n_head, cfg.head_dim

        def heads(t, rotate):
            t = layers.reshape(t, shape=[0, 0, h, dh])
            t = layers.transpose(t, perm=[0, 2, 1, 3])      # [B, H, S, dh]
            return layers.rotary_embedding(t, base=cfg.rope_theta) \
                if rotate else t

        q = heads(self.linear(x, cfg.d_model, h * dh, f"{name}_q"), True)
        k = heads(self.linear(x, cfg.d_model, h * dh, f"{name}_k"), True)
        v = heads(self.linear(x, cfg.d_model, h * dh, f"{name}_v"), False)
        # the Pallas flash kernel on a TPU, plain jax attention elsewhere
        ctx = layers.fused_attention(q, k, v, causal=True)
        ctx = layers.reshape(layers.transpose(ctx, perm=[0, 2, 1, 3]),
                             shape=[0, 0, h * dh])
        return self.linear(ctx, h * dh, cfg.d_model, f"{name}_o")

    def mlp(self, x, name, d_inner=None):
        cfg = self.cfg
        d_inner = d_inner or cfg.d_inner
        gate = layers.swish(self.linear(x, cfg.d_model, d_inner,
                                        f"{name}_gate"))
        up = self.linear(x, cfg.d_model, d_inner, f"{name}_up")
        return self.linear(layers.elementwise_mul(gate, up), d_inner,
                           cfg.d_model, f"{name}_down")

    def layer(self, h, name):
        attn = self.attention(self.norm(h, f"{name}_n1"), f"{name}_attn")
        a = layers.elementwise_add(h, self.norm(attn, f"{name}_n2"))
        mlp = self.mlp(self.norm(a, f"{name}_n3"), f"{name}_mlp")
        return layers.elementwise_add(a, self.norm(mlp, f"{name}_n4"))

    def stack(self, h):
        for i in range(self.cfg.n_layer):
            h = self.layer(h, f"l{i}")
        return self.norm(h, "final")


def _exit_distribution(lam):
    """p [R, B, S] from the gates lambda [R, B, S], fp32: p_t = lambda_t x
    the probability of not having left before t; the last trip takes what
    is left, so the p_t sum to 1."""
    gates = layers.unstack(lam, axis=0)
    left = layers.fill_constant_batch_size_like(
        gates[0], shape=[-1] + list(gates[0].shape[1:]), dtype="float32",
        value=1.0)
    ps = []
    for gate in gates[:-1]:
        ps.append(layers.elementwise_mul(gate, left))
        left = layers.elementwise_mul(
            left, layers.scale(gate, scale=-1.0, bias=1.0))
    return layers.stack(ps + [left], axis=0)


def _heads_and_loss(b, states, labels, table=None):
    """(loss, logits, exit distribution or None) from the trips' states
    [R, B, S, D]: all of them through the one head matmul and the one
    softmax_with_cross_entropy; gate, exit distribution, entropy and the
    weighted sum in fp32.  With `table` [vocabulary, D], the embedding's
    parameter, the head is that table transposed and no parameter of its
    own: one parameter, its two gradients summed."""
    cfg = b.cfg
    R = cfg.loop_steps
    gated = cfg.exit_gate and R > 1
    if not gated and R > 1:  # the last trip decodes; the others have no head
        states = layers.slice(states, axes=[0], starts=[R - 1], ends=[R])
    n = R if gated else 1
    logits = (b.linear(states, cfg.d_model, cfg.vocab_size, "head")
              if table is None
              else layers.matmul(states, table, transpose_y=True))
    tiled = layers.expand(layers.unsqueeze(labels, axes=[0]),
                          expand_times=[n, 1, 1])              # [n, B, S]
    ce = layers.softmax_with_cross_entropy(logits=logits, label=tiled)
    # from here on fp32, whatever amp made of the logits
    ce = layers.cast(layers.squeeze(ce, axes=[3]), "float32")
    if not gated:
        return layers.mean(ce), logits, None
    w_g = layers.create_parameter(
        [cfg.d_model], "float32",
        attr=ParamAttr(name="gate_w", initializer=b.init))
    b_g = layers.create_parameter(
        [1], "float32", attr=ParamAttr(name="gate_b"), is_bias=True,
        default_initializer=ConstantInitializer(0.0))
    z = layers.reduce_sum(layers.elementwise_mul(
        layers.cast(states, "float32"), w_g), dim=-1)          # [R, B, S]
    p = _exit_distribution(layers.sigmoid(layers.elementwise_add(z, b_g)))
    expected = layers.reduce_sum(layers.elementwise_mul(p, ce), dim=0)
    # -H(p) = sum_t p_t log p_t; the clip keeps the log finite where a
    # gate saturates (p log p -> 0 there)
    neg_entropy = layers.reduce_sum(layers.elementwise_mul(
        p, layers.log(layers.clip(p, min=1e-30, max=1.0))), dim=0)
    per_token = layers.elementwise_add(
        expected, layers.scale(neg_entropy, scale=cfg.entropy_beta))
    return layers.mean(per_token), logits, p


def _several_heads_loss(b, h, labels):
    """(loss, logits [B, S, P, vocabulary]) of the final states h [B, S, D]
    under `cfg.pred_heads` = P heads on the one state (several byte- or
    token-prediction heads): ONE [D, P x vocabulary] product whose fp32
    accumulator is the logits, whatever the AMP tier makes of its operands
    (layers.matmul's `out_dtype`); `labels` [B, S, P], head i's target at a
    position, IGNORED_LABEL where it has none (past the sequence's end: no
    loss, no gradient); one softmax_with_cross_entropy over [B, S, P,
    vocabulary], the sum over the targets there are divided by their count,
    all heads weighing alike."""
    cfg = b.cfg
    P = cfg.pred_heads
    w = layers.create_parameter(
        [cfg.d_model, P * cfg.vocab_size], "float32",
        attr=ParamAttr(name="head_w", initializer=b.init))
    logits = layers.reshape(layers.matmul(h, w, out_dtype="float32"),
                            shape=[0, 0, P, cfg.vocab_size])
    ce = layers.softmax_with_cross_entropy(logits=logits, label=labels,
                                           ignore_index=IGNORED_LABEL)
    there = layers.cast(layers.not_equal(
        labels, layers.fill_constant([1], "int64", IGNORED_LABEL)), "float32")
    loss = layers.elementwise_div(layers.reduce_sum(ce),
                                  layers.reduce_sum(there))
    return loss, logits


def looped_decoder(cfg: Optional[LoopedDecoderConfig] = None, tokens=None,
                   labels=None) -> ModelSpec:
    cfg = cfg or LoopedDecoderConfig()
    S = cfg.max_length
    if tokens is None:
        tokens = layers.data("tokens", [S], dtype="int64")
    if labels is None:
        labels = layers.data("labels", [S], dtype="int64")
    b = _Builder(cfg)

    h0 = layers.embedding(tokens, size=[cfg.vocab_size, cfg.d_model],
                          param_attr=ParamAttr(name="embed",
                                               initializer=b.init))
    trip_scope = (recompute_scope if cfg.use_recompute
                  else contextlib.nullcontext)
    with name_scope("loop.body"), trip_scope():
        rec = layers.Recurrence(trips=cfg.loop_steps)
        with rec.block():
            h = rec.carry(h0)
            out = b.stack(h)
            rec.update(h, out)
            rec.output(out)
        states = rec()                                         # [R, B, S, D]
    with name_scope("loop.heads"):
        loss, logits, p = _heads_and_loss(b, states, labels)

    def synthetic_batch(batch_size: int, seed: int = 0) -> Dict[str, np.ndarray]:
        """Packed sequences: ids uniform over the vocabulary, the labels the
        ids shifted by one, no padding."""
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, cfg.vocab_size, size=(batch_size, S + 1))
        return {tokens.name: ids[:, :-1].astype(np.int64),
                labels.name: ids[:, 1:].astype(np.int64)}

    return ModelSpec(
        name="looped_decoder",
        feed_names=[tokens.name, labels.name],
        loss=loss,
        synthetic_batch=synthetic_batch,
        extras={"config": cfg, "states": states, "logits": logits,
                "exit_distribution": p},
    )

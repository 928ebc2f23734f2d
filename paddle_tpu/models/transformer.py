"""Transformer NMT — the framework's flagship model and north-star benchmark
(tokens/sec/chip).  Reference configs: benchmark/fluid dist_transformer /
machine-translation family; architecture is the standard base Transformer
(6+6 layers, d_model 512, 8 heads, ffn 2048, sinusoid positions, label
smoothing), built entirely from framework layers so the whole training step
lowers to one XLA computation.

TPU-first design points:
- static [batch, max_len] shapes; padding masks built in-graph from pad_idx
  (equal -> cast -> -1e9 bias), causal mask from a range/compare triangle —
  no ragged LoD on the hot path.
- Megatron-style tensor parallelism is expressed as sharding annotations on
  the weights (qkv/ffn-in column-split -> 'tp', out-proj/ffn-out row-split),
  applied when the caller trains under a mesh with a 'tp' axis; XLA inserts
  the all-reduces.
- sequence axis annotated 'sp' on the activations via feed sharding for
  context parallelism (ring collectives over ICI).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Optional

import numpy as np

from .. import layers
from ..core.framework import recompute_scope
from ..param_attr import ParamAttr
from ..initializer import NumpyArrayInitializer, XavierInitializer
from .common import ModelSpec

__all__ = ["TransformerConfig", "transformer"]


@dataclasses.dataclass
class TransformerConfig:
    src_vocab_size: int = 10000
    trg_vocab_size: int = 10000
    max_length: int = 256
    n_layer: int = 6
    n_head: int = 8
    d_model: int = 512
    d_inner: int = 2048
    dropout: float = 0.1
    label_smooth_eps: float = 0.1
    pad_idx: int = 0
    # parallelism: mesh axes the weights/activations are annotated for
    tp_axis: str = "tp"
    shard_weights: bool = True
    # fuse attention into one flash-kernel op (pallas on TPU); key padding
    # rides as lengths, no [Sq, Sk] bias tensor is materialized
    use_flash_attention: bool = False
    # project q/k/v with ONE [d, 3d] matmul (k/v fused to [d, 2d] for
    # cross-attention) instead of three [d, d] ones: fewer, larger MXU
    # calls and one pass over the activations.  Fused weights keep the
    # same column-parallel 'tp' annotation; numerically identical to the
    # unfused projections (test_transformer_fuse_qkv_parity stitches the
    # weights and compares logits).  Default OFF: fusing renames the
    # attention parameters (*_q_w/_k_w/_v_w -> *_qkv_w), which would break
    # loading checkpoints saved from the unfused layout.
    fuse_qkv: bool = False
    # rematerialize the ops of each encoder/decoder layer in backward
    # (fluid.recompute_scope; per-op jax.checkpoint boundaries).  Matters
    # for the fused_attention composite op — its internal [B, H, Sq, Sk]
    # probability matrix is recomputed instead of stored — so pair it
    # with use_flash_attention; a chain of primitive ops keeps its
    # op-boundary activations resident either way.
    use_recompute: bool = False
    # fold label smoothing into softmax_with_cross_entropy (smooth_eps):
    # identical numbers, no [B, S, V] label tensors.  False restores the
    # reference-shaped one_hot -> label_smooth -> soft-label chain
    fuse_smooth_ce: bool = True


def _sinusoid_table(max_len: int, d_model: int) -> np.ndarray:
    pos = np.arange(max_len, dtype=np.float64)[:, None]
    dim = np.arange(d_model // 2, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * dim / d_model)
    table = np.zeros((max_len, d_model), dtype=np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle)
    return table


class _Builder:
    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def linear(self, x, d_in, d_out, name, shard=None, act=None, bias=True,
               initializer=None):
        cfg = self.cfg
        w = layers.create_parameter(
            [d_in, d_out], "float32",
            attr=ParamAttr(name=f"{name}_w", initializer=initializer),
        )
        if cfg.shard_weights and shard is not None:
            w.sharding = shard
        out = layers.matmul(x, w)
        if bias:
            b = layers.create_parameter(
                [d_out], "float32", attr=ParamAttr(name=f"{name}_b"), is_bias=True,
            )
            out = layers.elementwise_add(out, b)
        if act == "relu":
            out = layers.relu(out)
        return out

    def mha(self, q_in, kv_in, bias, name, k_lengths=None, causal=False):
        """Multi-head attention.  q_in/kv_in: [B, S, D]; bias: additive
        attention bias broadcastable to [B, H, Sq, Sk].  With
        cfg.use_flash_attention and k_lengths given, the bias tensor is
        bypassed: one fused_attention op (pallas flash kernel) gets the
        causal flag + per-row key counts instead."""
        cfg = self.cfg
        d, h = cfg.d_model, cfg.n_head
        dh = d // h
        tp = cfg.tp_axis

        # fused projections keep the UNFUSED per-projection Xavier scale
        # (fan_in=d, fan_out=d): the default would read fan_out=3d/2d off
        # the fused shape and shrink init ~1.4x, changing from-scratch
        # training vs the separate projections.  The fused weight carries
        # NO tp annotation: a [None, tp] column split of the block-wise
        # q|k|v concat puts shard cuts mid-projection (tp=2 cuts k at
        # 1.5d), so the logical split(3) would cross shard boundaries and
        # force per-layer resharding — under tensor parallelism prefer
        # fuse_qkv=False, whose per-projection column splits stay local.
        proj_init = XavierInitializer(fan_in=d, fan_out=d)
        if cfg.fuse_qkv and q_in is kv_in:
            qkv = self.linear(q_in, d, 3 * d, f"{name}_qkv",
                              initializer=proj_init)
            q, k, v = layers.split(qkv, num_or_sections=3, dim=-1)
        elif cfg.fuse_qkv:
            q = self.linear(q_in, d, d, f"{name}_q", shard=[None, tp])
            kv = self.linear(kv_in, d, 2 * d, f"{name}_kv",
                             initializer=proj_init)
            k, v = layers.split(kv, num_or_sections=2, dim=-1)
        else:
            q = self.linear(q_in, d, d, f"{name}_q", shard=[None, tp])
            k = self.linear(kv_in, d, d, f"{name}_k", shard=[None, tp])
            v = self.linear(kv_in, d, d, f"{name}_v", shard=[None, tp])

        if cfg.use_flash_attention and k_lengths is not None:
            # heads-last: q, k, v go to the op as the projections wrote
            # them, [B, S, H * dh], and ctx comes back so for the output
            # projection; no transposition on either side (kernels/
            # flash_attention.py, PR 57)
            ctx = layers.fused_attention(
                q, k, v, causal=causal, k_lengths=k_lengths, n_head=h
            )
            if cfg.dropout:
                # the flash kernel does not expose attention weights, so
                # regularization moves to the attention output (the common
                # flash-attention approximation of weight dropout)
                ctx = layers.dropout(ctx, dropout_prob=cfg.dropout)
        else:
            def split_heads(x):
                x = layers.reshape(x, shape=[0, 0, h, dh])
                return layers.transpose(x, perm=[0, 2, 1, 3])  # [B, H, S, dh]

            q, k, v = split_heads(q), split_heads(k), split_heads(v)
            q = layers.scale(q, scale=dh ** -0.5)
            scores = layers.matmul(q, k, transpose_y=True)  # [B, H, Sq, Sk]
            scores = layers.elementwise_add(scores, bias)
            weights = layers.softmax(scores)
            if cfg.dropout:
                weights = layers.dropout(weights, dropout_prob=cfg.dropout)
            ctx = layers.matmul(weights, v)  # [B, H, Sq, dh]
            ctx = layers.transpose(ctx, perm=[0, 2, 1, 3])
            ctx = layers.reshape(ctx, shape=[0, 0, d])
        return self.linear(ctx, d, d, f"{name}_o", shard=[tp, None])

    def ffn(self, x, name):
        cfg = self.cfg
        tp = cfg.tp_axis
        hidden = self.linear(x, cfg.d_model, cfg.d_inner, f"{name}_in",
                             shard=[None, tp], act="relu")
        if cfg.dropout:
            hidden = layers.dropout(hidden, dropout_prob=cfg.dropout)
        return self.linear(hidden, cfg.d_inner, cfg.d_model, f"{name}_out",
                           shard=[tp, None])

    def sublayer(self, x, out, name):
        """post-norm residual connection: LayerNorm(x + dropout(out))."""
        cfg = self.cfg
        if cfg.dropout:
            out = layers.dropout(out, dropout_prob=cfg.dropout)
        return layers.layer_norm(
            layers.elementwise_add(x, out),
            begin_norm_axis=2,
            param_attr=ParamAttr(name=f"{name}_ln_scale"),
            bias_attr=ParamAttr(name=f"{name}_ln_bias"),
        )

    def embed(self, words, vocab_size, name):
        """token embedding * sqrt(d) + sinusoid positions, then dropout."""
        cfg = self.cfg
        emb = layers.embedding(
            words,
            size=[vocab_size, cfg.d_model],
            padding_idx=cfg.pad_idx,
            param_attr=ParamAttr(name=f"{name}_emb"),
        )
        emb = layers.scale(emb, scale=cfg.d_model ** 0.5)
        seq_len = words.shape[1]
        pos_table = layers.create_parameter(
            [seq_len, cfg.d_model], "float32",
            attr=ParamAttr(
                name=f"{name}_pos_enc", trainable=False,
                initializer=NumpyArrayInitializer(
                    _sinusoid_table(cfg.max_length, cfg.d_model)[:seq_len]
                ),
            ),
        )
        out = layers.elementwise_add(emb, pos_table, axis=1)
        if cfg.dropout:
            out = layers.dropout(out, dropout_prob=cfg.dropout)
        return out

    # -- masks (in-graph, static shapes) --------------------------------
    def pad_bias(self, words):
        """[B, 1, 1, S] additive bias: -1e9 at pad positions."""
        pad = layers.fill_constant_batch_size_like(
            words, shape=[-1, words.shape[1]], dtype="int64", value=self.cfg.pad_idx
        )
        is_pad = layers.cast(layers.equal(words, pad), "float32")
        bias = layers.scale(is_pad, scale=-1e9)
        return layers.unsqueeze(layers.unsqueeze(bias, axes=[1]), axes=[1])

    def seq_lengths(self, words):
        """[B] count of non-pad tokens (key-padding lengths for flash)."""
        pad = layers.fill_constant_batch_size_like(
            words, shape=[-1, words.shape[1]], dtype="int64",
            value=self.cfg.pad_idx,
        )
        not_pad = layers.cast(layers.not_equal(words, pad), "int32")
        return layers.reduce_sum(not_pad, dim=1)

    def causal_bias(self, seq_len):
        """[1, 1, S, S] additive bias: -1e9 above the diagonal."""
        r = layers.range(0, seq_len, 1, "float32")
        rows = layers.unsqueeze(r, axes=[1])  # [S, 1]
        cols = layers.unsqueeze(r, axes=[0])  # [1, S]
        future = layers.cast(layers.greater_than(cols, rows), "float32")
        bias = layers.scale(future, scale=-1e9)
        return layers.unsqueeze(bias, axes=[0, 1])


def transformer(
    cfg: Optional[TransformerConfig] = None,
    src_word=None,
    trg_word=None,
    lbl_word=None,
) -> ModelSpec:
    cfg = cfg or TransformerConfig()
    S = cfg.max_length
    if src_word is None:
        src_word = layers.data("src_word", [S], dtype="int64")
    if trg_word is None:
        trg_word = layers.data("trg_word", [S], dtype="int64")
    if lbl_word is None:
        lbl_word = layers.data("lbl_word", [S], dtype="int64")

    b = _Builder(cfg)

    flash = cfg.use_flash_attention
    src_bias = None if flash else b.pad_bias(src_word)    # enc self-attn
    trg_bias = None if flash else layers.elementwise_add(  # dec self-attn
        b.pad_bias(trg_word), b.causal_bias(S)
    )
    src_len = b.seq_lengths(src_word) if flash else None
    trg_len = b.seq_lengths(trg_word) if flash else None

    layer_scope = (recompute_scope if cfg.use_recompute
                   else contextlib.nullcontext)

    # encoder.  enc_boundaries = [embed out, layer1 out, ...] — the
    # stage cut points parallel.ProgramPipeline uses to pipeline the
    # encoder stack over a pp mesh axis (the embedding + bias ops form
    # the pipeline prefix)
    enc = b.embed(src_word, cfg.src_vocab_size, "src")
    enc_boundaries = [enc]
    for i in range(cfg.n_layer):
        with layer_scope():
            attn = b.mha(enc, enc, src_bias, f"enc_l{i}_attn",
                         k_lengths=src_len)
            enc = b.sublayer(enc, attn, f"enc_l{i}_attn")
            ff = b.ffn(enc, f"enc_l{i}_ffn")
            enc = b.sublayer(enc, ff, f"enc_l{i}_ffn")
            enc_boundaries.append(enc)

    # decoder.  dec_boundaries: ProgramPipeline cut points — the whole
    # encoder lands in the pipeline PREFIX and `enc` rides as a carried
    # side input to every decoder stage (cross-attention)
    dec = b.embed(trg_word, cfg.trg_vocab_size, "trg")
    dec_boundaries = [dec]
    for i in range(cfg.n_layer):
        with layer_scope():
            self_attn = b.mha(dec, dec, trg_bias, f"dec_l{i}_self",
                              k_lengths=trg_len, causal=True)
            dec = b.sublayer(dec, self_attn, f"dec_l{i}_self")
            cross = b.mha(dec, enc, src_bias, f"dec_l{i}_cross",
                          k_lengths=src_len)
            dec = b.sublayer(dec, cross, f"dec_l{i}_cross")
            ff = b.ffn(dec, f"dec_l{i}_ffn")
            dec = b.sublayer(dec, ff, f"dec_l{i}_ffn")
            dec_boundaries.append(dec)

    logits = b.linear(dec, cfg.d_model, cfg.trg_vocab_size, "project",
                      shard=[None, cfg.tp_axis], bias=False)

    # label-smoothed CE, masked to non-pad target positions.  The fused
    # path folds the smoothing into softmax_with_cross_entropy analytically
    # (smooth_eps attr, ops/loss_ops.py): no [B, S, V] one_hot/smooth
    # tensors are ever materialized — at V=32k, bs=32 that chain moved
    # ~1 GB/step of HBM.  fuse_smooth_ce=False keeps the reference-shaped
    # one_hot -> label_smooth -> soft-label CE ops (parity-tested equal).
    if cfg.fuse_smooth_ce:
        cost = layers.softmax_with_cross_entropy(
            logits=logits, label=lbl_word,
            smooth_eps=cfg.label_smooth_eps,
        )  # [B, S, 1]
    else:
        one_hot = layers.one_hot(lbl_word, depth=cfg.trg_vocab_size)
        if cfg.label_smooth_eps:
            smooth = layers.label_smooth(one_hot, epsilon=cfg.label_smooth_eps)
        else:
            smooth = one_hot
        cost = layers.softmax_with_cross_entropy(
            logits=logits, label=smooth, soft_label=True
        )  # [B, S, 1]
    cost = layers.squeeze(cost, axes=[2])
    pad = layers.fill_constant_batch_size_like(
        lbl_word, shape=[-1, S], dtype="int64", value=cfg.pad_idx
    )
    non_pad = layers.cast(layers.not_equal(lbl_word, pad), "float32")
    token_count = layers.reduce_sum(non_pad)
    sum_cost = layers.reduce_sum(layers.elementwise_mul(cost, non_pad))
    avg_cost = layers.elementwise_div(sum_cost, token_count)

    def synthetic_batch(batch_size: int, seed: int = 0) -> Dict[str, np.ndarray]:
        rng = np.random.RandomState(seed)
        # avoid pad_idx in real positions; ragged tails padded with pad_idx
        def seqs():
            w = rng.randint(1, cfg.src_vocab_size, size=(batch_size, S))
            lens = rng.randint(S // 2, S + 1, size=(batch_size,))
            for r, l in zip(w, lens):
                r[l:] = cfg.pad_idx
            return w.astype(np.int64)

        return {
            src_word.name: seqs(),
            trg_word.name: seqs(),
            lbl_word.name: seqs(),
        }

    return ModelSpec(
        name="transformer_base",
        feed_names=[src_word.name, trg_word.name, lbl_word.name],
        loss=avg_cost,
        metrics={"token_count": token_count, "sum_cost": sum_cost},
        synthetic_batch=synthetic_batch,
        extras={"logits": logits, "config": cfg,
                "enc_boundaries": enc_boundaries,
                "dec_boundaries": dec_boundaries},
    )

"""ModelSpec: what a model builder hands back to benches/tests, and what
two or more of the decoder builders share: the layer as a one-trip
recurrence (the unit of recomputation), the gated MLP whose first product
survives that recomputation, a chip's share of a softmax-routed
expert block with the scaled initialisation of the residual stream's
writers, and the packed batch."""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class ModelSpec:
    name: str
    feed_names: List[str]
    loss: Any  # Variable
    metrics: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # batch_size -> {feed_name: np.ndarray}; deterministic synthetic data
    synthetic_batch: Optional[Callable[[int], Dict[str, np.ndarray]]] = None
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


def class_batch(
    batch_size: int,
    img_shape,
    num_classes: int,
    img_name: str = "image",
    label_name: str = "label",
    seed: int = 0,
) -> Dict[str, np.ndarray]:
    rng = np.random.RandomState(seed)
    return {
        img_name: rng.rand(batch_size, *img_shape).astype(np.float32),
        label_name: rng.randint(0, num_classes, size=(batch_size, 1)).astype(np.int64),
    }


def one_trip_layer(h, body, use_recompute: bool = True,
                   prevent_cse: bool = False):
    """(h', the recurrence) of one layer built as a one-trip
    layers.Recurrence, under `use_recompute` inside a recompute scope, so
    that the layer is the unit of recomputation.  `h` is the value a layer
    hands the next, or a tuple of them (the residual stream and a router's
    state): each is a carry of the recurrence, so it survives the unit's
    recomputation and its gradient comes back through it.  `body(carried)`
    builds the layer and returns (its output, one value or a tuple as
    `h` is, the values the recurrence hands out besides: read them with
    the recurrence's call)."""
    from .. import layers
    from ..core.framework import recompute_scope

    many = isinstance(h, tuple)
    scope = recompute_scope if use_recompute else contextlib.nullcontext
    with scope():
        rec = layers.Recurrence(trips=1, prevent_cse=prevent_cse)
        with rec.block():
            carried = tuple(rec.carry(v) for v in (h if many else (h,)))
            out, handed_out = body(carried if many else carried[0])
            for mem, value in zip(carried, out if many else (out,)):
                rec.update(mem, value)
            for value in handed_out:
                rec.output(value)
        finals = tuple(rec.final(mem) for mem in carried)
    return (finals if many else finals[0]), rec


def kept_gated_mlp(builder, x, name):
    """W2(silu(g) * u), (g, u) = split(W1 x), no bias, under the name scope
    `mlp`; a builder's `mlp` where its layers are units that really run
    their recomputation (`prevent_cse`: sambay_decoder, ssd_hybrid_decoder).
    W1's output [B, S, 2 d_inner] survives it (layers.kept): the backward
    reads g and u themselves, and the product that makes them is the
    dearest thing a layer would run twice."""
    from .. import layers
    from ..core.framework import name_scope

    cfg = builder.cfg
    with name_scope("mlp"):
        gate, up = layers.split(layers.kept(
            builder.linear(x, cfg.d_model, 2 * cfg.d_inner, f"{name}_1")),
            2, dim=-1)
        return builder.linear(
            layers.elementwise_mul(layers.swish(gate), up),
            cfg.d_inner, cfg.d_model, f"{name}_2")


def packed_batch(vocab_size: int, length: int, batch_size: int, seed: int,
                 tokens: str, labels: str) -> Dict[str, np.ndarray]:
    """Packed sequences: ids uniform over the vocabulary held here, the
    labels the ids shifted by one, no padding."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab_size, size=(batch_size, length + 1))
    return {tokens: ids[:, :-1].astype(np.int64),
            labels: ids[:, 1:].astype(np.int64)}


class SoftmaxExpertShare:
    """For a builder with `cfg` and `param(shape, name, **attr)`: a chip's
    share of a softmax-routed expert block (Qwen-MoE's rule: the softmax
    over all `n_routed_experts`, the `top_k` chosen, their weights over
    their sum under `norm_topk_prob`; `experts_held` experts from
    `expert_offset` on held here, whose terms alone are added), and the
    matrices that write into the residual stream (attention's o, the
    experts' down), which start at init_std / sqrt(2 x
    `residual_init_layers`): the published depth, whatever depth is held
    (0: init_std like the rest).  Where `cfg.train_router` is there and
    False the router takes no gradient: its weight is not trainable and the
    gates are constants to the backward pass (a share can compute 8 of the
    64 terms of the router's gradient, and Adam would step the full rate
    along them, towards the held experts)."""

    def residual_param(self, shape, name):
        """A matrix that writes into the residual stream."""
        from ..initializer import NormalInitializer

        cfg = self.cfg
        scale = (2.0 * cfg.residual_init_layers) ** -0.5 \
            if cfg.residual_init_layers else 1.0
        return self.param(shape, name, initializer=NormalInitializer(
            0.0, cfg.init_std * scale))

    def expert_block(self, x, name):
        from .. import layers

        cfg = self.cfg
        trained = getattr(cfg, "train_router", True)
        idx, weight, _ = layers.moe_router(
            x, self.param([cfg.d_model, cfg.n_routed_experts],
                          f"{name}_router_w", trainable=trained),
            None, top_k=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
            scoring="softmax")
        if not trained:
            weight = layers.detach(weight)
        return self.held_experts(x, idx, weight, name)

    def held_experts(self, x, idx, weight, name):
        """The held experts' terms of x under a router's choice `idx` and
        gates `weight`."""
        from .. import layers

        cfg = self.cfg
        held, d, f = cfg.experts_held, cfg.d_model, cfg.d_expert
        return layers.moe_experts(
            x, idx, weight,
            self.param([held, d, f], f"{name}_experts_gate_w"),
            self.param([held, d, f], f"{name}_experts_up_w"),
            self.residual_param([held, f, d], f"{name}_experts_down_w"),
            experts_total=cfg.n_routed_experts,
            expert_offset=cfg.expert_offset, scoring="softmax")

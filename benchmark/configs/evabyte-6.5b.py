"""evabyte-6.5b: the build function, the synthetic batch and the FLOP, pair
and byte counts of benchmark/configs/evabyte-6.5b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

IGNORED = -100      # a label where a head has no target


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    assert cfg["attention_class"] == "eva" and cfg["hidden_act"] == "silu"
    assert not cfg["attention_bias"] and not cfg["tie_word_embeddings"]
    assert cfg["rope_scaling"] is None and cfg["norm_add_unit_offset"]
    assert cfg["fp32_skip_add"] and not cfg["fp32_ln"]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    spec = models.eva_decoder(models.EvaDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"],
        n_head=cfg["num_attention_heads"], heads_held=cfg["heads_held"],
        head_offset=cfg["head_offset"], head_dim=cfg["head_dim"],
        window_size=cfg["window_size"], chunk_size=cfg["chunk_size"],
        pred_heads=cfg["num_pred_heads"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], init_std=cfg["init_std"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed rows of max_length bytes: ids uniform over the 320,
    labels [S, P] the ids shifted by 1 .. P (head i at position t is held to
    byte t + 1 + i), IGNORED where that lies past the row's end."""
    rng = np.random.RandomState(fold_seed(seed))
    S, P = cfg["max_length"], cfg["num_pred_heads"]
    ids = rng.randint(0, cfg["vocab_size"], size=(batch, S)).astype(np.int64)
    labels = np.full((batch, S, P), IGNORED, np.int64)
    for i in range(P):
        labels[:, :max(S - 1 - i, 0), i] = ids[:, 1 + i:]
    tokens, names = spec.feed_names
    return {tokens: ids, names: labels}


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters a token passes in one layer: W_q, W_k, W_v and W_o
    at the heads held, the gated MLP whole."""
    d = cfg["hidden_size"]
    return (4 * d * cfg["heads_held"] * cfg["head_dim"]
            + 3 * d * cfg["intermediate_size"])


def head_matmul_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_pred_heads"] * cfg["vocab_size"]


def eva_pairs(cfg: dict) -> tuple:
    """(query-key pairs inside the windows, query-summary pairs) of one
    head and one row of max_length bytes: query t sees the t % w + 1 keys
    of its own window up to itself and the (w / c) (t // w) summaries of
    the windows before it."""
    S, w, c = cfg["max_length"], cfg["window_size"], cfg["chunk_size"]
    w = min(w, S)
    t = np.arange(S, dtype=np.int64)
    return int((t % w + 1).sum()), int((t // w * (w // c)).sum())


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of the attention's core for one visible pair over the
    heads held: q.k and p.v, 2 FLOPs a multiply-add."""
    return 2.0 * 2 * cfg["heads_held"] * cfg["head_dim"]


def flops_per_sample(cfg: dict) -> float:
    """One row of max_length bytes.  Per byte 6 x the matmul parameters it
    passes (2 forward, 4 backward): the four projections at the heads held
    and the gated MLP in every layer, the 8 x 320-wide head once.  EVA over
    the pairs its two masks let through only (`eva_pairs`), x 3 for
    training.  The pooling (multiplies and sums on the VPU over K and V,
    under 0.1% of the step), the embedding's gather, work on pairs a block
    computes and masks away, and recomputed work are not counted."""
    S = cfg["max_length"]
    matmul = cfg["num_hidden_layers"] * layer_matmul_params(cfg) \
        + head_matmul_params(cfg)
    attend = 3 * attend_flops_per_pair(cfg) * sum(eva_pairs(cfg)) \
        * cfg["num_hidden_layers"]
    return S * 6.0 * matmul + attend


def eva_attend_flops_per_step(cfg: dict, sequences: int = 1) -> float:
    """FLOPs of EVA's core a training step over the visible pairs of both
    key sets, every pass that runs counted once: the forward's 2 block
    products (q.k, p.v) and the backward kernel's 5 (the scores again, dP,
    dV, dK, dQ), 7 products of 2 FLOPs a multiply-add (PR 42's rule: the
    layer's recomputation runs no second forward, the flash sites keep out
    and lse).  What eva_attend_roofline.train divides by the device time
    under `eva.attend` and the MXU's peak; the kernels compute whole blocks
    and mask what an edge cuts, and the summaries' part computes every
    window against all pooled chunks, so the share cannot pass 100%."""
    return (7 / 2.0) * attend_flops_per_pair(cfg) * sum(eva_pairs(cfg)) \
        * cfg["num_hidden_layers"] * sequences


def eva_pool_bytes_per_step(cfg: dict, sequences: int = 1,
                            element_bytes: int = 2) -> float:
    """Bytes the pooling has to move through HBM a training step, in the
    keep tier's bf16: K and V of the pooled positions (every window's but
    the last) read and 1 / chunk of them written, forward; the same read
    again with the summaries' cotangents and dK, dV written, backward:
    (1 + 1/c) + (2 + 1/c) passes over 2 x heads x pooled x head_dim
    elements.  No recomputed pass is counted (PR 42's rule, and none runs:
    the trace shows the forward once).  No reader reads it yet: it is
    `eva_pool_roofline.train`'s numerator for the day the pooling is a
    kernel."""
    S, w, c = cfg["max_length"], cfg["window_size"], cfg["chunk_size"]
    pooled = (-(-S // min(w, S)) - 1) * min(w, S)
    one = 2.0 * cfg["heads_held"] * pooled * cfg["head_dim"] * element_bytes
    return (3 + 2.0 / c) * one * cfg["num_hidden_layers"] * sequences

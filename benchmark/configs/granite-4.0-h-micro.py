"""granite-4.0-h-micro: the build function, the synthetic batch and the FLOP,
pair and byte counts of benchmark/configs/granite-4.0-h-micro.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

MAMBA, ATTENTION = "mamba", "attention"


def layer_kinds(cfg: dict) -> tuple:
    """A layer's kind, one entry a layer: the first `num_hidden_layers` of
    the published `layer_types`."""
    kinds = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    assert len(kinds) == cfg["num_hidden_layers"], kinds
    return kinds


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    assert cfg["model_type"] == "granitemoehybrid"
    assert cfg["hidden_act"] == "silu" and cfg["tie_word_embeddings"]
    assert cfg["position_embedding_type"] == "nope"
    assert cfg["normalization_function"] == "rmsnorm"
    assert cfg["num_local_experts"] == cfg["num_experts_per_tok"] == 0
    assert not cfg["attention_bias"] and not cfg["mamba_proj_bias"]
    assert cfg["mamba_conv_bias"]
    spec = models.ssd_hybrid_decoder(models.SsdHybridDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        d_model=cfg["hidden_size"], d_inner=cfg["shared_intermediate_size"],
        layer_types=layer_kinds(cfg), ssm_heads=cfg["mamba_heads_held"],
        ssm_head_dim=cfg["mamba_d_head"], d_state=cfg["mamba_d_state"],
        n_groups=cfg["mamba_n_groups"], d_conv=cfg["mamba_d_conv"],
        n_head=cfg["attention_heads_held"],
        n_kv_head=cfg["key_value_heads_held"], head_dim=head_dim(cfg),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"], init_std=cfg["init_std"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed rows of max_length tokens: ids uniform over the rows
    of the table held here, labels the ids shifted by one, no padding."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1))
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1].astype(np.int64),
            labels: ids[:, 1:].astype(np.int64)}


def head_dim(cfg: dict) -> int:
    """An attention head's size: the hidden size over the PUBLISHED number
    of query heads."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def inner_width(cfg: dict) -> int:
    """The channels of the state-space heads held here."""
    return cfg["mamba_heads_held"] * cfg["mamba_d_head"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    """Matmul parameters a token passes in one layer's mixer."""
    d = cfg["hidden_size"]
    if kind == MAMBA:
        E = inner_width(cfg)
        shared = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
        return d * (2 * E + shared + cfg["mamba_heads_held"]) + E * d
    return d * head_dim(cfg) * (2 * cfg["attention_heads_held"]
                                + 2 * cfg["key_value_heads_held"])


def visible_pairs(cfg: dict) -> int:
    """Query-key pairs the causal mask lets through in a row of max_length
    tokens."""
    S = cfg["max_length"]
    return S * (S + 1) // 2


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of one visible pair over the query heads held here:
    q.k and p.v at head D, 2 FLOPs a multiply-add."""
    return 4.0 * head_dim(cfg) * cfg["attention_heads_held"]


def _scan_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count(MAMBA)


def scan_flops_per_step(cfg: dict, sequences: int = 1) -> float:
    """Matmul operations of the state-space-dual scans (the op ssd_scan,
    scope `ssd.scan`) a training step, forward and backward (twice the
    forward), in the chunked form at the PUBLISHED chunk (`mamba_chunk_size`
    256) and the pairs i >= j only: a token's scores C B^T take 2 N (Q + 1)
    / 2 a group; a head's masked product 2 P (Q + 1) / 2, its read of the
    state 2 N P and its write 2 N P.  No recomputed pass is counted, and
    the count is the same whatever engine runs the scan and whatever chunk
    it walks.  The decay masks (an exponential and two products a pair and
    a head) are vector-unit work and are not counted."""
    Q, N, P = (cfg["mamba_chunk_size"], cfg["mamba_d_state"],
               cfg["mamba_d_head"])
    pairs = (Q + 1) / 2.0
    token = cfg["mamba_n_groups"] * 2 * N * pairs \
        + cfg["mamba_heads_held"] * (2 * P * pairs + 4 * N * P)
    return 3.0 * _scan_layers(cfg) * sequences * cfg["max_length"] * token


def scan_bytes_per_step(cfg: dict, sequences: int = 1,
                        element_bytes: int = 2) -> float:
    """Bytes the same two passes have to move through HBM whatever the
    engine, at the element size of the op's boundary under the keep tier
    (bf16): the forward reads x [S, H P], dt [S, H] and B, C [S, G N] and
    writes y; the backward reads those and dy and writes dx, ddt, dB, dC.
    The chunk starts, A, D, their gradients and the running sums are an
    engine's choice or small and are not counted."""
    rows = sequences * cfg["max_length"] * element_bytes
    wide = rows * inner_width(cfg)
    shared = rows * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    narrow = rows * cfg["mamba_heads_held"]
    return float(_scan_layers(cfg) * (
        (2 * wide + 2 * shared + narrow)
        + (4 * wide + 4 * shared + 2 * narrow)))


def flops_per_sample(cfg: dict) -> float:
    """One row of max_length tokens.  Per token 6 x the matmul parameters
    it passes (2 forward, 4 backward): every layer's mixer by its kind and
    its gated MLP, the tied head once (the embedding is a gather).
    Attention over the pairs the causal mask lets through only, x 3 for
    training.  The scans' matmuls (scan_flops_per_step: 1.1% of the step)
    are counted with them.  The convolution, the norms, the gates, the
    decay masks, work on pairs a block computes and masks away, and
    recomputed work are not counted."""
    S, d = cfg["max_length"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    matmul = sum(mixer_matmul_params(cfg, k) for k in kinds) \
        + len(kinds) * 3 * d * cfg["shared_intermediate_size"] \
        + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * visible_pairs(cfg) \
        * kinds.count(ATTENTION)
    return S * 6.0 * matmul + attend + scan_flops_per_step(cfg)

"""qwen3-next-80b-a3b: the build function, the synthetic batch and the FLOP,
pair and byte counts of benchmark/configs/qwen3-next-80b-a3b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

GDN, ATTENTION = "gdn", "attention"
# tokens a chunk of the scan's counts: the algorithm's, whatever an engine
# walks
SCAN_CHUNK = 64


def layer_kinds(cfg: dict) -> tuple:
    """A layer's kind: gated attention where (i + 1) is a multiple of
    `full_attention_interval`, Gated DeltaNet elsewhere."""
    return tuple(
        ATTENTION if (i + 1) % cfg["full_attention_interval"] == 0 else GDN
        for i in range(cfg["num_hidden_layers"]))


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    assert cfg["model_type"] == "qwen3_next"
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert cfg["decoder_sparse_step"] == 1 and not cfg["mlp_only_layers"]
    assert cfg["rope_scaling"] is None and not cfg["use_sliding_window"]
    assert cfg["linear_key_head_dim"] == cfg["linear_value_head_dim"]
    spec = models.gated_delta_decoder(models.GatedDeltaDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        d_model=cfg["hidden_size"],
        linear_key_heads=cfg["linear_num_key_heads"],
        linear_value_heads=cfg["linear_num_value_heads"],
        linear_head_dim=cfg["linear_key_head_dim"],
        conv_kernel=cfg["linear_conv_kernel_dim"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rotary_dim=rotary_dim(cfg), rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        d_shared_expert=cfg["shared_expert_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        train_router=cfg["train_router"], init_std=cfg["init_std"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed rows of max_length tokens: ids uniform over the rows
    of the tables held here, labels the ids shifted by one, no padding."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1))
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1].astype(np.int64),
            labels: ids[:, 1:].astype(np.int64)}


def rotary_dim(cfg: dict) -> int:
    """The features of an attention head the rotary turns."""
    return int(cfg["head_dim"] * cfg["partial_rotary_factor"])


def expected_rows_per_token(cfg: dict) -> float:
    """Rows an even router sends the held experts for one token."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    """Matmul parameters a token passes in one layer's mixer."""
    d = cfg["hidden_size"]
    if kind == GDN:
        keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
        values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
        return d * (2 * keys + 2 * values) \
            + d * 2 * cfg["linear_num_value_heads"] + values * d
    D = cfg["head_dim"]
    return d * D * (3 * cfg["num_attention_heads"]
                    + 2 * cfg["num_key_value_heads"])


def expert_layer_matmul_params(cfg: dict) -> float:
    """Matmul parameters a token passes in one expert block, a parameter
    that takes no gradient (the router's under `train_router` false) at
    2 / 6 of one that does: the router, the shared expert with its gate's
    vector, the routed experts AT THE EXPECTED rows."""
    d = cfg["hidden_size"]
    router = d * cfg["router_experts"] * (1.0 if cfg["train_router"]
                                          else 2.0 / 6.0)
    shared = 3 * d * cfg["shared_expert_intermediate_size"] + d
    return router + shared + expected_rows_per_token(cfg) \
        * 3 * d * cfg["moe_intermediate_size"]


def visible_pairs(cfg: dict) -> int:
    """Query-key pairs the causal mask lets through in a row of max_length
    tokens."""
    S = cfg["max_length"]
    return S * (S + 1) // 2


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of one visible pair over the query heads: q.k and p.v
    at head D, 2 FLOPs a multiply-add."""
    return 4.0 * cfg["head_dim"] * cfg["num_attention_heads"]


def _scan_layers(cfg: dict) -> int:
    return layer_kinds(cfg).count(GDN)


def scan_flops_per_step(cfg: dict, sequences: int = 1) -> float:
    """Matmul operations of the Gated DeltaNet layers' chunk scans (the op
    gated_delta_attention in its head-decay form, scope `gdn.scan`) a
    training step: the algorithm's forward and its backward at twice the
    forward, at chunks of SCAN_CHUNK tokens, AS THE MODEL STATES THE LAYER:
    a chunk's k k^T and q k^T at their triangles once a KEY head (2 C^2 D:
    its value heads share them before their decay masks); a VALUE head's
    triangular inverse (C^3 / 3: beta and the decay are a value head's, so
    (I + A)^-1 is too), T applied to [K | V] (2 C^2 D), the state read
    twice and written once (6 C D^2) and P U' (C^2 D).  The decay masks
    (one exponential and two products a pair) are vector-unit work and are
    not counted.  No recomputed pass, and the same whatever engine runs
    the scan and whatever form the op was given."""
    c, d = SCAN_CHUNK, cfg["linear_key_head_dim"]
    a_key_head = 2 * c * c * d
    a_value_head = 3 * c * c * d + c ** 3 // 3 + 6 * c * d * d
    chunks = sequences * cfg["max_length"] // c
    return 3.0 * _scan_layers(cfg) * chunks * (
        cfg["linear_num_key_heads"] * a_key_head
        + cfg["linear_num_value_heads"] * a_value_head)


def scan_bytes_per_step(cfg: dict, sequences: int = 1,
                        element_bytes: int = 2) -> float:
    """Bytes the same two passes have to move through HBM whatever the
    engine, as the model states the layer: the forward reads q and k at
    the KEY heads and v at the value heads (bf16), g and beta [S, Hv]
    (fp32) and writes out; the backward reads those and out's cotangent
    and writes dq, dk (key heads), dv, dg and dbeta.  No decay a channel,
    no q or k repeated to the value heads, no state traffic: those are an
    engine's choice."""
    rows = sequences * cfg["max_length"]
    keys = rows * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] \
        * element_bytes
    values = rows * cfg["linear_num_value_heads"] \
        * cfg["linear_value_head_dim"] * element_bytes
    scalars = rows * cfg["linear_num_value_heads"] * 4
    fwd = 2 * keys + values + 2 * scalars + values
    bwd = fwd + 2 * keys + values + 2 * scalars
    return float(_scan_layers(cfg) * (fwd + bwd))


def flops_per_sample(cfg: dict) -> float:
    """One row of max_length tokens.  Per token 6 x the matmul parameters
    it passes (2 forward, 4 backward): every layer's mixer by its kind, its
    expert block (expert_layer_matmul_params: the held experts at the
    EXPECTED 0.625 rows a token) and the sliced untied head once (the
    embedding is a gather).  Attention over the pairs the causal mask lets
    through only, x 3 for training.  The scans' matmuls
    (scan_flops_per_step) are counted with them.  The convolution, the
    norms, the gates, the decay masks, work on pairs a block computes and
    masks away, and recomputed work are not counted."""
    S, d = cfg["max_length"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    matmul = sum(mixer_matmul_params(cfg, k) for k in kinds) \
        + len(kinds) * expert_layer_matmul_params(cfg) \
        + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * visible_pairs(cfg) \
        * kinds.count(ATTENTION)
    return S * 6.0 * matmul + attend + scan_flops_per_step(cfg)

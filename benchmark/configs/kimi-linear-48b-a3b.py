"""kimi-linear-48b-a3b: the build function, the synthetic batch and the FLOP
and byte counts of benchmark/configs/kimi-linear-48b-a3b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

# the chunk the algorithm is counted at, whatever chunk or engine runs it
SCAN_CHUNK = 64


def layer_kinds(cfg: dict) -> list:
    """"kda" or "mla" for each of the depth's layers: the two lists number
    the layers from 1 and are kept whole; the entries up to the depth are
    read."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for layer in range(1, cfg["num_hidden_layers"] + 1):
        kda = layer in lin["kda_layers"]
        assert kda != (layer in lin["full_attn_layers"]), layer
        kinds.append("kda" if kda else "mla")
    return kinds


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    lin = cfg["linear_attn_config"]
    layer_kinds(cfg)
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert cfg["q_lora_rank"] is None and cfg["mla_use_nope"]
    assert cfg["rope_scaling"] is None
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["moe_router_activation_func"] == "sigmoid"
    assert cfg["num_expert_group"] == cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1 and \
        cfg["num_nextn_predict_layers"] == 0
    spec = models.hybrid_linear_decoder(models.HybridLinearDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        n_head=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        mla_rope="none", rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["num_experts"],
        expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_token"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["moe_renormalize"],
        bias_update_gamma=cfg["bias_update_gamma"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed sequences of max_length tokens: ids uniform over the
    vocabulary slice held here, the labels the ids shifted by one."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1)).astype(np.int64)
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:]}


def expected_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here when the router's load
    is even: top_k x held / all (0.25 at 8 x 8 / 256)."""
    return cfg["num_experts_per_token"] * cfg["num_experts"] \
        / cfg["router_experts"]


def kda_matmul_params(cfg: dict) -> int:
    """Matmul parameters a token passes in one KDA layer: q, k, v, o, the
    two maps of rank D (decay and gate) and beta."""
    lin = cfg["linear_attn_config"]
    d, H, D = cfg["hidden_size"], lin["num_heads"], lin["head_dim"]
    return 4 * d * H * D + 2 * (d * D + D * H * D) + d * H


def mla_matmul_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * H * (dn + dr) + d * (cfg["kv_lora_rank"] + dr)
            + cfg["kv_lora_rank"] * H * (dn + dv) + H * dv * d)


def scan_flops_per_chunk(cfg: dict) -> int:
    """FLOPs of one chunk of SCAN_CHUNK tokens of one head, forward: the
    two decayed products at their triangles (2 C^2 D), the triangular
    inverse (C^3 / 3), T applied to [K | V] (2 C^2 D), the state read
    twice and written once (6 C D^2) and P U' (C^2 D)."""
    c, d = SCAN_CHUNK, cfg["linear_attn_config"]["head_dim"]
    return 5 * c * c * d + c ** 3 // 3 + 6 * c * d * d


def scan_flops_per_step(cfg: dict, samples: int) -> float:
    """FLOPs of the KDA layers' chunk scans (the op gated_delta_attention,
    scope `kda.scan`) a training step: the algorithm's forward and its
    backward at twice the forward, at chunks of SCAN_CHUNK tokens.  A
    recomputed pass is never counted and the count is the same whatever
    engine runs the scan: a PR that changes either moves
    kda_scan_roofline.train through the time alone."""
    heads = cfg["linear_attn_config"]["num_heads"]
    chunks = samples * cfg["max_length"] // SCAN_CHUNK
    return 3.0 * layer_kinds(cfg).count("kda") * heads * chunks \
        * scan_flops_per_chunk(cfg)


def scan_bytes_per_step(cfg: dict, samples: int) -> float:
    """Bytes the same two passes have to move through HBM, whatever the
    engine: the forward reads q, k, v (bf16), the log-decay (fp32) and beta
    (fp32) and writes out (bf16); the backward reads all of those and
    out's cotangent and writes the five gradients.  The chunk states are
    an engine's choice and are not counted."""
    lin = cfg["linear_attn_config"]
    rows = samples * cfg["max_length"] * lin["num_heads"]
    wide = rows * lin["head_dim"]
    fwd = 3 * 2 * wide + 4 * wide + 4 * rows + 2 * wide
    bwd = fwd + 3 * 2 * wide + 4 * wide + 4 * rows
    return float(layer_kinds(cfg).count("kda") * (fwd + bwd))


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): the mixer of every
    layer by its kind, the dense MLP in the leading layers, in every
    expert layer the router, the shared expert and the routed experts AT
    THE EXPECTED expected_rows_per_token (0.25: the rows an even router
    sends to the 8 held of 256), and the head; plus latent attention's
    score and value matmuls by benchmark/harness/flops.py's convention
    (2*S*H*(qk + v) forward a position and layer, x 3 for training, the
    causal half not taken off) and the KDA layers' chunk scans
    (scan_flops_per_step).  Recomputed work is no work of the algorithm."""
    S, L = cfg["max_length"], cfg["num_hidden_layers"]
    kinds = layer_kinds(cfg)
    dense = cfg["first_k_dense_replace"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    expert_layer = (d * cfg["router_experts"]
                    + 3 * d * f * cfg["num_shared_experts"]
                    + expected_rows_per_token(cfg) * 3 * d * f)
    matmul = (kinds.count("kda") * kda_matmul_params(cfg)
              + kinds.count("mla") * mla_matmul_params(cfg)
              + dense * 3 * d * cfg["intermediate_size"]
              + (L - dense) * expert_layer + d * cfg["vocab_size"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 3 * 2 * S * cfg["num_attention_heads"] \
        * (qk + cfg["v_head_dim"]) * kinds.count("mla")
    return S * (6.0 * matmul + attn) + scan_flops_per_step(cfg, 1)

"""moonlight-16b-a3b: the build function, the synthetic batch and the FLOP
counts of benchmark/configs/moonlight-16b-a3b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"], cfg
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert cfg["q_lora_rank"] is None and not cfg["attention_bias"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["topk_method"] == \
        "noaux_tc" and cfg["n_group"] == cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1 and \
        cfg["num_nextn_predict_layers"] == 0
    spec = models.expert_decoder(models.ExpertDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        n_head=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"], top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
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
    is even: top_k x held / all (0.75 at 6 x 8 / 64)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def expert_matmul_params(cfg: dict) -> int:
    """Matmul parameters one routed row passes: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mla_matmul_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * H * (dn + dr) + d * (cfg["kv_lora_rank"] + dr)
            + cfg["kv_lora_rank"] * H * (dn + dv) + H * dv * d)


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): MLA in every layer,
    the dense MLP in the leading layers, in every expert layer the router,
    the shared experts and the routed experts AT THE EXPECTED
    expected_rows_per_token (0.75: the rows an even router sends to the 8
    held of 64), and the head; plus the attention score and value matmuls
    by benchmark/harness/flops.py's convention (2*S*H*(qk + v) forward a
    position and layer, x 3 for training, the causal half not taken off).
    Recomputed work is no work of the algorithm."""
    S, L = cfg["max_length"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    f = cfg["moe_intermediate_size"]
    expert_layer = (d * cfg["router_experts"]
                    + 3 * d * f * cfg["n_shared_experts"]
                    + expected_rows_per_token(cfg) * expert_matmul_params(cfg))
    matmul = (L * mla_matmul_params(cfg)
              + dense * 3 * d * cfg["intermediate_size"]
              + (L - dense) * expert_layer + d * cfg["vocab_size"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 3 * 2 * S * H * (qk + cfg["v_head_dim"]) * L
    return S * (6.0 * matmul + attn)


def grouped_matmul_flops_per_step(cfg: dict, tokens: int) -> float:
    """FLOPs of the expert layers' grouped matmuls a training step at the
    expected rows: the algorithm's passes, forward 1 and backward 2 (input
    and weight gradient), 2 FLOPs a multiply-add: 3 x 2 x rows x parameters
    a row, over the expert layers.  Recomputed work is never counted,
    whatever `use_recompute` says and whatever the program recomputes: a PR
    that stops or starts recomputing a forward moves the share through the
    time alone, and a count tied to what the program recomputes goes stale
    with every such PR.  What
    moe_experts_roofline.train divides by the device time under the scope
    `moe.experts`, which holds every pass that runs."""
    passes = 3
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows = tokens * expected_rows_per_token(cfg)
    return passes * 2.0 * rows * expert_matmul_params(cfg) * layers

"""xing4.0-29b-a4b: the build function, the synthetic batch and the FLOP and
byte counts of benchmark/configs/xing4.0-29b-a4b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

# a stream's element on the chip: the AMP keep tier holds activations in bf16
STREAM_BYTES = 2
PHI_BYTES = 4


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert not cfg["attention_bias"]
    assert cfg["rope_scaling"]["type"] == "yarn"
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["scoring_func"] == "sigmoid" and \
        cfg["topk_method"] == "noaux_tc"
    assert cfg["n_group"] == cfg["topk_group"] == 1
    assert cfg["moe_layer_freq"] == 1
    # num_nextn_predict_layers stays as published; the block is not built
    # (the file's `assumed`.mtp_left_out)
    spec = models.hyper_expert_decoder(models.HyperExpertDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"],
        first_k_dense=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        n_head=cfg["heads_held"],
        q_lora_rank=cfg["q_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=cfg["rope_theta"], rope_scaling=cfg["rope_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        bias_update_gamma=cfg["bias_update_gamma"],
        hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp_min=cfg["mhc_h_res_clamp_min"],
        hc_clamp_max=cfg["mhc_h_res_clamp_max"],
        hc_alpha_init=cfg["hc_alpha_init"],
        hc_res_diag_init=cfg["hc_res_diag_init"],
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
    is even: top_k x held / all (0.5 at 4 x 8 / 64)."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]


def mla_matmul_params(cfg: dict) -> int:
    """Matmul parameters a token passes in one layer's share of latent
    attention: the two down-maps whole, W_qb, W_kvb and the output map at
    the held heads."""
    d, H = cfg["hidden_size"], cfg["heads_held"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (dn + dr)
            + d * (cfg["kv_lora_rank"] + dr)
            + cfg["kv_lora_rank"] * H * (dn + dv) + H * dv * d)


def mhc_sublayers(cfg: dict) -> int:
    return 2 * cfg["num_hidden_layers"]


def mhc_matmul_params(cfg: dict) -> int:
    """Phi of one sublayer: [n C, n + n + n^2]."""
    n = cfg["hc_mult"]
    return n * cfg["hidden_size"] * (2 * n + n * n)


def mhc_flops_per_step(cfg: dict, samples: int) -> float:
    """FLOPs of the hyper-connections' products with Phi a training step
    (forward 2, backward 4 a parameter and token).  The mixing itself is
    multiply-adds on the vector unit and is counted by its bytes."""
    return 6.0 * samples * cfg["max_length"] * mhc_sublayers(cfg) \
        * mhc_matmul_params(cfg)


def mhc_bytes_per_step(cfg: dict, samples: int) -> float:
    """Bytes the hyper-connections have to move through HBM a training
    step, whatever implements the ops and whatever is recomputed: a
    sublayer, 5 passes over the streams (forward: read X, write X';
    backward: read X, read dX', write dX) and 4 over a [T, C] value (x_in,
    y and their cotangents) at the stream's element size, and Phi once."""
    n, tokens = cfg["hc_mult"], samples * cfg["max_length"]
    return float(mhc_sublayers(cfg) * (
        (5 * n + 4) * tokens * cfg["hidden_size"] * STREAM_BYTES
        + mhc_matmul_params(cfg) * PHI_BYTES))


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): every layer's share
    of latent attention and its two hyper-connections' Phi, the dense MLP
    in the leading layers, in every expert layer the router, the shared
    expert and the routed experts AT THE EXPECTED expected_rows_per_token
    (0.5: the rows an even router sends to the 8 held of 64), and the
    head; plus latent attention's score and value matmuls at the held
    heads by benchmark/harness/flops.py's convention (2*S*H*(qk + v)
    forward a position and layer, x 3 for training, the causal half not
    taken off).  Recomputed work is no work of the algorithm."""
    S, L = cfg["max_length"], cfg["num_hidden_layers"]
    dense = cfg["first_k_dense_replace"]
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    expert_layer = (d * cfg["router_experts"]
                    + 3 * d * f * cfg["n_shared_experts"]
                    + expected_rows_per_token(cfg) * 3 * d * f)
    matmul = (L * mla_matmul_params(cfg)
              + mhc_sublayers(cfg) * mhc_matmul_params(cfg)
              + dense * 3 * d * cfg["intermediate_size"]
              + (L - dense) * expert_layer + d * cfg["vocab_size"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 3 * 2 * S * cfg["heads_held"] * (qk + cfg["v_head_dim"]) * L
    return S * (6.0 * matmul + attn)

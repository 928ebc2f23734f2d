"""zaya1-8b: the build function, the synthetic batch and the FLOP counts of
benchmark/configs/zaya1-8b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed


def rotary_dim(cfg: dict) -> int:
    """The features of a head that turn: partial_rotary_factor x head_dim."""
    return int(cfg["rope_parameters"]["hybrid"]["partial_rotary_factor"]
               * cfg["head_dim"])


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    depth = cfg["num_hidden_layers"]
    rope = cfg["rope_parameters"]["hybrid"]
    assert cfg["hidden_act"] == "silu" and cfg["tie_word_embeddings"]
    assert not cfg["attention_bias"] and not cfg["lm_head_bias"]
    assert set(cfg["layer_types"][:depth]) == {"hybrid"}
    assert rope["rope_type"] == "default" and cfg["sliding_window"] is None
    assert rope["partial_rotary_factor"] == cfg["partial_rotary_factor"]
    spec = models.compressed_decoder(models.CompressedDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=depth, d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        conv_time0=cfg["cca_time0"], conv_time1=cfg["cca_time1"],
        rotary_dim=rotary_dim(cfg), rope_theta=rope["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        router_dim=cfg["router_hidden_size"], norm_topk_prob=False,
        train_router=cfg["train_router"],
        residual_init_layers=cfg["published"]["num_hidden_layers"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed sequences of max_length tokens: ids uniform over the
    table's rows held here, the labels the ids shifted by one."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1)).astype(np.int64)
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:]}


def expected_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here when the router's load
    is even: top_k x held / all (0.5 at 1 x 8 / 16)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def expert_matmul_params(cfg: dict) -> int:
    """Matmul parameters one routed row passes: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def latent_widths(cfg: dict) -> tuple:
    """(Lq, Lk): the query latent H x D and the key/value latent G x D."""
    D = cfg["head_dim"]
    return cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D


def attention_matmul_params(cfg: dict) -> int:
    """q and o at Lq, k and v at Lk, and convolution B: cca_time1 taps of a
    D x D map a head over the Lq + Lk channels (convolution A is one
    multiply a channel and tap: no matmul)."""
    d, (lq, lk) = cfg["hidden_size"], latent_widths(cfg)
    return 2 * d * lq + 2 * d * lk \
        + cfg["cca_time1"] * (lq + lk) * cfg["head_dim"]


def router_matmul_params(cfg: dict) -> int:
    """The down-projection, the two hidden maps and the last one."""
    R = cfg["router_hidden_size"]
    return cfg["hidden_size"] * R + 2 * R * R + R * cfg["router_experts"]


def pairs(cfg: dict) -> int:
    """Query-key pairs of one sequence under the causal mask."""
    S = cfg["max_length"]
    return S * (S + 1) // 2


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of the attention's core for one (query, key) pair
    over all query heads: q.k and p.v, 2 FLOPs a multiply-add."""
    return 2.0 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): the latent's
    projections and convolution B, the router's maps, the routed experts
    AT THE EXPECTED expected_rows_per_token (0.5: the rows an even router
    sends to the 8 held of 16) in every layer, and the sliced head ONCE
    though the table is read twice (the lookup is no matmul).  Attention
    over the causal pairs only, x 3 for training.  Work on pairs a block
    computes and masks away, and recomputed work, are no work of the
    algorithm."""
    S, d = cfg["max_length"], cfg["hidden_size"]
    # a router that takes no gradient runs forward only: 2 of the 6
    router = router_matmul_params(cfg) * (1.0 if cfg["train_router"]
                                          else 2.0 / 6.0)
    layer = (attention_matmul_params(cfg) + router
             + expected_rows_per_token(cfg) * expert_matmul_params(cfg))
    matmul = cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * pairs(cfg) \
        * cfg["num_hidden_layers"]
    return S * 6.0 * matmul + attend


def attend_passes(cfg: dict) -> dict:
    """The kernel passes over a site's pairs a training step, in block
    products (q.k or p.v and their like).  COUNTED, the algorithm's: the
    forward's 2 and the backward kernel's 5 (the scores again, dP, dV, dK,
    dQ).  Besides them a step of this cell RUNS the forward a second time
    (`recomputed_forward`): the layer is the unit of recomputation, and here
    the compiler does not merge the recomputed forward with the first as it
    does in the three older decoder cells (PERF.md 6, PR 43: 8 forward
    kernel calls a traced step for 4 layers; merged, the layers' kept
    activations would not fit beside the state).  A recomputed pass is
    never counted, so the share reads lower by the time it takes.
    tests/benchmark/test_zaya_benchmark.py holds these counts to the
    kernels the step calls once the v5e's compiler is done with it,
    chip-less."""
    del cfg
    return {"forward": 1, "recomputed_forward": 1, "backward": 1,
            "products": 2 * 1 + 5}


def attend_flops_per_step(cfg: dict, sequences: int = 1) -> float:
    """FLOPs of the attention's core a training step, over the causal
    pairs, every pass that runs counted once (attend_passes).  What
    cca_attend_roofline.train divides by the device time under the scope
    `cca.attend`, which holds those passes, the recomputed forward, and
    nothing else but the backward's glue (rowsum(dO * O), a group's dK and
    dV added up, the chunks' slices), and the MXU's peak.  The kernels compute whole blocks
    and mask the ones the diagonal cuts, so they run more than these: the
    share cannot pass 100%."""
    return (attend_passes(cfg)["products"] / 2.0) \
        * attend_flops_per_pair(cfg) * pairs(cfg) \
        * cfg["num_hidden_layers"] * sequences

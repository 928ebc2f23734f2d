"""keye-vl-2.0-30b-a3b: the build function, the synthetic batch and the FLOP
counts of benchmark/configs/keye-vl-2.0-30b-a3b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches: cross entropy plus
    the index's loss)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    sa, rope = cfg["sa_config"], cfg["rope_scaling"]
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert not cfg["attention_bias"] and not cfg["use_sliding_window"]
    assert cfg["decoder_sparse_step"] == 1 and not cfg["mlp_only_layers"]
    assert sa["indexer_num_kv_heads"] == 1 and rope["rope_type"] == "default"
    spec = models.sparse_decoder(models.SparseDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"],
        mrope_section=tuple(rope["mrope_section"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], q_chunk=sa["q_chunk_size"],
        kv_chunk=sa["kv_chunk_size"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        residual_init_layers=cfg["published"]["num_hidden_layers"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed text sequences of max_length tokens: ids uniform over
    the vocabulary slice held here, the labels the ids shifted by one, the
    three position streams equal (text has one position a token)."""
    rng = np.random.RandomState(fold_seed(seed))
    S = cfg["max_length"]
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, S + 1)).astype(np.int64)
    streams = len(cfg["rope_scaling"]["mrope_section"])
    tokens, labels, positions = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:],
            positions: np.ascontiguousarray(np.broadcast_to(
                np.arange(S, dtype=np.int32), (batch, streams, S)))}


def expected_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here when the router's load
    is even: top_k x held / all (1.0 at 8 x 16 / 128)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def expert_matmul_params(cfg: dict) -> int:
    """Matmul parameters one routed row passes: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    """q and o at H x D, k and v at G x D, and the index's three maps."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    sa = cfg["sa_config"]
    return (2 * d * cfg["num_attention_heads"] * D
            + 2 * d * cfg["num_key_value_heads"] * D
            + d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                   + sa["indexer_head_dim"] + sa["indexer_num_heads"]))


def keys_selected(cfg: dict) -> int:
    """Query-key pairs of one sequence the index keeps: sum over t of
    min(t + 1, topk)."""
    S = cfg["max_length"]
    seen = min(S, cfg["sa_config"]["topk"])
    return seen * (seen + 1) // 2 + (S - seen) * cfg["sa_config"]["topk"]


def keys_causal(cfg: dict) -> int:
    S = cfg["max_length"]
    return S * (S + 1) // 2


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of the main attention for one chosen (query, key)
    pair over all heads: q.k and p.v, 2 FLOPs a multiply-add."""
    return 2.0 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def index_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of the index's scoring for one (query, key) pair."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): the attention's and
    the index's projections, the router, the routed experts AT THE EXPECTED
    expected_rows_per_token (1.0: the rows an even router sends to the 16
    held of 128) in every layer, and the head.  Attention over the CHOSEN
    keys only (keys_selected: sum over t of min(t + 1, 2048)), x 3 for
    training; the index's scoring over the causal keys forward and over the
    chosen ones backward (its loss reads no other).  Work on keys that were
    masked away, and recomputed work, are no work of the algorithm."""
    S, L, d = cfg["max_length"], cfg["num_hidden_layers"], cfg["hidden_size"]
    layer = (attention_matmul_params(cfg) + d * cfg["router_experts"]
             + expected_rows_per_token(cfg) * expert_matmul_params(cfg))
    matmul = L * layer + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * keys_selected(cfg)
    index = index_flops_per_pair(cfg) * (keys_causal(cfg)
                                         + 2 * keys_selected(cfg))
    return S * 6.0 * matmul + L * (attend + index)


def grouped_matmul_flops_per_step(cfg: dict, tokens: int) -> float:
    """FLOPs of the expert layers' grouped matmuls a training step at the
    expected rows: forward 1 and backward 2 (input and weight gradient)
    passes of 2 x rows x parameters a row.  Recomputed work is never
    counted, whatever `use_recompute` says."""
    passes = 3
    rows = tokens * expected_rows_per_token(cfg)
    return passes * 2.0 * rows * expert_matmul_params(cfg) \
        * cfg["num_hidden_layers"]


def attend_flops_per_step(cfg: dict, sequences: int) -> float:
    """FLOPs of the main attention's core over the CHOSEN keys a training
    step: the algorithm's passes, forward 2 products (q.k, p.v) and
    backward 5 (the scores again, dP, dV, dK, dQ).  Recomputed work is
    never counted, whatever `use_recompute` says and whatever the program
    recomputes: a PR that stops or starts recomputing a forward moves the
    share through the time alone, and a count tied to what the program
    recomputes goes stale with every such PR.  What
    dsa_attend_roofline.train divides by the device time under the scope
    `dsa.attend`, which holds every pass that runs, and the MXU's peak: the
    masked block engine is bound by its matmuls, of which it runs
    4.3 times these at S 16384 (every causal block, masked), so the share
    cannot pass 100% and reads low by design.  The heads' summed
    probabilities (one more q.k product a pass, for the index's loss) are
    not counted."""
    passes = 2 + 5
    return (passes / 2.0) * attend_flops_per_pair(cfg) \
        * keys_selected(cfg) * cfg["num_hidden_layers"] * sequences

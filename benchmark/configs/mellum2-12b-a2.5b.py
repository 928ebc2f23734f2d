"""mellum2-12b-a2.5b: the build function, the synthetic batch and the FLOP
counts of benchmark/configs/mellum2-12b-a2.5b.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def layer_kinds(cfg: dict) -> list:
    """"sliding" / "full" of the layers held here: the first
    num_hidden_layers entries of the published layer_types."""
    return [KINDS[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


def yarn(cfg: dict):
    """The full layers' rotary as layers.rotary_embedding takes it."""
    rope = cfg["rope_parameters"]["full_attention"]
    assert rope["rope_type"] == "yarn", rope
    return {"factor": rope["factor"],
            "original_length": rope["original_max_position_embeddings"],
            "beta_fast": rope["beta_fast"], "beta_slow": rope["beta_slow"],
            "attention_factor": rope["attention_factor"]}


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    rope = cfg["rope_parameters"]
    depth = cfg["num_hidden_layers"]
    assert cfg["hidden_act"] == "silu" and not cfg["tie_word_embeddings"]
    assert not cfg["attention_bias"] and cfg["use_sliding_window"]
    assert set(cfg["mlp_layer_types"][:depth]) == {"sparse"}
    assert rope["sliding_attention"]["rope_type"] == "default"
    assert rope["sliding_attention"]["rope_theta"] == \
        rope["full_attention"]["rope_theta"]
    spec = models.windowed_decoder(models.WindowedDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        d_model=cfg["hidden_size"], n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        layer_types=tuple(layer_kinds(cfg)),
        sliding_window=cfg["sliding_window"],
        rope_theta=rope["full_attention"]["rope_theta"], yarn=yarn(cfg),
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=cfg["router_experts"],
        experts_held=cfg["num_experts"], expert_offset=cfg["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        d_expert=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
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
    vocabulary slice held here, the labels the ids shifted by one."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1)).astype(np.int64)
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:]}


def expected_rows_per_token(cfg: dict) -> float:
    """Rows a token sends to the experts held here when the router's load
    is even: top_k x held / all (1.0 at 8 x 8 / 64)."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["router_experts"]


def expert_matmul_params(cfg: dict) -> int:
    """Matmul parameters one routed row passes: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_matmul_params(cfg: dict) -> int:
    """q and o at H x D, k and v at G x D."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * D
            + 2 * d * cfg["num_key_value_heads"] * D)


def pairs(cfg: dict, kind: str) -> int:
    """Query-key pairs of one sequence the mask of a layer of `kind` lets
    through: sum over t of min(t + 1, sliding_window), or of t + 1."""
    S = cfg["max_length"]
    seen = min(S, cfg["sliding_window"]) if kind == "sliding" else S
    return seen * (seen + 1) // 2 + (S - seen) * seen


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of the attention's core for one (query, key) pair
    over all query heads: q.k and p.v, 2 FLOPs a multiply-add."""
    return 2.0 * 2 * cfg["num_attention_heads"] * cfg["head_dim"]


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): the attention's
    projections, the router, the routed experts AT THE EXPECTED
    expected_rows_per_token (1.0: the rows an even router sends to the 8
    held of 64) in every layer, and the sliced head.  Attention over the
    pairs inside causal AND window only (`pairs`), x 3 for training.  Work
    on pairs a block computes and masks away, and recomputed work, are no
    work of the algorithm."""
    S, d = cfg["max_length"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    # a router that takes no gradient runs forward only: 2 of the 6
    router = d * cfg["router_experts"] * (1.0 if cfg["train_router"]
                                          else 2.0 / 6.0)
    layer = (attention_matmul_params(cfg) + router
             + expected_rows_per_token(cfg) * expert_matmul_params(cfg))
    matmul = len(kinds) * layer + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * sum(
        pairs(cfg, kind) for kind in kinds)
    return S * 6.0 * matmul + attend


def grouped_matmul_flops_per_step(cfg: dict, tokens: int) -> float:
    """FLOPs of the expert layers' grouped matmuls a training step at the
    expected rows: forward 1 and backward 2 (input and weight gradient)
    passes of 2 x rows x parameters a row.  Recomputed work is never
    counted, whatever `use_recompute` says."""
    passes = 3
    rows = tokens * expected_rows_per_token(cfg)
    return passes * 2.0 * rows * expert_matmul_params(cfg) \
        * cfg["num_hidden_layers"]


def attend_passes(cfg: dict) -> dict:
    """The kernel passes over a site's pairs that RUN a training step, in
    block products (q.k or p.v and their like): the forward's 2 and the
    backward kernel's 5 (the scores again, dP, dV, dK, dQ).  The layer's
    recomputation traces the forward a second time, but a one-trip
    recurrence leaves no loop boundary between the two calls and their
    operands are the same, so the compiler keeps one and holds its output
    and logsumexp for the backward: a traced step on the chip runs 3 + 1
    forward kernels, not 6 + 2 (PERF.md 6, PR 38), with `use_recompute` or
    without.  tests/benchmark/test_mellum_benchmark.py holds these counts
    to the kernels the step calls once the v5e's compiler is done with
    it, chip-less."""
    del cfg
    return {"forward": 1, "backward": 1, "products": 2 * 1 + 5}


def attend_flops_per_step(cfg: dict, kind: str, sequences: int = 1) -> float:
    """FLOPs of the attention's core in the layers of `kind` a training
    step, over the pairs the mask lets through, every pass that runs
    counted once (attend_passes: forward and backward).  What
    attn_<kind>_roofline.train divides by the device time under the scope
    `attn.<kind>`, which holds those passes and nothing else but the
    backward's glue (rowsum(dO * O), a
    group's dK and dV added up, the chunks' slices), and the MXU's peak.
    The kernels compute whole blocks and mask the ones an edge cuts, so
    they run more than these (the `flash.plan` / `flash.bwd_plan` spans say
    how many blocks): the share cannot pass 100% and the sliding one reads
    lower by design."""
    layers_of_kind = sum(k == kind for k in layer_kinds(cfg))
    return (attend_passes(cfg)["products"] / 2.0) \
        * attend_flops_per_pair(cfg) * pairs(cfg, kind) * layers_of_kind \
        * sequences

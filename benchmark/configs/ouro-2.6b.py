"""ouro-2.6b: the build function, the synthetic batch and the FLOP count of
benchmark/configs/ouro-2.6b.json."""

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
    spec = models.looped_decoder(models.LoopedDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["num_hidden_layers"], n_head=cfg["num_attention_heads"],
        head_dim=cfg["head_dim"], d_model=cfg["hidden_size"],
        d_inner=cfg["intermediate_size"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"], loop_steps=cfg["total_ut_steps"],
        exit_gate=cfg["exit_gate"], entropy_beta=cfg["entropy_beta"],
        use_recompute=cfg["use_recompute"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` packed sequences of max_length tokens: ids uniform in
    [0, vocab), the labels the ids shifted by one, no padding."""
    rng = np.random.RandomState(fold_seed(seed))
    ids = rng.randint(0, cfg["vocab_size"],
                      size=(batch, cfg["max_length"] + 1)).astype(np.int64)
    tokens, labels = spec.feed_names
    return {tokens: ids[:, :-1], labels: ids[:, 1:]}


def layer_matmul_params(cfg: dict) -> int:
    d = cfg["hidden_size"]
    return (4 * d * cfg["num_attention_heads"] * cfg["head_dim"]
            + 3 * d * cfg["intermediate_size"])


def flops_per_sample(cfg: dict) -> float:
    """One sequence of max_length tokens.  Per token 6 x the matmul
    parameters a token passes (2 forward, 4 backward): the layers on every
    one of the R trips, and the head once a trip; plus the attention score
    and value matmuls by benchmark/harness/flops.py's convention (4*S*H*dh
    forward a position and layer, x 3 for training, the causal half not
    taken off).  The trip that is computed again in the backward pass is
    not counted: recomputed work is no work of the algorithm."""
    S, R, L = cfg["max_length"], cfg["total_ut_steps"], \
        cfg["num_hidden_layers"]
    matmul = R * L * layer_matmul_params(cfg) \
        + R * cfg["hidden_size"] * cfg["vocab_size"]
    attn = 3 * 4 * S * cfg["num_attention_heads"] * cfg["head_dim"] * R * L
    return S * (6.0 * matmul + attn)

"""phi-4-mini-flash: the build function, the synthetic batch and the FLOP,
pair and byte counts of benchmark/configs/phi-4-mini-flash.json."""

import numpy as np

from benchmark.harness.traffic import fold_seed

MAMBA, MEMORY, SLIDING, FULL, GMU, CROSS = (
    "mamba", "memory", "sliding", "full", "gmu", "cross")


def layer_kinds(cfg: dict) -> tuple:
    """A layer's kind, one entry a layer: `self_decoder_periods` x (mamba,
    sliding), the layer that hands out the memory, the one that hands out
    K and V, `cross_decoder_periods` x (gmu, cross); their count is the
    depth."""
    kinds = (MAMBA, SLIDING) * cfg["self_decoder_periods"] \
        + (MEMORY, FULL) + (GMU, CROSS) * cfg["cross_decoder_periods"]
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
    assert cfg["model_type"] == "phi4flash" and cfg["hidden_act"] == "silu"
    assert cfg["tie_word_embeddings"] and not cfg["mlp_bias"]
    assert not cfg["lm_head_bias"] and cfg["mb_per_layer"] == 2
    assert cfg["embd_pdrop"] == cfg["resid_pdrop"] == 0
    layer_kinds(cfg)
    spec = models.sambay_decoder(models.SambaYDecoderConfig(
        vocab_size=cfg["vocab_size"], max_length=cfg["max_length"],
        d_model=cfg["hidden_size"], d_inner=cfg["intermediate_size"],
        n_head=cfg["num_attention_heads"],
        n_kv_head=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        self_periods=cfg["self_decoder_periods"],
        cross_periods=cfg["cross_decoder_periods"],
        expand=cfg["mamba_expand"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"],
        layer_norm_eps=cfg["layer_norm_eps"], init_std=cfg["init_std"],
        lambda_std=cfg["lambda_std"], use_recompute=cfg["use_recompute"]))
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


def channels(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mixer_matmul_params(cfg: dict, kind: str) -> int:
    """Matmul parameters a token passes in one layer's mixer."""
    d, E = cfg["hidden_size"], channels(cfg)
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    if kind in (MAMBA, MEMORY):
        R, N = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
        return d * 2 * E + E * (R + 2 * N) + R * E + E * d
    if kind == GMU:
        return 2 * d * E
    if kind == CROSS:
        return 2 * d * d
    return d * (d + 2 * kv) + d * d


def visible_pairs(cfg: dict, kind: str) -> int:
    """Query-key pairs one map's mask lets through in a row of max_length
    tokens: causal, in a sliding layer also t - s < sliding_window."""
    t = np.arange(cfg["max_length"], dtype=np.int64)
    seen = t + 1
    if kind == SLIDING:
        seen = np.minimum(seen, cfg["sliding_window"])
    return int(seen.sum())


def attend_flops_per_pair(cfg: dict) -> float:
    """Forward FLOPs of differential attention's two maps for one visible
    pair over all head pairs: q.k at head D and p.v at width 2 D, 2 FLOPs a
    multiply-add, two maps a pair of heads."""
    D = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2.0 * (D + 2 * D) * cfg["num_attention_heads"]


def scan_flops_per_step(cfg: dict, sequences: int = 1) -> float:
    """Operations of the selective scans (the op selective_scan, scope
    `ssm.scan`) a training step, an exponential counted as one: a token,
    channel and state takes 7 in the forward (dt A, exp, the decay's
    product, dt x B's two, the state's add, s C and y's add) and 14 in the
    backward (the decay and the state again, ds, dC, du, dB, the three of
    g, ddt, dA, the carried ds).  No recomputed pass is counted and the
    count is the same whatever engine runs the scan.  None of it is the
    MXU's: it is vector-unit work."""
    kinds = layer_kinds(cfg)
    layers = kinds.count(MAMBA) + kinds.count(MEMORY)
    return 21.0 * layers * sequences * cfg["max_length"] * channels(cfg) \
        * cfg["mamba_d_state"]


def scan_bytes_per_step(cfg: dict, sequences: int = 1,
                        element_bytes: int = 2) -> float:
    """Bytes the same two passes have to move through HBM whatever the
    engine, at the element size of the op's boundary under the keep tier
    (bf16): the forward reads x, dt [S, E] and B, C [S, N] and writes y;
    the backward reads those and dy and writes dx, ddt, dB, dC.  The chunk
    starts, A, D and their gradients are an engine's choice or small and
    are not counted."""
    kinds = layer_kinds(cfg)
    layers = kinds.count(MAMBA) + kinds.count(MEMORY)
    rows = sequences * cfg["max_length"]
    wide = rows * channels(cfg) * element_bytes
    narrow = rows * cfg["mamba_d_state"] * element_bytes
    return float(layers * ((3 * wide + 2 * narrow) + (5 * wide + 4 * narrow)))


def flops_per_sample(cfg: dict) -> float:
    """One row of max_length tokens.  Per token 6 x the matmul parameters
    it passes (2 forward, 4 backward): every layer's mixer by its kind and
    its gated MLP, the tied head once (the embedding is a gather).
    Differential attention over the pairs its mask lets through only
    (`visible_pairs`), x 3 for training.  The scans' vector-unit work
    (scan_flops_per_step: 0.08% of the step) is counted with them.  The
    convolution, the norms, the gates, work on pairs a block computes and
    masks away, and recomputed work are not counted."""
    S, d = cfg["max_length"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    matmul = sum(mixer_matmul_params(cfg, k) for k in kinds) \
        + len(kinds) * 3 * d * cfg["intermediate_size"] \
        + d * cfg["vocab_size"]
    attend = 3 * attend_flops_per_pair(cfg) * sum(
        visible_pairs(cfg, k) for k in kinds if k in (SLIDING, FULL, CROSS))
    return S * 6.0 * matmul + attend + scan_flops_per_step(cfg)

"""transformer-base: the build function, the synthetic batch and the FLOP
count of benchmark/configs/transformer-base.json."""

import numpy as np

from benchmark.harness import flops
from benchmark.harness.traffic import fold_seed


def build(cfg: dict, seed: int):
    """The training program in paddle_tpu's default environment; returns
    the ModelSpec (its `.loss` is what a step fetches)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    spec = models.transformer(models.TransformerConfig(
        src_vocab_size=cfg["src_vocab_size"],
        trg_vocab_size=cfg["trg_vocab_size"], max_length=cfg["max_length"],
        n_layer=cfg["n_layer"], n_head=cfg["n_head"], d_model=cfg["d_model"],
        d_inner=cfg["d_inner"], dropout=cfg["dropout"],
        label_smooth_eps=cfg["label_smooth_eps"],
        use_flash_attention=cfg["use_flash_attention"],
        fuse_qkv=cfg["fuse_qkv"]))
    opt = cfg["optimizer"]
    assert opt["name"] == "adam", opt
    fluid.optimizer.AdamOptimizer(
        learning_rate=opt["learning_rate"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` sentence pairs: source, target and label ids in
    [1, vocab), ragged lengths in [S/2, S] padded with 0 to S."""
    rng = np.random.RandomState(fold_seed(seed))
    S = cfg["max_length"]

    def seqs(vocab):
        w = rng.randint(1, vocab, size=(batch, S))
        for row, n in zip(w, rng.randint(S // 2, S + 1, size=batch)):
            row[n:] = 0
        return w.astype(np.int64)

    src, trg, lbl = spec.feed_names
    return {src: seqs(cfg["src_vocab_size"]),
            trg: seqs(cfg["trg_vocab_size"]),
            lbl: seqs(cfg["trg_vocab_size"])}


def flops_per_sample(cfg: dict) -> float:
    S = cfg["max_length"]
    return S * flops.transformer_train_flops_per_token(
        cfg["d_model"], cfg["d_inner"], cfg["n_layer"], S,
        cfg["trg_vocab_size"])

"""resnet50: the build function, the synthetic batch and the FLOP count of
benchmark/configs/resnet50.json."""

import numpy as np

from benchmark.harness import flops
from benchmark.harness.traffic import fold_seed


def build(cfg: dict, seed: int):
    import paddle_tpu as fluid
    from paddle_tpu import models

    fluid.reset_default_env()
    fluid.default_main_program().random_seed = fold_seed(seed)
    fluid.default_startup_program().random_seed = fold_seed(seed)
    side = cfg["image_size"]
    spec = models.resnet_imagenet(
        depth=cfg["depth"], class_num=cfg["class_num"],
        img_shape=(cfg["channels"], side, side))
    opt = cfg["optimizer"]
    assert opt["name"] == "momentum", opt
    fluid.optimizer.MomentumOptimizer(
        learning_rate=opt["learning_rate"],
        momentum=opt["momentum"]).minimize(spec.loss)
    return spec


def make_batch(cfg: dict, spec, batch: int, seed: int) -> dict:
    """`batch` images uniform in [0, 1) and their labels."""
    rng = np.random.RandomState(fold_seed(seed))
    side = cfg["image_size"]
    img, label = spec.feed_names
    return {
        img: rng.random_sample(
            (batch, cfg["channels"], side, side)).astype(np.float32),
        label: rng.randint(0, cfg["class_num"],
                           size=(batch, 1)).astype(np.int64),
    }


def flops_per_sample(cfg: dict) -> float:
    return flops.resnet_train_flops_per_image(
        cfg["depth"], cfg["image_size"], cfg["class_num"])

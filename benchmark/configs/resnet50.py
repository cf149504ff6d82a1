"""The ``resnet50`` configuration as the program builds it: the repo's
own ``nets.resnet`` compiled with the reference ImageNet recipe."""

from __future__ import annotations

from typing import Dict


def build(cfg: Dict):
    """The compiled keras model (weights not yet the seed's)."""
    from analytics_zoo_tpu.models.image.imageclassification import resnet
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import (
        SGD, poly, warmup_then)
    depth = {(3, 4, 6, 3): 50}[tuple(cfg["stage_blocks"])]
    size = cfg["image_size"]
    model = resnet(depth, num_classes=cfg["num_classes"],
                   input_shape=(size, size, cfg["image_channels"]),
                   conv_padding=cfg["conv_padding"])
    opt, sched = cfg["optimizer"], cfg["optimizer"]["schedule"]
    schedule = warmup_then(
        sched["base"], sched["warmup_iterations"],
        poly(sched["base"], sched["power"],
             max_iteration=sched["max_iteration"]))
    model.compile(SGD(learning_rate=opt["learning_rate"],
                      momentum=opt["momentum"], schedule=schedule),
                  cfg["loss"])
    return model


def input_spec(cfg: Dict) -> Dict:
    """What one record is, for the data generator."""
    size = cfg["image_size"]
    return {"kind": "image", "shape": [size, size, cfg["image_channels"]],
            "classes": cfg["num_classes"]}

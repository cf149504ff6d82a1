"""Plain reference for the ``resnet50`` configuration.

ResNet-50 v1.5 (He et al. 2015, "Deep Residual Learning for Image
Recognition"; the torchvision layout: bottleneck stages 3-4-6-3, the
stride on each stage's first 3x3) in float32 at ``highest`` precision,
with its loss, gradients and the recipe's SGD step.  Straight
``jax.numpy``/``lax``: no kernels, no mixed precision, nothing of the
program.

Departures from the paper, all stated in ``configs/resnet50.json``:
XLA ``SAME`` padding (the repo's ``nets.resnet`` default; torchvision
pads symmetrically), BatchNorm epsilon 1e-3 and momentum 0.99 (the keras defaults the repo
builds with).  BatchNorm's moving averages are kept as the program keeps
them (biased batch variance): a training step does not read them, but
they are the one record of the forward pass that survives a dispatch.

Each bottleneck is recomputed in the backward pass (``jax.checkpoint``)
so that float32 activations of a 128-image batch fit the chip beside
nothing else; BatchNorm ties the rows of a batch together, so the batch
is not cut into blocks.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common

EXPANSION = 4


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    """(name, shape, kind) of every parameter in forward order."""
    out = [("stem.conv", (7, 7, cfg["image_channels"], cfg["stem_width"]),
            "conv")]
    out += _bn("stem.bn", cfg["stem_width"])
    in_ch = cfg["stem_width"]
    for s, (n, width) in enumerate(zip(cfg["stage_blocks"],
                                       cfg["stage_widths"])):
        for b in range(n):
            p = f"s{s}.b{b}"
            out.append((f"{p}.conv1", (1, 1, in_ch, width), "conv"))
            out += _bn(f"{p}.bn1", width)
            out.append((f"{p}.conv2", (3, 3, width, width), "conv"))
            out += _bn(f"{p}.bn2", width)
            out.append((f"{p}.conv3", (1, 1, width, EXPANSION * width),
                        "conv"))
            out += _bn(f"{p}.bn3", EXPANSION * width)
            if b == 0:
                out.append((f"{p}.down.conv",
                            (1, 1, in_ch, EXPANSION * width), "conv"))
                out += _bn(f"{p}.down.bn", EXPANSION * width)
            in_ch = EXPANSION * width
    out.append(("fc.kernel", (in_ch, cfg["num_classes"]), "fc"))
    out.append(("fc.bias", (cfg["num_classes"],), "fc_bias"))
    return out


def _bn(prefix: str, ch: int):
    return [(f"{prefix}.gamma", (ch,), "one"), (f"{prefix}.beta", (ch,),
                                                "zero")]


def param_order(cfg: Dict) -> List[str]:
    return [name for name, _, _ in _spec(cfg)]


def state_init(cfg: Dict) -> Dict[str, jax.Array]:
    """BatchNorm's moving mean (0) and variance (1) in forward order."""
    out = {}
    for name, shape, kind in _spec(cfg):
        if name.endswith(".gamma"):
            bn = name[:-len(".gamma")]
            out[bn + ".mean"] = jnp.zeros(shape, jnp.float32)
            out[bn + ".var"] = jnp.ones(shape, jnp.float32)
    return out


def init(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """All weights in one jitted call from the seed: He-normal (fan-out)
    convolutions, unit BatchNorm, the classifier uniform in
    +-1/sqrt(fan_in), as torchvision initialises the model."""
    spec = _spec(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "conv":
                fan_out = shape[0] * shape[1] * shape[3]
                params[name] = jax.random.normal(k, shape, jnp.float32) \
                    * jnp.sqrt(2.0 / fan_out)
            elif kind == "one":
                params[name] = jnp.ones(shape, jnp.float32)
            elif kind == "zero":
                params[name] = jnp.zeros(shape, jnp.float32)
            else:
                fan_in = cfg["stage_widths"][-1] * EXPANSION
                params[name] = jax.random.uniform(
                    k, shape, jnp.float32, -1.0, 1.0) / jnp.sqrt(fan_in)
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _conv(stride: int):
    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=common.HIGHEST)
    return f


def _batch_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta, mean, var


def forward(cfg: Dict, params, images, rounding: Optional[str] = None):
    """Logits of a batch in training mode, and the batch statistics
    (mean and biased variance) that each BatchNorm normalised by."""
    eps = cfg["bn_epsilon"]

    def cbn(ps, x, conv, bn, stride, stats, relu=True):
        y = common.product(_conv(stride), rounding)(x, ps[conv])
        y, stats[bn + ".mean"], stats[bn + ".var"] = _batch_norm(
            y, ps[bn + ".gamma"], ps[bn + ".beta"], eps)
        return jax.nn.relu(y) if relu else y

    stats = {}
    x = cbn(params, images.astype(jnp.float32), "stem.conv", "stem.bn", 2,
            stats)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for s, n in enumerate(cfg["stage_blocks"]):
        for b in range(n):
            p = f"s{s}.b{b}"
            stride = 2 if (s > 0 and b == 0) else 1

            @jax.checkpoint
            def block(ps, x, p=p, stride=stride, first=(b == 0)):
                st = {}
                y = cbn(ps, x, f"{p}.conv1", f"{p}.bn1", 1, st)
                y = cbn(ps, y, f"{p}.conv2", f"{p}.bn2", stride, st)
                y = cbn(ps, y, f"{p}.conv3", f"{p}.bn3", 1, st, relu=False)
                short = cbn(ps, x, f"{p}.down.conv", f"{p}.down.bn",
                            stride, st, relu=False) if first else x
                return jax.nn.relu(y + short), st

            x, st = block({k: v for k, v in params.items()
                           if k.startswith(p + ".")}, x)
            stats.update(st)
    x = jnp.mean(x, axis=(1, 2))
    logits = common.product(common.matmul, rounding)(
        x, params["fc.kernel"]) + params["fc.bias"]
    return logits, stats


def prepare(cfg: Dict, stages: List[Dict], x):
    """The input stages of a cell's file, in float32: the images the
    model is to see, from the rows as the data set holds them."""
    x = jnp.asarray(x)
    for stage in stages:
        if stage["kind"] == "flip_normalize":
            x = (x[:, :, ::-1, :].astype(jnp.float32) - stage["mean"]) \
                / stage["std"]
        else:
            raise ValueError(f"unknown stage {stage!r}")
    return x.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _step(cfg_key, rounding, fault):
    cfg = json.loads(cfg_key)
    opt = cfg["optimizer"]

    def step(params, state, batch, i):
        images, labels = batch
        if fault == "half_batch":
            half = images.shape[0] // 2
            images, labels = images[:half], labels[:half]

        def objective(p):
            logits, stats = forward(cfg, p, images, rounding)
            return common.sparse_cross_entropy(logits, labels), stats

        (loss, stats), grads = jax.value_and_grad(
            objective, has_aux=True)(params)
        m = cfg["bn_momentum"]
        moving = {k: m * v + (1 - m) * stats[k]
                  for k, v in state["moving"].items()}
        new, state = common.optimizer_update(opt, params, grads, state, i)
        return new, dict(state, moving=moving), loss, grads

    return jax.jit(step, donate_argnums=(1,))


def follow(cfg: Dict, seed: int, batches, moment_after: int,
           rounding: Optional[str] = None, fault: Optional[str] = None):
    """The first ``len(batches)`` training steps from the seed's weights
    on ``batches`` (each ``(float32 images, int labels)``)."""
    step = _step(json.dumps(cfg, sort_keys=True), rounding, fault)
    batches = ((jnp.asarray(x, jnp.float32),
                jnp.asarray(y, jnp.int32).reshape(-1)) for x, y in batches)
    return common.follow(step, cfg["optimizer"], init(cfg, seed), batches,
                         moment_after, moving=state_init(cfg))

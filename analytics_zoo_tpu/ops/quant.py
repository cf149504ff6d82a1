"""Calibrated int8 kernels.

Reference: InferenceModel.scala:400-421 — TF models are calibrated and
converted to int8 OpenVINO IR (activation ranges recorded over a
calibration set, then int8 execution).

TPU-native version: symmetric per-tensor ACTIVATION scales (recorded by
a calibration pass) + per-output-channel WEIGHT scales; matmul/conv run
int8 x int8 -> int32 on the MXU (v5e int8 peak is 2x bf16) and rescale
to f32 in the epilogue.  The quantized path is params-driven: a layer
whose params carry ``kernel_scale``/``act_scale`` (with an int8
``kernel``) executes quantized — no layer-class mutation, the same
model object serves f32 and int8.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def quantize_activation(x, act_scale):
    """Symmetric int8 quantization with a calibrated scale."""
    return jnp.clip(jnp.round(x.astype(jnp.float32) / act_scale),
                    -127, 127).astype(jnp.int8)


def quantized_matmul(x, kernel_q, kernel_scale, act_scale):
    """int8 x int8 -> int32 contraction over the last/first dims, f32
    rescale epilogue.  ``kernel_scale`` has keepdims shape
    (1, ..., out)."""
    xq = quantize_activation(x, act_scale)
    acc = jax.lax.dot_general(
        xq, kernel_q, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    scale = act_scale * kernel_scale.reshape(
        (1,) * (acc.ndim - 1) + (-1,))
    return acc.astype(jnp.float32) * scale


def quantized_conv(x, kernel_q, kernel_scale, act_scale, *, strides,
                   padding, rhs_dilation, dimension_numbers,
                   feature_group_count=1):
    """int8 conv -> int32 accumulation, f32 rescale epilogue.  Always
    the integer convolution: both backends this installation has (the
    CPU and the v5e) compile s8 x s8 -> s32, and a backend that refuses
    it raises from the program's own compile with the compiler's
    message — never an f32 stand-in under the int8 name."""
    xq = quantize_activation(x, act_scale)
    acc = jax.lax.conv_general_dilated(
        xq, kernel_q, window_strides=strides, padding=padding,
        rhs_dilation=rhs_dilation,
        dimension_numbers=dimension_numbers,
        feature_group_count=feature_group_count,
        preferred_element_type=jnp.int32)
    scale = act_scale * kernel_scale.reshape(
        (1,) * (acc.ndim - 1) + (-1,))
    return acc.astype(jnp.float32) * scale


# -------------------------------------------------- model-level workflow
def calibrate_model(model, calib_data, batch_size: int = 32,
                    max_batches: int = 8) -> Dict[str, float]:
    """Calibration pass: run eager forwards over ``calib_data``
    recording each layer's input absmax via the engine's activation
    taps (ref InferenceModel.scala:400-421's OpenVINO calibration
    role).  ``calib_data`` is an ndarray/pytree-of-columns or a
    FeatureSet; returns ``{layer_name: max |input|}``."""
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras.engine import (
        record_activations)
    variables = model.get_variables()
    if isinstance(calib_data, FeatureSet):
        batches = (b[0] for b in calib_data.epoch_batches(
            0, batch_size, train=False))
    else:
        n = len(jax.tree_util.tree_leaves(calib_data)[0])
        batches = (jax.tree_util.tree_map(
            lambda a: a[i:i + batch_size], calib_data)
            for i in range(0, n, batch_size))
    ranges: Dict[str, float] = {}
    with record_activations() as taps:
        for i, xb in enumerate(batches):
            if i >= max_batches:
                break
            model.apply(variables["params"], xb,
                        state=variables["state"], training=False)
        ranges.update(taps)
    return ranges


def quantize_model(variables, act_ranges, min_size: int = 1024):
    """Produce the params-driven int8 layout from calibrated ranges:
    per-layer int8 ``kernel`` + per-output-channel ``kernel_scale``
    (keepdims — shape ``(1, ..., out)``) + symmetric scalar
    ``act_scale``.  Layers whose params carry those keys execute
    ``quantized_matmul``/``quantized_conv`` natively (Dense/conv
    ``call``); everything else is untouched — the same model object
    serves f32 and int8."""
    params = variables["params"]
    qparams = {}
    for lname, p in params.items():
        qp = dict(p) if isinstance(p, dict) else p
        k = p.get("kernel") if isinstance(p, dict) else None
        rng_max = act_ranges.get(lname, 0.0)
        if k is not None and rng_max > 0.0:
            arr = np.asarray(k)
            if (arr.dtype == np.float32 and arr.ndim >= 2
                    and arr.size >= min_size):
                axes = tuple(range(arr.ndim - 1))
                w_scale = np.maximum(
                    np.max(np.abs(arr), axis=axes, keepdims=True)
                    / 127.0, 1e-12).astype(np.float32)
                qp["kernel"] = np.clip(
                    np.round(arr / w_scale), -127, 127).astype(np.int8)
                qp["kernel_scale"] = w_scale
                qp["act_scale"] = np.float32(max(rng_max / 127.0, 1e-12))
        qparams[lname] = qp
    return {"params": qparams, "state": variables["state"]}

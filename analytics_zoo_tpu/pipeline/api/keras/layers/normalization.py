"""Normalization layers (ref: keras/layers/BatchNormalization.scala,
LayerNorm in keras/layers/ internal transformer utils).

BatchNormalization is the framework's canonical *stateful* layer: its
moving statistics live in the ``state`` collection and ``apply`` returns
the updated state (pure-functionally) when training.  Under data
parallelism the batch statistics are computed per-shard, matching the
reference's per-replica BN behavior in BigDL.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.pipeline.api.keras.engine import Layer, Params, State


class BatchNormalization(Layer):
    def __init__(self, epsilon: float = 1e-3, momentum: float = 0.99,
                 beta_init="zero", gamma_init="one", axis: int = -1,
                 scale: bool = True, center: bool = True, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = float(epsilon)
        self.momentum = float(momentum)
        self.axis = axis
        self.scale = scale
        self.center = center
        self.beta_init = beta_init
        self.gamma_init = gamma_init

    def _dim(self, input_shape):
        return input_shape[self.axis]

    def build(self, rng, input_shape) -> Params:
        d = self._dim(input_shape)
        params: Params = {}
        if self.scale:
            self.add_weight(params, rng, "gamma", (d,), init=self.gamma_init)
        if self.center:
            self.add_weight(params, rng, "beta", (d,), init=self.beta_init)
        return params

    def init_state(self, input_shape) -> State:
        d = self._dim(input_shape)
        dtype = get_policy().param_dtype
        return {"moving_mean": jnp.zeros((d,), dtype),
                "moving_var": jnp.ones((d,), dtype)}

    def apply(self, params, x, state=None, training=False, rng=None):
        ax = self.axis % x.ndim
        reduce_axes = tuple(i for i in range(x.ndim) if i != ax)
        bshape = [1] * x.ndim
        bshape[ax] = x.shape[ax]

        if training:
            # single-pass f32 statistics: mean and mean-of-squares share
            # one read of the (bf16) activation — XLA multi-output-fuses
            # the two reductions, where jnp.var's (x - mean)^2 form
            # costs a second full pass.  var = E[x^2] - E[x]^2 in f32 is
            # the standard mixed-precision BN formulation (flax does the
            # same); clamp guards the tiny negative from cancellation.
            xf = x.astype(jnp.float32)
            mean = jnp.mean(xf, axis=reduce_axes)
            m2 = jnp.mean(xf * xf, axis=reduce_axes)
            var = jnp.maximum(m2 - mean * mean, 0.0)
            m = self.momentum
            new_state = {
                "moving_mean": m * state["moving_mean"] + (1 - m) * mean,
                "moving_var": m * state["moving_var"] + (1 - m) * var,
            }
        else:
            mean = state["moving_mean"]
            var = state["moving_var"]
            new_state = state

        # fold mean/var/gamma/beta into per-channel scale+bias (C cheap
        # f32 scalars), then apply ONE fused multiply-add in the input's
        # compute dtype — the per-element work is bf16 and fusable into
        # the producing conv's epilogue.
        inv = jax.lax.rsqrt(var + self.epsilon)
        if self.scale:
            inv = inv * params["gamma"]
        bias = -mean * inv
        if self.center:
            bias = bias + params["beta"]
        y = x * inv.reshape(bshape).astype(x.dtype) \
            + bias.reshape(bshape).astype(x.dtype)
        return y, new_state


class LayerNorm(Layer):
    """Layer normalization over the last dim (transformer building block,
    ref: keras/layers/ internal LayerNorm used by BERT.scala).

    ``activation`` fuses an elementwise epilogue (e.g. "gelu") into the
    normalization via the kernel suite (ops/fused.py layernorm_act) —
    one pass over the activation instead of LN→HBM→activation."""

    def __init__(self, epsilon: float = 1e-5, activation=None, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = float(epsilon)
        from analytics_zoo_tpu.ops import activations as acts
        self.activation = acts.get(activation)

    def build(self, rng, input_shape) -> Params:
        d = input_shape[-1]
        params: Params = {}
        self.add_weight(params, rng, "gamma", (d,), init="one")
        self.add_weight(params, rng, "beta", (d,), init="zero")
        return params

    def call(self, params, x, training=False, rng=None):
        if self.activation is not None:
            from analytics_zoo_tpu.ops import fused
            if fused.fused_enabled():
                return fused.layernorm_act(
                    x, params["gamma"], params["beta"],
                    eps=self.epsilon, activation=self.activation)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mean) / jnp.sqrt(var + self.epsilon)
        y = (y * params["gamma"] + params["beta"]).astype(x.dtype)
        if self.activation is not None:
            y = self.activation(y)
        return y


class RMSNorm(Layer):
    """Root-mean-square normalization over the last dim with a learned
    gain and no bias: ``x / sqrt(mean(x^2) + epsilon) * gamma``.  The
    statistics are taken in float32 whatever ``x`` is, and the result
    comes back in ``x.dtype``.  Applied to a ``(..., heads, head_dim)``
    tensor it is the per-head q/k norm (one gain of ``head_dim``)."""

    def __init__(self, epsilon: float = 1e-6, **kwargs):
        super().__init__(**kwargs)
        self.epsilon = float(epsilon)

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        self.add_weight(params, rng, "gamma", (input_shape[-1],),
                        init="one")
        return params

    def call(self, params, x, training=False, rng=None):
        return rms_norm(x, params["gamma"], self.epsilon)


def rms_norm(x, gamma, epsilon: float):
    """The ``RMSNorm`` arithmetic (``GroupedQueryAttention`` applies it
    to q and k per head)."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + epsilon)
    return (xf * scale * gamma).astype(x.dtype)


class L2Normalization(Layer):
    """Unit-L2 normalize along an axis (objectdetection Normalize
    analogue)."""

    def __init__(self, axis: int = -1, epsilon: float = 1e-12, **kwargs):
        super().__init__(**kwargs)
        self.axis = axis
        self.epsilon = epsilon

    def call(self, params, x, training=False, rng=None):
        norm = jnp.linalg.norm(x, axis=self.axis, keepdims=True)
        return x / jnp.maximum(norm, self.epsilon)


class NormalizeScale(Layer):
    """Unit-L2 normalize along the channel axis, then multiply by a
    LEARNED per-channel scale — the SSD conv4_3 feature rescaler
    (ref: objectdetection/ssd/SSDGraph.scala:73 ``conv4_3_norm =
    NormalizeScale(2, scale=normScale)``; torchvision's
    ``backbone.scale_weight`` plays the same role)."""

    def __init__(self, axis: int = -1, scale_init: float = 20.0,
                 epsilon: float = 1e-12, **kwargs):
        super().__init__(**kwargs)
        self.axis = int(axis)
        self.scale_init = float(scale_init)
        self.epsilon = float(epsilon)

    def build(self, rng, input_shape) -> Params:
        c = input_shape[self.axis]
        params: Params = {}
        s = self.scale_init
        self.add_weight(params, rng, "scale", (c,),
                        init=lambda rng, shape, dtype:
                        jnp.full(shape, s, dtype))
        return params

    def call(self, params, x, training=False, rng=None):
        norm = jnp.linalg.norm(x, axis=self.axis, keepdims=True)
        y = x / jnp.maximum(norm, self.epsilon)
        # broadcast the per-channel scale along self.axis
        shape = [1] * x.ndim
        shape[self.axis] = -1
        return y * params["scale"].reshape(shape)

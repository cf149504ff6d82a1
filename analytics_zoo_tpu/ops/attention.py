"""Attention primitives.

``scaled_dot_product_attention`` is the single-device reference path —
one fused XLA program (two MXU matmuls + softmax).  The ring-parallel
long-context variant lives in ``parallel/ring_attention.py``.

No reference counterpart: the reference's BERT computes full-sequence
attention on one CPU node (keras/layers/BERT.scala:66); long-context
sharding is a new TPU-native capability (SURVEY.md §5).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def scaled_dot_product_attention(q, k, v, mask=None, causal: bool = False,
                                 scale: Optional[float] = None):
    """q,k,v: (B, H, T, D). mask: broadcastable to (B, H, Tq, Tk), 1=keep.

    Softmax statistics are computed in f32 even for bf16 inputs.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        idx_q = jnp.arange(tq)[:, None]
        idx_k = jnp.arange(tk)[None, :]
        logits = jnp.where(idx_q >= idx_k, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def blockwise_attention_step(q, k_blk, v_blk, acc, m, l, scale,
                             logits_bias=None):
    """One online-softmax accumulation step (the flash/ring inner loop).

    q: (B,H,Tq,D); k_blk/v_blk: (B,H,Tb,D);
    acc: (B,H,Tq,D) f32; m,l: (B,H,Tq) f32 running max / normalizer.
    Returns updated (acc, m, l).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if logits_bias is not None:
        s = s + logits_bias
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # rescale previous accumulation
    correction = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    acc_new = acc * correction[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
    return acc_new, m_new, l_new


def rotary_embedding(x, positions, base: float):
    """Rotary position embedding, rotate-half form.  x: (B, T, H, D)
    with D even; positions: (B, T) integer position ids.  The angles are
    taken in float32 and the result comes back in ``x.dtype``."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)

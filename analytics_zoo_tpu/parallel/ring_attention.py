"""Ring attention — sequence-parallel exact attention over the ICI ring.

Long-context capability with no reference counterpart (the reference's
attention is single-node full-sequence, SURVEY.md §5): the sequence is
sharded over the mesh's ``seq`` axis; each device holds one block of
Q/K/V.  K/V blocks rotate around the ring via ``lax.ppermute`` while
each device accumulates its queries' attention with the online-softmax
(flash) recurrence — memory stays O(T/n · T/n) per device and the K/V
transfer overlaps with compute on real hardware.

Built on ``shard_map`` so the collective schedule is explicit; inside
the shard the math is the same ``blockwise_attention_step`` the
single-device flash path uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.ops.attention import blockwise_attention_step
from analytics_zoo_tpu.parallel.mesh import SEQ_AXIS


def _ring_body(q, k, v, axis_name: str, causal: bool, scale: float,
               axis_size: int):
    """Per-shard computation: q,k,v are the local (B,H,Tblk,D) blocks."""
    my_idx = jax.lax.axis_index(axis_name)
    b, h, t_blk, d = q.shape

    acc = jnp.zeros((b, h, t_blk, d), jnp.float32)
    m = jnp.full((b, h, t_blk), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, t_blk), jnp.float32)

    def step(i, carry):
        acc, m, l, k_cur, v_cur = carry
        # which device's block are we currently holding?
        src_idx = (my_idx + i) % axis_size
        if causal:
            # global positions: queries my_idx*t_blk+.., keys src_idx*t_blk+..
            q_pos = my_idx * t_blk + jnp.arange(t_blk)[:, None]
            k_pos = src_idx * t_blk + jnp.arange(t_blk)[None, :]
            bias = jnp.where(q_pos >= k_pos, 0.0, -1e30)
        else:
            bias = None
        acc, m, l = blockwise_attention_step(
            q, k_cur, v_cur, acc, m, l, scale, logits_bias=bias)
        # rotate K/V one hop around the ring
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    acc, m, l, _, _ = jax.lax.fori_loop(
        0, axis_size, step, (acc, m, l, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = False,
                   scale: Optional[float] = None,
                   axis_name: str = SEQ_AXIS):
    """Exact attention with Q/K/V sharded on ``axis_name`` (dim 2).

    q,k,v: (B, H, T, D) global arrays; T must divide the seq-axis size.
    Returns (B, H, T, D) with the same sharding.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    axis_size = mesh.shape[axis_name]
    if axis_size == 1:
        from analytics_zoo_tpu.ops.attention import (
            scaled_dot_product_attention)
        return scaled_dot_product_attention(q, k, v, causal=causal,
                                            scale=scale)
    spec = P(None, None, axis_name, None)
    body = functools.partial(_ring_body, axis_name=axis_name,
                             causal=causal, scale=scale,
                             axis_size=axis_size)
    # replication checking off: the ring body is explicitly collective
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)

"""Pallas flash-attention kernels for TPU — forward AND backward.

Part of the fused kernel suite (ops/fused.py holds the elementwise /
reduction half — fused optimizer update, bias→GeLU, LayerNorm→act —
and the shared ``pallas_supported()`` capability probe that gates all
Pallas routing).  Single-chip long-context attention: O(T·Tb) VMEM
instead of the O(T²) logits matrix XLA materialises for plain
attention.  Pairs with parallel/ring_attention.py (across-chip SP):
ring handles the inter-chip blocks, this kernel is what each chip
should run on its local block.

The public ``flash_attention`` is differentiable: a ``custom_vjp``
routes the backward through two Pallas kernels (the standard
flash-attention backward — recompute the probability blocks from the
forward's saved log-sum-exp, then ``dv = PᵀdO``, ``ds = P∘(dOVᵀ - D)``,
``dq = dsK``, ``dk = dsᵀQ``), so the same memory bound holds in
training.

Grid: (batch·heads, T/block).  K/V (and in the backward Q/dO) for one
(batch·head) live in VMEM — fine for T·D up to ~4k·128 at bf16/f32;
the kernels stream the blocked operand.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _apply_causal_mask(s, q_start, k_start, block_q: int,
                       block_k: int):
    """Mask future positions in one (block_q, block_k) logits tile —
    the ONE definition shared by the forward and both backward kernels
    so P is recomputed under the identical mask."""
    q_pos = q_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, -1e30)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, scale: float, block_q: int):
    t = k_ref.shape[0]
    d = q_ref.shape[-1]
    q = q_ref[:] * scale                       # (block_q, d)
    q_idx = pl.program_id(1)

    n_k = t // block_k

    def body(i, carry):
        acc, m, l = carry
        k_blk = k_ref[pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            s = _apply_causal_mask(s, q_idx * block_q, i * block_k,
                                   block_q, block_k)
        m_blk = jnp.max(s, axis=1)
        m_new = jnp.maximum(m, m_blk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    if causal:
        # future k blocks are fully masked for every query row in this
        # q block — skip them instead of computing masked-out matmuls
        n_k = jnp.minimum(
            n_k, ((q_idx + 1) * block_q + block_k - 1) // block_k)
    acc, m, l = jax.lax.fori_loop(0, n_k, body, (acc, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # TPU blocks must be >=2D: lse is stored (block_q, 1)
    lse_ref[:] = (m + jnp.log(l_safe))[:, None].astype(lse_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, *, block_k: int, causal: bool,
                     scale: float, block_q: int):
    """dq for one q block: stream k blocks, recompute P from lse."""
    t = k_ref.shape[0]
    d = q_ref.shape[-1]
    # recompute logits EXACTLY as the forward did (same dtype for the
    # q*scale product), so exp(s - lse) reproduces the forward's P —
    # a higher-precision recompute would desynchronise from the saved
    # lse under bf16
    q = q_ref[:] * scale                          # (bq, d), input dtype
    do = do_ref[:].astype(jnp.float32)            # (bq, d)
    lse = lse_ref[:][:, 0]                        # (bq,)
    delta = delta_ref[:][:, 0]                    # (bq,)
    q_idx = pl.program_id(1)
    n_k = t // block_k

    def body(i, dq):
        k_blk = k_ref[pl.ds(i * block_k, block_k), :]
        v_blk = v_ref[pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32)
        if causal:
            s = _apply_causal_mask(s, q_idx * block_q, i * block_k,
                                   block_q, block_k)
        p = jnp.exp(s - lse[:, None])             # (bq, bk)
        dp = jnp.dot(do, v_blk.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        return dq + jnp.dot(ds, k_blk.astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    if causal:
        n_k = jnp.minimum(
            n_k, ((q_idx + 1) * block_q + block_k - 1) // block_k)
    dq = jax.lax.fori_loop(
        0, n_k, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, block_k: int, causal: bool,
                      scale: float, block_q: int):
    """dk/dv for one (k block, q block) grid cell.  The grid's
    innermost axis walks q blocks while dk/dv REVISIT the same output
    block — TPU pallas executes the grid sequentially per core, so
    accumulating into the output across the q axis is safe, and only
    ONE q block lives in VMEM at a time (the full-T operand layout
    OOM'd scoped vmem at T=8k)."""
    q_idx = pl.program_id(2)
    k_idx = pl.program_id(1)

    @pl.when(q_idx == 0)
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    def _compute():
        k_blk = k_ref[:]                          # (bk, d) input dtype
        v_blk = v_ref[:]                          # (bk, d)
        # same-dtype q*scale as the forward (see dq kernel note)
        q_blk = q_ref[:] * scale                  # (bq, d)
        do_blk = do_ref[:].astype(jnp.float32)    # (bq, d)
        lse = lse_ref[:][:, 0]
        delta = delta_ref[:][:, 0]

        s = jnp.dot(q_blk, k_blk.T,
                    preferred_element_type=jnp.float32)  # (bq, bk)
        if causal:
            s = _apply_causal_mask(s, q_idx * block_q, k_idx * block_k,
                                   block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dv_upd = jnp.dot(p.T, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v_blk.T.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        # dk = Σ ds_ijᵀ (scale·q_i): q_blk enters pre-scaled, so the
        # scale is already in the accumulation
        dk_upd = jnp.dot(ds.T, q_blk.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        dk_ref[:] += dk_upd.astype(dk_ref.dtype)
        dv_ref[:] += dv_upd.astype(dv_ref.dtype)

    if causal:
        # skip fully-masked cells (q block entirely above the diagonal)
        # — ~half the grid at large T would otherwise burn full matmuls
        # on results that are discarded
        pl.when((q_idx + 1) * block_q - 1 >= k_idx * block_k)(_compute)
    else:
        _compute()


def _resolve_blocks(t: int, block_q: int, block_k: int):
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError(
            f"seq len {t} must divide block sizes ({block_q}, {block_k})")
    return block_q, block_k


def _flash_fwd_impl(q, k, v, cfg):
    causal, scale, block_q, block_k, interpret = cfg
    b, h, t, d = q.shape
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    kernel = functools.partial(_flash_kernel, block_k=block_k,
                               causal=causal, scale=scale,
                               block_q=block_q)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32)),
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((None, block_q, d),
                                lambda i, j: (i, j, 0)),
                   pl.BlockSpec((None, block_q, 1),
                                lambda i, j: (i, j, 0))),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, t, d), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    out, _ = _flash_fwd_impl(q, k, v, cfg)
    return out


def _flash_vjp_fwd(q, k, v, cfg):
    out, lse = _flash_fwd_impl(q, k, v, cfg)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(cfg, res, dout):
    causal, scale, block_q, block_k, interpret = cfg
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, t, d)
    vf = v.reshape(b * h, t, d)
    dof = dout.reshape(b * h, t, d)
    of = out.reshape(b * h, t, d)
    # D_i = rowsum(dO_i ∘ O_i) — cheap elementwise, computed by XLA
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1, keepdims=True)       # (bh, t, 1)

    dq_kernel = functools.partial(_flash_dq_kernel, block_k=block_k,
                                  causal=causal, scale=scale,
                                  block_q=block_q)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=(b * h, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda i, j: (i, j, 0)),
        interpret=interpret,
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(_flash_dkv_kernel, block_k=block_k,
                                   causal=causal, scale=scale,
                                   block_q=block_q)
    # grid (bh, k blocks, q blocks): dk/dv output blocks are revisited
    # along the innermost q axis (sequential per core → accumulation is
    # safe); dk/dv must be f32 so the += accumulation doesn't round
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, t, d), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, t, d), jnp.float32)),
        grid=(b * h, t // block_k, t // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d),
                         lambda i, jk, jq: (i, jq, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda i, jk, jq: (i, jk, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda i, jk, jq: (i, jk, 0)),
            pl.BlockSpec((None, block_q, d),
                         lambda i, jk, jq: (i, jq, 0)),
            pl.BlockSpec((None, block_q, 1),
                         lambda i, jk, jq: (i, jq, 0)),
            pl.BlockSpec((None, block_q, 1),
                         lambda i, jk, jq: (i, jq, 0)),
        ],
        out_specs=(pl.BlockSpec((None, block_k, d),
                                lambda i, jk, jq: (i, jk, 0)),
                   pl.BlockSpec((None, block_k, d),
                                lambda i, jk, jq: (i, jk, 0))),
        interpret=interpret,
        name="flash_attention_dkv",
    )(qf, kf, vf, dof, lse, delta)
    dk = dk.astype(k.dtype)
    dv = dv.astype(v.dtype)

    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q,k,v: (B, H, T, D) -> (B, H, T, D).  Differentiable (flash
    backward kernels)."""
    b, h, t, d = q.shape
    if scale is None:
        scale = d ** -0.5
    block_q, block_k = _resolve_blocks(t, block_q, block_k)
    return _flash(q, k, v, (causal, scale, block_q, block_k, interpret))

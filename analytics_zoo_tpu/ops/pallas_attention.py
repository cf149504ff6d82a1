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

Grid: every operand enters through the grid, one (block_q, d) or
(block_k, d) tile at a time, so no sequence length is capped by VMEM.
The second grid axis walks a STATIC list of (q tile, k tile) pairs,
worked out on the host from the mask's description and handed to the
kernels as scalar-prefetch tables: a tile with no allowed pair is not
in the list, so it costs neither a grid step nor a DMA, in the forward,
dq and dkv kernels alike; a tile every pair of which is allowed skips
the mask's arithmetic.  The mask itself is evaluated from iotas inside
the kernel (``_key_interval``: each query row may read one interval of
key positions in a key tile), never materialised.

Grouped-query attention: ``k``/``v`` may have fewer heads than ``q``;
query head ``h`` reads K/V head ``h // group`` through the index map,
and the dkv kernel walks the group's query heads itself, so no repeated
K/V and no per-query-head dk/dv exists in HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
# flags of one (q tile, k tile) pair in a kernel's walk
_FIRST, _LAST, _PARTIAL = 1, 2, 4


class BlockDiffusionMask(NamedTuple):
    """The block-diffusion training mask over ``[xt ; x0]``: positions
    ``[0, seq_len)`` are the noisy copy, ``[seq_len, 2 seq_len)`` the
    clean one, both cut into blocks of ``block`` positions.  A noisy
    query reads its own noisy block and the clean blocks before it; a
    clean query reads the clean blocks up to and including its own."""
    seq_len: int
    block: int


def block_diffusion(seq_len: int, block: int) -> BlockDiffusionMask:
    if seq_len % block:
        raise ValueError(f"block {block} must divide seq_len {seq_len}")
    return BlockDiffusionMask(int(seq_len), int(block))


def _key_interval(mask, q_pos, k_clean, xp):
    """``(lo, hi)``: query position ``q_pos`` may read the keys at
    positions ``lo <= k < hi`` of a key tile (``k_clean``: whether that
    tile lies in the clean half; unused by ``causal``).  THE definition
    of the masks: the host's tile tables (``xp = numpy``) and the three
    kernels (``xp = jax.numpy``, on a row or a column of positions) all
    call it, so P is recomputed under the identical mask."""
    if mask == "causal":
        return xp.zeros_like(q_pos), q_pos + 1
    L, B = mask
    noisy = q_pos < L
    rel = xp.where(noisy, q_pos, q_pos - L)
    start = (rel // B) * B
    end = start + B
    # noisy keys: the query's own block, and only for a noisy query
    lo_n, hi_n = start, xp.where(noisy, end, start)
    # clean keys: blocks before a noisy query's, up to a clean query's
    lo_c, hi_c = xp.zeros_like(q_pos) + L, L + xp.where(noisy, start, end)
    return xp.where(k_clean, lo_c, lo_n), xp.where(k_clean, hi_c, hi_n)


def allowed_pairs(mask, t: int) -> np.ndarray:
    """The mask as a dense ``(t, t)`` boolean array (query, key): what
    the lax path of a layer hands dense attention, and what the tests
    hold the kernels against."""
    q_pos, k_pos = np.arange(t)[:, None], np.arange(t)[None, :]
    if mask is None:
        return np.ones((t, t), bool)
    lo, hi = _key_interval(mask, q_pos, k_pos >= _half(mask, t), np)
    return (k_pos >= lo) & (k_pos < hi)


@functools.lru_cache(maxsize=None)
def _tile_pairs(mask, t: int, block_q: int, block_k: int):
    """The (q tile, k tile) pairs that hold an allowed pair, in the
    order the forward and dq kernels walk them (by q tile) and in the
    order the dkv kernel does (by k tile), each as int32 arrays
    ``(q_idx, k_idx, flags)``."""
    nq, nk = t // block_q, t // block_k
    if mask is None:
        any_ = np.ones((nq, nk), bool)
        all_ = any_
    else:
        q_pos = np.arange(t, dtype=np.int64)
        any_ = np.zeros((nq, nk), bool)
        all_ = np.zeros((nq, nk), bool)
        half = _half(mask, t)
        for j in range(nk):
            ks, ke = j * block_k, (j + 1) * block_k
            lo, hi = _key_interval(mask, q_pos, ks >= half, np)
            some = (np.maximum(lo, ks) < np.minimum(hi, ke))
            whole = (lo <= ks) & (hi >= ke)
            any_[:, j] = some.reshape(nq, block_q).any(axis=1)
            all_[:, j] = whole.reshape(nq, block_q).all(axis=1)
    if not any_.any(axis=1).all():
        raise ValueError("a query tile with no key to read")

    def walk(by_q: bool):
        grid = any_ if by_q else any_.T
        outer, inner = np.nonzero(grid)
        flags = np.zeros(len(outer), np.int32)
        flags[np.r_[True, outer[1:] != outer[:-1]]] |= _FIRST
        flags[np.r_[outer[1:] != outer[:-1], True]] |= _LAST
        qi, ki = (outer, inner) if by_q else (inner, outer)
        flags[~all_[qi, ki]] |= _PARTIAL
        return (qi.astype(np.int32), ki.astype(np.int32), flags)

    return walk(True), walk(False)


def _masked(s, mask, mask_all, flags, q_pos, k_pos, k_clean):
    """Logits tile ``s`` under the mask, evaluated only on a tile that
    holds a pair that is not allowed; ``mask_all`` (static: most of the
    walk's tiles do, as under ``causal`` at two tiles a side) evaluates
    it on every tile and saves the branch (0.3 ms of 5.4 a forward and
    backward at the GPT cell's shapes: my chip run, PR 27)."""
    if mask is None:
        return s

    def apply(s):
        lo, hi = _key_interval(mask, q_pos, k_clean, jnp)
        return jnp.where((k_pos >= lo) & (k_pos < hi), s, NEG)

    if mask_all:
        return apply(s)
    return jax.lax.cond((flags & _PARTIAL) != 0, apply, lambda s: s, s)


def _mostly_partial(mask, t: int, block_q: int, block_k: int) -> bool:
    if mask is None:
        return False
    flags = _tile_pairs(mask, t, block_q, block_k)[0][2]
    return bool(2 * ((flags & _PARTIAL) != 0).sum() >= len(flags))


def _positions(start, n: int, axis: int):
    """``n`` consecutive positions from ``start`` as a column
    (``axis=0``) or a row."""
    shape = (n, 1) if axis == 0 else (1, n)
    return start + jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _half(mask, t: int) -> int:
    return mask.seq_len if isinstance(mask, BlockDiffusionMask) else t


_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_LANES = 128


def _col_to_row(col):
    """(n, 1) -> (1, n) through a full-tile transpose (Mosaic has no
    transpose of a one-lane column); once a q tile, not once a pair."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, _LANES)).T[0:1, :]


def _row_to_col(row):
    n = row.shape[1]
    return jnp.broadcast_to(row, (_LANES, n)).T[:, 0:1]


def _flash_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref,
                  lse_ref, acc_ref, m_ref, l_ref, *, mask, mask_all: bool,
                  scale: float, block_q: int, block_k: int, half: int):
    """One (q tile, k tile) pair of the forward's online softmax."""
    p_id = pl.program_id(1)
    flags = fl_ref[p_id]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...] * scale                     # (bq, d), input dtype
    k_blk, v_blk = k_ref[...], v_ref[...]
    s = jax.lax.dot_general(q, k_blk, _NT,
                            preferred_element_type=jnp.float32)
    k_start = ki_ref[p_id] * block_k
    s = _masked(s, mask, mask_all, flags,
                _positions(qi_ref[p_id] * block_q, block_q, 0),
                _positions(k_start, block_k, 1), k_start >= half)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when((flags & _LAST) != 0)
    def _store():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lse leaves as a lane-dense (1, block_q) row: a (t, 1) float32
        # array is tiled to 128 lanes in HBM, 128 times its size
        lse_ref[...] = _col_to_row(m_ref[...] + jnp.log(l_safe))


def _flash_dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                     lse_ref, delta_ref, dq_ref, acc_ref, lse_col, delta_col,
                     *, mask, mask_all: bool, scale: float, block_q: int,
                     block_k: int, half: int):
    """dq for one q tile: walk its k tiles, recompute P from lse.  lse
    and delta enter as rows and are turned into columns once a tile."""
    p_id = pl.program_id(1)
    flags = fl_ref[p_id]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        lse_col[...] = _row_to_col(lse_ref[...])
        delta_col[...] = _row_to_col(delta_ref[...])

    # recompute logits EXACTLY as the forward did (same dtype for the
    # q*scale product), so exp(s - lse) reproduces the forward's P —
    # a higher-precision recompute would desynchronise from the saved
    # lse under bf16
    q = q_ref[...] * scale
    k_blk, v_blk, do = k_ref[...], v_ref[...], do_ref[...]
    s = jax.lax.dot_general(q, k_blk, _NT,
                            preferred_element_type=jnp.float32)
    k_start = ki_ref[p_id] * block_k
    s = _masked(s, mask, mask_all, flags,
                _positions(qi_ref[p_id] * block_q, block_q, 0),
                _positions(k_start, block_k, 1), k_start >= half)
    p = jnp.exp(s - lse_col[...])                       # (bq, bk)
    dp = jax.lax.dot_general(do, v_blk, _NT,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col[...])
    acc_ref[...] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                            preferred_element_type=jnp.float32)

    @pl.when((flags & _LAST) != 0)
    def _store():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      mask, mask_all: bool, scale: float, block_q: int,
                      block_k: int, half: int):
    """dk/dv for one K/V head's k tile: the grid walks the tile's q
    tiles and, innermost, the query heads that share the K/V head,
    accumulating into VMEM scratch (TPU pallas runs the grid in order
    on a core) and writing the tile once.  The logits are held
    TRANSPOSED, (block_k, block_q), so lse and delta enter as lane-dense
    rows and every product is a plain or an ``a @ b.T`` one."""
    p_id, g = pl.program_id(1), pl.program_id(2)
    flags = fl_ref[p_id]

    @pl.when(((flags & _FIRST) != 0) & (g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_blk, v_blk, do = k_ref[...], v_ref[...], do_ref[...]
    # same-dtype q*scale as the forward (see dq kernel note)
    q = q_ref[...] * scale
    s_t = jax.lax.dot_general(k_blk, q, _NT,
                              preferred_element_type=jnp.float32)
    k_start = ki_ref[p_id] * block_k
    s_t = _masked(s_t, mask, mask_all, flags,
                  _positions(qi_ref[p_id] * block_q, block_q, 1),
                  _positions(k_start, block_k, 0), k_start >= half)
    p_t = jnp.exp(s_t - lse_ref[...])                   # (bk, bq)
    dv_acc[...] += jnp.dot(p_t.astype(do.dtype), do,
                           preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do, _NT,
                               preferred_element_type=jnp.float32)
    ds_t = p_t * (dp_t - delta_ref[...])
    # dk = Σ ds_ijᵀ (scale·q_i): q enters pre-scaled, so the scale is
    # already in the accumulation
    dk_acc[...] += jnp.dot(ds_t.astype(q.dtype), q,
                           preferred_element_type=jnp.float32)

    @pl.when(((flags & _LAST) != 0) & (g == pl.num_programs(2) - 1))
    def _store():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _resolve_blocks(t: int, block_q: int, block_k: int, mask=None):
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    if t % block_q != 0 or t % block_k != 0:
        raise ValueError(
            f"seq len {t} must divide block sizes ({block_q}, {block_k})")
    if isinstance(mask, BlockDiffusionMask):
        L = mask.seq_len
        if t != 2 * L:
            raise ValueError(
                f"block_diffusion({L}, ...) masks {2 * L} positions, "
                f"got {t}")
        block_q, block_k = min(block_q, L), min(block_k, L)
        if L % block_q or L % block_k:
            raise ValueError(
                f"seq_len {L} must divide block sizes "
                f"({block_q}, {block_k})")
    return block_q, block_k


def _tables(walk):
    return tuple(jnp.asarray(a) for a in walk)


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _by_q_specs(block_q: int, block_k: int, d: int, group: int):
    """Block specs of a walk by q tile (grid: heads, pairs): a q-sized
    tile, a K/V tile of the head's K/V head, a lane-dense row."""
    return (pl.BlockSpec((None, block_q, d),
                         lambda i, p, qi, ki, fl: (i, qi[p], 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda i, p, qi, ki, fl: (i // group, ki[p], 0)),
            pl.BlockSpec((None, 1, block_q),
                         lambda i, p, qi, ki, fl: (i, 0, qi[p])))


def _flash_fwd_impl(q, k, v, cfg):
    mask, scale, block_q, block_k, interpret = cfg
    b, h, t, d = q.shape
    group = h // k.shape[1]
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(-1, t, d)
    vf = v.reshape(-1, t, d)
    by_q, _ = _tile_pairs(mask, t, block_q, block_k)
    kernel = functools.partial(
        _flash_kernel, mask=mask, scale=scale, block_q=block_q,
        block_k=block_k, half=_half(mask, t),
        mask_all=_mostly_partial(mask, t, block_q, block_k))
    q_spec, kv_spec, row_spec = _by_q_specs(block_q, block_k, d, group)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, len(by_q[0])),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=(q_spec, row_spec),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*_tables(by_q), qf, kf, vf)
    return out.reshape(b, h, t, d), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg):
    out, _ = _flash_fwd_impl(q, k, v, cfg)
    return out


def _flash_vjp_fwd(q, k, v, cfg):
    out, lse = _flash_fwd_impl(q, k, v, cfg)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(cfg, res, dout):
    mask, scale, block_q, block_k, interpret = cfg
    q, k, v, out, lse = res
    b, h, t, d = q.shape
    h_kv = k.shape[1]
    group = h // h_kv
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h_kv, t, d)
    vf = v.reshape(b * h_kv, t, d)
    dof = dout.reshape(b * h, t, d)
    of = out.reshape(b * h, t, d)
    # D_i = rowsum(dO_i ∘ O_i) — cheap elementwise, computed by XLA
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]                  # (bh, 1, t)
    by_q, by_k = _tile_pairs(mask, t, block_q, block_k)
    static = dict(mask=mask, scale=scale, block_q=block_q, block_k=block_k,
                  half=_half(mask, t),
                  mask_all=_mostly_partial(mask, t, block_q, block_k))

    q_spec, kv_spec, row_spec = _by_q_specs(block_q, block_k, d, group)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **static),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, len(by_q[0])),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec,
                      row_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32),
                            pltpu.VMEM((block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention_dq",
    )(*_tables(by_q), qf, kf, vf, dof, lse, delta)

    # grid (K/V heads, pairs by k tile, query heads of the group): the
    # dk/dv tile of one K/V head stays in scratch while its q tiles and
    # the group's query heads go by
    qg_spec = pl.BlockSpec(
        (None, block_q, d),
        lambda i, p, g, qi, ki, fl: (i * group + g, qi[p], 0))
    kg_spec = pl.BlockSpec((None, block_k, d),
                           lambda i, p, g, qi, ki, fl: (i, ki[p], 0))
    rowg_spec = pl.BlockSpec(
        (None, 1, block_q),
        lambda i, p, g, qi, ki, fl: (i * group + g, 0, qi[p]))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct((b * h_kv, t, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, t, d), v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h_kv, len(by_k[0]), group),
            in_specs=[qg_spec, kg_spec, kg_spec, qg_spec, rowg_spec,
                      rowg_spec],
            out_specs=(kg_spec, kg_spec),
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "arbitrary",
                                         "arbitrary"),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*_tables(by_k), qf, kf, vf, dof, lse, delta)

    return (dq.reshape(b, h, t, d), dk.reshape(b, h_kv, t, d),
            dv.reshape(b, h_kv, t, d))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False,
                    mask: Optional[BlockDiffusionMask] = None):
    """q: (B, H, T, D); k, v: (B, H_kv, T, D) with ``H_kv`` dividing
    ``H`` (query head ``h`` reads K/V head ``h // (H / H_kv)``)
    -> (B, H, T, D).  ``causal`` or ``mask=block_diffusion(L, B)``
    (``T = 2 L``) restrict what a query reads.  Differentiable (flash
    backward kernels)."""
    b, h, t, d = q.shape
    if k.shape != v.shape or k.shape[0] != b or k.shape[2:] != (t, d) \
            or h % k.shape[1]:
        raise ValueError(
            f"k/v {k.shape}/{v.shape} do not fit q {q.shape}")
    if causal and mask is not None:
        raise ValueError("causal and mask exclude each other")
    if scale is None:
        scale = d ** -0.5
    what = "causal" if causal else mask
    block_q, block_k = _resolve_blocks(t, block_q, block_k, what)
    return _flash(q, k, v, (what, scale, block_q, block_k, interpret))

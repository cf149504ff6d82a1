"""Pallas flash-attention kernels for TPU — forward AND backward.

Part of the fused kernel suite (ops/fused.py holds the elementwise /
reduction half — fused optimizer update, bias→GeLU, LayerNorm→act —
and the shared ``pallas_supported()`` capability probe that gates all
Pallas routing).  Single-chip long-context attention: O(T·Tb) VMEM
instead of the O(T²) logits matrix XLA materialises for plain
attention.  Pairs with parallel/ring_attention.py (across-chip SP):
ring handles the inter-chip blocks, this kernel is what each chip
should run on its local block.

``flash_attention_token_major`` (and ``flash_attention``, the same for
callers that hold head-major arrays) is differentiable: the forward
kernel's results carry a ``custom_vjp`` whose backward is the standard
flash-attention backward (recompute the probability blocks from the
forward's saved log-sum-exp, then ``dv = PᵀdO``, ``ds = P∘(dOVᵀ - D)``,
``dq = dsK``, ``dk = dsᵀQ``), so the same memory bound holds in
training.  It has two forms, chosen by the operands' shapes alone
(``_fits_resident``; ``fused_kernel_builds_total{kernel=
"flash_attention_backward",path="one_pass"|"two_pass"}`` says which a
program got), whose arithmetic a (tile pair, head) is ONE function
(``_key_side``):

* **one pass** (``flash_attention_bwd``), wherever a query tile's WHOLE
  dq and its K/V tile's whole dk and dv fit ``_RESIDENT_VMEM`` (float32
  accumulators and two buffers of each output block: 8,192 positions of
  128 lanes in bfloat16 do, 24 MiB).  The grid is (batch, K/V lane
  tile, query tile of its group, walk), the walk ``_tile_pairs``'
  by-k-tile list: for each key tile its query tiles.  Sᵀ, the mask, Pᵀ,
  dPᵀ and dSᵀ are formed ONCE a (tile pair, head) and feed all three
  gradients: five products.  dq accumulates in float32 scratch addressed
  by the query tile's rows and leaves once a walk; dk and dv accumulate
  by the key tile's rows over the walks of ALL the group's query tiles
  and leave once a K/V tile, so no per-query-head dk/dv exists in HBM.
  ``D`` = rowsum(dO∘O) is formed before the kernel, by XLA.
* **two passes** (``flash_attention_dq`` + ``_dkv``) past that length
  (16,384 positions at 128 lanes): the dq kernel walks by query tile
  (and forms ``D``), the dkv kernel by key tile with the group's query
  heads innermost; each forms S, P, dP and dS for itself, seven
  products.

Layout: the operands lie where a projection wrote them, (B, T, H·D),
and a head is a BLOCK OF THE LAST DIMENSION, picked by the index map:
no head axis is moved in front of the positions, on the way in or out.
A head that is a lane multiple is one block; heads narrower than the
128 lanes share a block, ``128 // D`` of them (``_heads_per_tile``),
and one grid step forms each head's logits and products from the shared
tile (every lane but the head's zeroed on one operand of each product,
so the other heads' lanes add exact zeros), with its own ``m``, ``l``,
``lse`` and ``delta``; the tile's output is written once, lane-dense.
q, k and v may be ONE array, the fused projection's result: the three
are then block offsets into it.

Grid: (batch, lane tiles of heads, pairs).  Every operand enters
through the grid, one (block_q, width) or (block_k, width) tile at a
time, so no sequence length is capped by VMEM.  The pairs axis walks a
STATIC list of (q tile, k tile) pairs, worked out on the host from the
mask's description and handed to the kernels as scalar-prefetch tables:
a tile with no allowed pair is not in the list, so it costs neither a
grid step nor a DMA, in the forward and the backward kernels alike; a tile
every pair of which is allowed skips the mask's arithmetic.  The mask
itself is evaluated from iotas inside the kernel (``_key_interval``:
each query row may read one interval of key positions in a key tile),
never materialised: ``causal``, ``sliding_window(W)`` (causal inside a
band of ``W`` keys) and ``block_diffusion(L, B)``.

Grouped-query attention: ``k``/``v`` may have fewer heads than ``q``;
query head ``h`` reads K/V head ``h // group`` through the index map,
and the backward kernels walk the group's query heads themselves, so no
repeated K/V and no per-query-head dk/dv exists in HBM.  K and V are operands
like any other: they may be another layer's.

The differential pair (``differential=True``): 64-wide heads in pairs, a
pair a lane tile, fewer K/V pairs than query pairs.  Each head's logits
come from its own half of the q and K tiles as above, and its map is
applied to the pair's WHOLE V tile, so a tile's output is two tiles wide
(one a head) and V is neither copied nor cut.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.compile.engine import engine_jit
from analytics_zoo_tpu.ops.fused import count_build, keep_result

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


class SlidingWindowMask(NamedTuple):
    """Causal within a window: query ``i`` reads the ``window`` keys
    ``i - window + 1 .. i``."""
    window: int


def sliding_window(window: int) -> SlidingWindowMask:
    if window < 1:
        raise ValueError(f"a window of {window} keys")
    return SlidingWindowMask(int(window))


def _key_interval(mask, q_pos, k_clean, xp):
    """``(lo, hi)``: query position ``q_pos`` may read the keys at
    positions ``lo <= k < hi`` of a key tile (``k_clean``: whether that
    tile lies in the clean half; unused by ``causal``).  THE definition
    of the masks: the host's tile tables (``xp = numpy``) and every
    kernel (``xp = jax.numpy``, on a row or a column of positions) call
    it, so P is recomputed under the identical mask."""
    if mask == "causal":
        return xp.zeros_like(q_pos), q_pos + 1
    if isinstance(mask, SlidingWindowMask):
        return xp.maximum(q_pos - (mask.window - 1), 0), q_pos + 1
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
    order the one-pass backward and the dkv kernel do (by k tile), each
    as int32 arrays ``(q_idx, k_idx, flags)``."""
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
    _gauge_tiles(mask, int(any_.sum()), _causal_tiles(t, block_q, block_k))

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


def _causal_tiles(t: int, block_q: int, block_k: int) -> int:
    """Tiles the plain causal mask walks at these tile sizes."""
    q_last = np.arange(t // block_q) * block_q + block_q - 1
    return int(np.sum(q_last // block_k + 1))


def _mask_name(mask) -> str:
    if mask is None or isinstance(mask, str):
        return mask or "none"
    return {BlockDiffusionMask: "block_diffusion",
            SlidingWindowMask: "sliding_window"}[type(mask)]


def _gauge_tiles(mask, walked: int, causal: int) -> None:
    """How many (q tile, k tile) pairs a batch element and head walk
    under the mask whose tables were just built, beside what the causal
    mask walks over the same tiles."""
    from analytics_zoo_tpu.observability import get_registry
    gauge = get_registry().gauge(
        "flash_attention_tiles",
        "tile pairs of the newest tile tables built for a mask",
        labels=("mask", "which"))
    name = _mask_name(mask)
    gauge.labels(name, "walked").set(walked)
    gauge.labels(name, "causal").set(causal)


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
# VMEM the one-pass backward may hold for what stays resident over a
# walk: the float32 accumulators of one query tile's WHOLE dq and of its
# K/V tile's whole dk and dv, and the two buffers of each of the three
# output blocks (24 MiB of it at 8,192 positions of 128 lanes in
# bfloat16).  A longer sequence takes the dq and dkv kernels.
_RESIDENT_VMEM = 32 << 20
# What a kernel may use unasked on the v5e, and what the tiles and the
# intermediates of a tile pair take of it at block 512: the one-pass
# kernel asks for this much beside what it keeps resident and no more,
# because XLA keeps arrays between operations in the VMEM that no kernel
# claims (a limit of 64 MiB on the GPT cell's backward, which keeps
# under 1 MiB resident, cost the step 6.8 ms in copies and slower
# fusions: my chip runs, PR 36).
_SCOPED_VMEM = 16 << 20


def _col_to_row(col):
    """(n, 1) -> (1, n) through a full-tile transpose (Mosaic has no
    transpose of a one-lane column); once a q tile, not once a pair."""
    n = col.shape[0]
    return jnp.broadcast_to(col, (n, _LANES)).T[0:1, :]


def _row_to_col(row):
    n = row.shape[1]
    return jnp.broadcast_to(row, (_LANES, n)).T[:, 0:1]


def _heads_per_tile(h: int, h_kv: int, d: int,
                    differential: bool = False) -> int:
    """How many heads one lane tile of the token-major operands holds:
    1 where a head is a lane multiple; ``128 // d`` consecutive heads
    where a narrower head divides the 128 lanes, every query head has
    its own K/V head and the tiles come out whole; 0 where neither
    holds (no lane-aligned block picks such a head: Mosaic refuses it,
    and interpret mode runs it at one head a block).

    ``differential``: the heads come in pairs, a pair of query heads
    reads a PAIR of K/V heads, first on first and second on second, and
    a pair is what a tile holds: 2 where two heads fill the 128 lanes
    (fewer K/V pairs than query pairs are then fewer K/V TILES, found by
    the index map as a wider head's K/V head is), else 0."""
    if differential:
        return 2 if 2 * d == _LANES and h % 2 == 0 and h_kv % 2 == 0 else 0
    if d % _LANES == 0:
        return 1
    per = _LANES // d
    if _LANES % d == 0 and h_kv == h and h % per == 0:
        return per
    return 0


def _head_lanes(j: int, per: int, *xs):
    """The operands ``xs``, each (rows, per x d), with the lanes of
    every head but the tile's ``j``-th zeroed: one operand of each
    product is cut so, and the other heads' lanes add exact zeros to
    it."""
    if per == 1:
        return xs
    d = xs[0].shape[1] // per
    lane = jax.lax.broadcasted_iota(jnp.int32, xs[0].shape, 1)
    own = (lane >= j * d) & (lane < (j + 1) * d)
    return tuple(jnp.where(own, x, jnp.zeros_like(x)) for x in xs)


def _own(j: int, width: int):
    """The lanes of head ``j``'s map in a differential tile's output."""
    return slice(j * width, (j + 1) * width)


def _spread(cols, width: int):
    """Per-head (n, 1) columns -> (n, width), head ``j``'s lanes holding
    ``cols[j]`` (one head: the column itself, which broadcasts)."""
    out = cols[0]
    if len(cols) > 1:
        d = width // len(cols)
        out = jnp.broadcast_to(out, (out.shape[0], width))
        lane = jax.lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for j in range(1, len(cols)):
            out = jnp.where(lane >= j * d, cols[j], out)
    return out


def _flash_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref,
                  lse_ref, acc_ref, m_ref, l_ref, *, mask, mask_all: bool,
                  scale: float, block_q: int, block_k: int, half: int):
    """One (q tile, k tile) pair of the forward's online softmax, for
    the heads of one lane tile: each head its own logits, ``m`` and
    ``l``; the tile's accumulator is updated and written whole."""
    p_id = pl.program_id(2)
    flags = fl_ref[p_id]
    per, width = m_ref.shape[0], q_ref.shape[1]
    # the differential pair: each head's map over the tile's WHOLE V,
    # side by side in an accumulator two tiles wide
    differential = acc_ref.shape[1] != width

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...] * scale                     # (bq, width), input dtype
    k_blk, v_blk = k_ref[...], v_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 0)
    k_pos = _positions(k_start, block_k, 1)
    corr, pv = [], None
    for j in range(per):
        k_j, v_j = _head_lanes(j, per, k_blk, v_blk)
        s = jax.lax.dot_general(q, k_j, _NT,
                                preferred_element_type=jnp.float32)
        s = _masked(s, mask, mask_all, flags, q_pos, k_pos, k_start >= half)
        m = m_ref[j]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr.append(jnp.exp(m - m_new))
        p = jnp.exp(s - m_new)
        l_ref[j] = l_ref[j] * corr[j] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[j] = m_new
        pv_j = jnp.dot(p.astype(v_blk.dtype),
                       v_blk if differential else v_j,
                       preferred_element_type=jnp.float32)
        if differential:
            own = _own(j, width)
            acc_ref[:, own] = acc_ref[:, own] * corr[j] + pv_j
        else:
            pv = pv_j if pv is None else pv + pv_j
    if not differential:
        acc_ref[...] = acc_ref[...] * _spread(corr, width) + pv

    @pl.when((flags & _LAST) != 0)
    def _store():
        l_safe = [jnp.maximum(l_ref[j], 1e-30) for j in range(per)]
        if differential:
            for j in range(per):
                own = _own(j, width)
                o_ref[:, own] = (acc_ref[:, own] / l_safe[j]
                                 ).astype(o_ref.dtype)
        else:
            o_ref[...] = (acc_ref[...] / _spread(l_safe, width)
                          ).astype(o_ref.dtype)
        # lse leaves as lane-dense (1, block_q) rows: a (t, 1) float32
        # array is tiled to 128 lanes in HBM, 128 times its size
        for j in range(per):
            lse_ref[j] = _col_to_row(m_ref[j] + jnp.log(l_safe[j]))


def _flash_dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                     o_ref, lse_ref, dq_ref, delta_ref, acc_ref, lse_col,
                     delta_col, *, mask, mask_all: bool, scale: float,
                     block_q: int, block_k: int, half: int):
    """dq for one q tile of one lane tile's heads: walk its k tiles,
    recompute each head's P from its lse.  On the tile's first pair it
    forms D_i = rowsum(dO_i ∘ O_i) over each head's lanes, keeps it as a
    column beside lse's (which enters as a row) and writes it as a
    lane-dense row for the dkv kernel."""
    p_id = pl.program_id(2)
    flags = fl_ref[p_id]
    per, width = lse_col.shape[0], q_ref.shape[1]
    differential = do_ref.shape[1] != width

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        do_o = (do_ref[...].astype(jnp.float32)
                * o_ref[...].astype(jnp.float32))
        for j in range(per):
            lse_col[j] = _row_to_col(lse_ref[j])
            own = do_o[:, _own(j, width)] if differential \
                else _head_lanes(j, per, do_o)[0]
            delta_col[j] = jnp.sum(own, axis=1, keepdims=True)
            delta_ref[j] = _col_to_row(delta_col[j])

    # recompute logits EXACTLY as the forward did (same dtype for the
    # q*scale product), so exp(s - lse) reproduces the forward's P —
    # a higher-precision recompute would desynchronise from the saved
    # lse under bf16
    q = q_ref[...] * scale
    k_blk, v_blk, do = k_ref[...], v_ref[...], do_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 0)
    k_pos = _positions(k_start, block_k, 1)
    dq = None
    for j in range(per):
        k_j, v_j = _head_lanes(j, per, k_blk, v_blk)
        s = jax.lax.dot_general(q, k_j, _NT,
                                preferred_element_type=jnp.float32)
        s = _masked(s, mask, mask_all, flags, q_pos, k_pos, k_start >= half)
        p = jnp.exp(s - lse_col[j])                         # (bq, bk)
        if differential:
            dp = jax.lax.dot_general(do[:, _own(j, width)], v_blk, _NT,
                                     preferred_element_type=jnp.float32)
        else:
            dp = jax.lax.dot_general(do, v_j, _NT,
                                     preferred_element_type=jnp.float32)
        ds = p * (dp - delta_col[j])
        dq_j = jnp.dot(ds.astype(k_blk.dtype), k_j,
                       preferred_element_type=jnp.float32)
        dq = dq_j if dq is None else dq + dq_j
    acc_ref[...] += dq

    @pl.when((flags & _LAST) != 0)
    def _store():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _key_side(j: int, per: int, q, k_blk, v_blk, do, lse, delta, masked):
    """Head ``j`` of one tile pair with the logits held TRANSPOSED,
    ``(block_k, block_q)``, so that ``lse`` and ``delta`` enter as
    lane-dense rows and every product is a plain or an ``a @ b.T`` one:
    forms P^T, dP^T and dS^T and returns dS^T (cast for its products)
    with the head's dk and dv.  THE backward arithmetic of a (pair,
    head): the dkv kernel and the one-pass kernel both call it.  ``q``
    enters pre-scaled in the input dtype, as the forward formed it (so
    ``exp(s - lse)`` reproduces the forward's P: a higher-precision
    recompute would desynchronise from the saved lse under bf16), and so
    the scale is already in dk's accumulation."""
    width = q.shape[1]
    if do.shape[1] != width:             # the differential pair
        q_j, do_j = _head_lanes(j, per, q)[0], do[:, _own(j, width)]
    else:
        q_j, do_j = _head_lanes(j, per, q, do)
    s_t = masked(jax.lax.dot_general(k_blk, q_j, _NT,
                                     preferred_element_type=jnp.float32))
    p_t = jnp.exp(s_t - lse)                                # (bk, bq)
    dv_j = jnp.dot(p_t.astype(do.dtype), do_j,
                   preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v_blk, do_j, _NT,
                               preferred_element_type=jnp.float32)
    ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
    dk_j = jnp.dot(ds_t, q_j, preferred_element_type=jnp.float32)
    return ds_t, dk_j, dv_j


def _flash_dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                      mask, mask_all: bool, scale: float, block_q: int,
                      block_k: int, half: int):
    """dk/dv for one k tile of one lane tile's K/V heads (the two-pass
    form): the grid walks the tile's q tiles and, innermost, the query
    heads that share the K/V head, accumulating into VMEM scratch (TPU
    pallas runs the grid in order on a core) and writing the tile
    once."""
    p_id, g = pl.program_id(2), pl.program_id(3)
    flags = fl_ref[p_id]

    @pl.when(((flags & _FIRST) != 0) & (g == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_blk, v_blk, do = k_ref[...], v_ref[...], do_ref[...]
    q = q_ref[...] * scale
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 1)
    k_pos = _positions(k_start, block_k, 0)

    def masked(s_t):
        return _masked(s_t, mask, mask_all, flags, q_pos, k_pos,
                       k_start >= half)

    per = lse_ref.shape[0]
    dk = dv = None
    for j in range(per):
        _, dk_j, dv_j = _key_side(j, per, q, k_blk, v_blk, do, lse_ref[j],
                                  delta_ref[j], masked)
        dk, dv = (dk_j, dv_j) if dk is None else (dk + dk_j, dv + dv_j)
    dk_acc[...] += dk
    dv_acc[...] += dv

    @pl.when(((flags & _LAST) != 0) & (g == pl.num_programs(3) - 1))
    def _store():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                      dk_acc, dv_acc, *, mask, mask_all: bool, scale: float,
                      block_q: int, block_k: int, half: int):
    """All three gradients in one pass (grid: batch, K/V lane tile,
    query tile of its group, the by-k walk): P, dP and dS are formed
    ONCE a (tile pair, head), transposed (``_key_side``, the dkv
    kernel's), and feed dk and dv of the key tile's rows and, through
    dS's transpose, dq of the q tile's rows.  The query tile's whole dq
    stays in ``dq_acc`` over its walk and leaves on the walk's last
    entry; the K/V tile's whole dk and dv stay in ``dk_acc`` /
    ``dv_acc`` over the walks of ALL the group's query tiles and leave
    once, so rows no pair reaches leave as the zeros they were set to."""
    g, p_id = pl.program_id(2), pl.program_id(3)
    flags = fl_ref[p_id]
    first, last = p_id == 0, p_id == pl.num_programs(3) - 1

    @pl.when(first)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first & (g == 0))
    def _init_shared():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    k_blk, v_blk, do = k_ref[...], v_ref[...], do_ref[...]
    q = q_ref[...] * scale
    q_start = pl.multiple_of(qi_ref[p_id] * block_q, block_q)
    k_start = pl.multiple_of(ki_ref[p_id] * block_k, block_k)
    q_pos = _positions(q_start, block_q, 1)
    k_pos = _positions(k_start, block_k, 0)

    def masked(s_t):
        return _masked(s_t, mask, mask_all, flags, q_pos, k_pos,
                       k_start >= half)

    per = lse_ref.shape[0]
    dq = dk = dv = None
    for j in range(per):
        ds_t, dk_j, dv_j = _key_side(j, per, q, k_blk, v_blk, do,
                                     lse_ref[j], delta_ref[j], masked)
        dq_j = jnp.dot(ds_t.T, _head_lanes(j, per, k_blk)[0],
                       preferred_element_type=jnp.float32)  # (bq, width)
        dq, dk, dv = (dq_j, dk_j, dv_j) if dq is None \
            else (dq + dq_j, dk + dk_j, dv + dv_j)
    dq_acc[pl.ds(q_start, block_q), :] += dq
    k_rows = pl.ds(k_start, block_k)
    dk_acc[k_rows, :] += dk
    dv_acc[k_rows, :] += dv

    @pl.when(last)
    def _store():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when(last & (g == pl.num_programs(2) - 1))
    def _store_shared():
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


class _Heads(NamedTuple):
    """How the kernels find a head in the token-major operands: ``h``
    query heads on ``h_kv`` K/V heads of ``d``, ``per`` of them to a
    block of ``width`` lanes, and where q, k and v start in the last
    dimension of their operand, in such blocks (all 0 unless the three
    are ONE array, the fused projection's result)."""
    h: int
    h_kv: int
    d: int
    per: int
    width: int
    offsets: tuple
    # lanes of a tile's OUTPUT (and of its cotangent): the tile's own,
    # or one tile a head under the differential pair
    out_width: int

    @property
    def group(self):
        return self.h // self.h_kv

    @property
    def tiles(self):
        return self.h // self.per


def _heads(ops, h: int, h_kv: int, differential: bool = False) -> _Heads:
    fused = len(ops) == 1
    d = ops[0].shape[-1] // (h + 2 * h_kv if fused else h)
    per = _heads_per_tile(h, h_kv, d, differential) or 1
    offsets = (0, h // per, (h + h_kv) // per) if fused else (0, 0, 0)
    width = per * d
    return _Heads(h, h_kv, d, per, width, offsets,
                  per * width if differential else width)


def _statics(cfg, t: int):
    mask, scale, block_q, block_k = cfg[:4]
    return dict(mask=mask, scale=scale, block_q=block_q, block_k=block_k,
                half=_half(mask, t),
                mask_all=_mostly_partial(mask, t, block_q, block_k))


def _by_q_specs(block_q: int, block_k: int, hd: _Heads):
    """Block specs of a walk by q tile (grid: batch, lane tiles, pairs):
    a q-sized tile of the heads' lanes, one of their output's (or its
    cotangent's) lanes, a K/V tile of their K/V head's, the heads'
    lane-dense rows."""
    def q_tile(off=0, width=hd.width):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b, i, p, qi, ki, fl: (b, qi[p], off + i))

    o_tile = functools.partial(q_tile, 0, hd.out_width)

    def kv_tile(off):
        return pl.BlockSpec(
            (None, block_k, hd.width),
            lambda b, i, p, qi, ki, fl: (b, ki[p], off + i // hd.group))

    rows = pl.BlockSpec(
        (hd.per, 1, block_q),
        lambda b, i, p, qi, ki, fl: (b * hd.tiles + i, 0, qi[p]))
    return q_tile, o_tile, kv_tile, rows


def _flash_fwd_impl(ops, cfg):
    mask, scale, block_q, block_k, interpret, h, h_kv, differential = cfg
    hd = _heads(ops, h, h_kv, differential)
    q, k, v = ops if len(ops) == 3 else ops * 3
    b, t = q.shape[:2]
    by_q, _ = _tile_pairs(mask, t, block_q, block_k)
    q_tile, o_tile, kv_tile, rows = _by_q_specs(block_q, block_k, hd)
    q_off, k_off, v_off = hd.offsets
    return pl.pallas_call(
        functools.partial(_flash_kernel, **_statics(cfg, t)),
        out_shape=(jax.ShapeDtypeStruct((b, t, hd.tiles * hd.out_width),
                                        q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hd.tiles, len(by_q[0])),
            in_specs=[q_tile(q_off), kv_tile(k_off), kv_tile(v_off)],
            out_specs=(o_tile(), rows),
            scratch_shapes=[pltpu.VMEM((block_q, hd.out_width),
                                       jnp.float32),
                            pltpu.VMEM((hd.per, block_q, 1), jnp.float32),
                            pltpu.VMEM((hd.per, block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*_tables(by_q), q, k, v)


# The forward and the backward pass are programs of their own: a model
# of twelve blocks traces each kernel body once, not twelve times, and
# not again each time the train step is traced (three times a process:
# 11 of the GPT cell's 45 s of set-up; my chip runs, PR 30).  XLA
# inlines the calls, so nothing changes in what it compiles.
_forward = engine_jit(_flash_fwd_impl, static_argnums=(1,),
                      key_hint="flash_attention_forward")


# What a recomputed layer keeps of a call (``fused.keep_result``): the
# forward kernel's two results, all its backward kernels read of it.
KEPT_RESULTS = ("flash_attention_out", "flash_attention_lse")


def _flash(ops, cfg):
    """The token-major core.  ``ops``: ``(q, k, v)`` as (B, T, H·D) and
    (B, T, H_kv·D) arrays, or ``(qkv,)``, the three side by side in the
    last dimension of one; -> (B, T, H·D).

    The forward kernel is an ordinary call, outside the ``custom_vjp``
    and on operands cut off from differentiation, and its results carry
    ``KEPT_RESULTS``' names: a ``jax.checkpoint`` whose policy saves
    those names keeps them and runs the forward kernel once, where a
    forward rule inside the ``custom_vjp`` is run again with everything
    else (a name given inside a forward rule is not kept: JAX 0.9).
    Under no such policy a name is the identity.  ``_attach`` hangs the
    backward kernels on the result."""
    out, lse = map(keep_result, _forward(jax.lax.stop_gradient(ops), cfg),
                   KEPT_RESULTS)
    return _attach(ops, out, lse, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attach(ops, out, lse, cfg):
    """``out``, the forward kernel's result on ``ops``, as a function of
    ``ops``: the backward kernels on ``(ops, out, lse)`` give ``ops``
    its cotangent; ``out`` and ``lse`` themselves get none (they were
    formed from stopped operands, so none could flow on)."""
    return out


def _attach_fwd(ops, out, lse, cfg):
    return out, (ops, out, lse)


def _resident_bytes(t: int, width: int, itemsize: int) -> int:
    """What the one-pass backward keeps in VMEM over a walk: a query
    tile's whole dq and its K/V tile's whole dk and dv over ``t``
    positions of ``width`` lanes, each a float32 accumulator and the two
    buffers of its output block.  The group's size does not enter: its
    query tiles go by one at a time."""
    return 3 * t * width * (4 + 2 * itemsize)


def _fits_resident(t: int, width: int, itemsize: int) -> bool:
    """The one-pass backward's condition."""
    return _resident_bytes(t, width, itemsize) <= _RESIDENT_VMEM


def _attach_bwd(cfg, res, dout):
    ops = res[0]
    hd = _heads(ops, *cfg[5:])
    one_pass = _fits_resident(ops[0].shape[1], hd.width,
                              ops[0].dtype.itemsize)
    count_build("flash_attention_backward",
                "one_pass" if one_pass else "two_pass")
    return (*_backward(res, dout, cfg, one_pass), None, None)


def _side_by_side(parts):
    """``concatenate`` along the last axis, written as a sum of pads:
    that XLA fuses into whatever reads the result (the projection's
    three gradient products), where a concatenate of three kernels'
    results is built in place, one copy a part (compiled for the v5e,
    PR 30)."""
    widths = [a.shape[-1] for a in parts]
    zero = jnp.zeros((), parts[0].dtype)
    lead = [(0, 0, 0)] * (parts[0].ndim - 1)
    return functools.reduce(jnp.add, (
        jax.lax.pad(a, zero, lead + [(sum(widths[:i]),
                                      sum(widths[i + 1:]), 0)])
        for i, a in enumerate(parts)))


def _flash_bwd_impl(res, dout, cfg, one_pass: bool):
    """The kernels' three gradients, in either form, as the cotangent
    of ``ops``."""
    dq, dk, dv = (_one_pass if one_pass else _two_pass)(res, dout, cfg)
    if len(res[0]) == 1:
        return (_side_by_side([dq, dk, dv]),),
    return (dq, dk, dv),


def _by_k_specs(block_q: int, block_k: int, hd: _Heads, walk_first: bool):
    """Block specs of a walk by k tile.  Grid: batch, K/V lane tile,
    then the walk and the query tiles of the K/V tile's group, in that
    order if ``walk_first`` (the dkv kernel's) and the other way round
    if not (the one-pass kernel's).  A walk entry's q-sized tile of the
    query tile's lanes (``width``: of its output's or cotangent's) and
    its K/V tile, or with ``whole`` positions EVERY position of those
    lanes as one block that stays while the walk goes by; the query
    tile's lane-dense rows."""
    def at(index):
        if walk_first:
            return lambda b, i, p, g, qi, ki, fl: index(b, i, g, p, qi, ki)
        return lambda b, i, g, p, qi, ki, fl: index(b, i, g, p, qi, ki)

    def q_tile(off=0, width=hd.width, whole=0):
        return pl.BlockSpec(
            (None, whole or block_q, width),
            at(lambda b, i, g, p, qi, ki: (b, 0 if whole else qi[p],
                                           off + i * hd.group + g)))

    def k_tile(off=0, whole=0):
        return pl.BlockSpec(
            (None, whole or block_k, hd.width),
            at(lambda b, i, g, p, qi, ki: (b, 0 if whole else ki[p],
                                           off + i)))

    rows = pl.BlockSpec(
        (hd.per, 1, block_q),
        at(lambda b, i, g, p, qi, ki: (b * hd.tiles + i * hd.group + g,
                                       0, qi[p])))
    return q_tile, k_tile, rows


def _delta_rows(dout, out, h: int):
    """rowsum(dO * O) a head (its ``lanes`` of ``out``: 128 under the
    differential pair) as lane-dense ``(B·H, 1, T)`` rows beside lse's,
    for a backward that walks by k tile: it meets a q tile once a key
    tile, so no pair is a q tile's first.  Summed as ONE product with
    the heads' lanes at float32's precision, a fusion over dO and O; as
    a reduction over a head's lanes XLA writes the float32 product out
    and relayouts it (1 ms a layer at 8,192 positions on the v5e)."""
    b, t, width = out.shape
    lanes = width // h
    own = jnp.arange(width) // lanes == jnp.arange(h)[:, None]
    return jnp.einsum(
        "hl,btl->bht", own.astype(jnp.float32),
        dout.astype(jnp.float32) * out.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).reshape(b * h, 1, t)


def _one_pass(res, dout, cfg):
    mask, scale, block_q, block_k, interpret, h, h_kv, differential = cfg
    ops, out, lse = res
    hd = _heads(ops, h, h_kv, differential)
    q, k, v = ops if len(ops) == 3 else ops * 3
    b, t = q.shape[:2]
    _, by_k = _tile_pairs(mask, t, block_q, block_k)
    q_off, k_off, v_off = hd.offsets
    delta = _delta_rows(dout, out, h)

    q_tile, k_tile, rows = _by_k_specs(block_q, block_k, hd, False)
    kv_shape = (b, t, h_kv * hd.d)
    resident = pltpu.VMEM((t, hd.width), jnp.float32)
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, **_statics(cfg, t)),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * hd.d), q.dtype),
                   jax.ShapeDtypeStruct(kv_shape, k.dtype),
                   jax.ShapeDtypeStruct(kv_shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hd.tiles // hd.group, hd.group, len(by_k[0])),
            in_specs=[q_tile(q_off), k_tile(k_off), k_tile(v_off),
                      q_tile(0, hd.out_width), rows, rows],
            out_specs=(q_tile(whole=t), k_tile(whole=t), k_tile(whole=t)),
            scratch_shapes=[resident, resident, resident]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_SCOPED_VMEM + _resident_bytes(
                t, hd.width, q.dtype.itemsize)),
        interpret=interpret,
        name="flash_attention_bwd",
    )(*_tables(by_k), q, k, v, dout, lse, delta)


def _two_pass(res, dout, cfg):
    mask, scale, block_q, block_k, interpret, h, h_kv, differential = cfg
    ops, out, lse = res
    hd = _heads(ops, h, h_kv, differential)
    q, k, v = ops if len(ops) == 3 else ops * 3
    b, t = q.shape[:2]
    by_q, by_k = _tile_pairs(mask, t, block_q, block_k)
    static = _statics(cfg, t)
    q_off, k_off, v_off = hd.offsets

    q_tile, o_tile, kv_tile, rows = _by_q_specs(block_q, block_k, hd)
    # the dq kernel also forms delta, (b·h, 1, t) rows as lse, for the
    # dkv kernel: each reads dO once, and O is read here alone
    dq, delta = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * hd.d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hd.tiles, len(by_q[0])),
            in_specs=[q_tile(q_off), kv_tile(k_off), kv_tile(v_off),
                      o_tile(), o_tile(), rows],
            out_specs=(q_tile(), rows),
            scratch_shapes=[pltpu.VMEM((block_q, hd.width), jnp.float32),
                            pltpu.VMEM((hd.per, block_q, 1), jnp.float32),
                            pltpu.VMEM((hd.per, block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name="flash_attention_dq",
    )(*_tables(by_q), q, k, v, dout, out, lse)

    # grid (batch, K/V lane tiles, pairs by k tile, query heads of the
    # group): the dk/dv tile of one K/V head stays in scratch while its
    # q tiles and the group's query heads go by
    qg_tile, kg_tile, rowg = _by_k_specs(block_q, block_k, hd, True)
    kv_shape = (b, t, h_kv * hd.d)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct(kv_shape, k.dtype),
                   jax.ShapeDtypeStruct(kv_shape, v.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hd.tiles // hd.group, len(by_k[0]), hd.group),
            in_specs=[qg_tile(q_off), kg_tile(k_off), kg_tile(v_off),
                      qg_tile(0, hd.out_width), rowg, rowg],
            out_specs=(kg_tile(), kg_tile()),
            scratch_shapes=[pltpu.VMEM((block_k, hd.width), jnp.float32),
                            pltpu.VMEM((block_k, hd.width), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary", "arbitrary"),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*_tables(by_k), q, k, v, dout, lse, delta)
    return dq, dk, dv


_backward = engine_jit(_flash_bwd_impl, static_argnums=(2, 3),
                       key_hint="flash_attention_backward")
_attach.defvjp(_attach_fwd, _attach_bwd)


def flash_attention_token_major(q, k=None, v=None, *, n_head: int,
                                n_kv_head: Optional[int] = None,
                                causal: bool = False,
                                scale: Optional[float] = None,
                                block_q: int = 256, block_k: int = 256,
                                interpret: bool = False, mask=None,
                                differential: bool = False):
    """Attention over heads where a projection wrote them.  q:
    (B, T, H·D); k, v: (B, T, H_kv·D) with ``H_kv`` dividing ``H``
    (query head ``h`` reads K/V head ``h // (H / H_kv)``) -> (B, T, H·D).
    Or ``q`` alone, the fused projection's (B, T, (H + 2 H_kv)·D) result
    with q, k and v side by side (``n_kv_head``: ``H_kv``, ``H`` if not
    given): the kernels read the three out of the one array.  A head is
    a block of the last dimension: heads narrower than the 128 lanes
    share a block (``_heads_per_tile``).  ``causal``,
    ``mask=sliding_window(W)`` or ``mask=block_diffusion(L, B)``
    (``T = 2 L``) restrict what a query reads.  k and v may be another
    layer's: their cotangents are this call's share of the sum over
    their readers.  Differentiable (flash backward kernels).

    ``differential``: the heads come in pairs, query heads ``2j`` and
    ``2j + 1`` on K/V heads ``2i`` and ``2i + 1`` with ``i = j // (H /
    H_kv)``, and each query head's map (over its own K head) is applied
    to the pair's two V heads side by side, ``V_i = [v_2i, v_2i+1]``:
    -> (B, T, H·2D), head ``h``'s ``softmax(q_h k^T) V_i`` at lanes
    ``[2 D h, 2 D (h + 1))``.  No second copy of V and no repeated K/V
    is built; what is done with the two maps of a pair is the caller's."""
    b, t, last = q.shape
    if k is None:
        h_kv = n_kv_head or n_head
        ops, d = (q,), last // (n_head + 2 * h_kv)
        fits = last == (n_head + 2 * h_kv) * d
    else:
        ops, d = (q, k, v), last // n_head
        h_kv = k.shape[-1] // max(d, 1)
        fits = last == n_head * d and k.shape == v.shape == (b, t, h_kv * d)
    if not (fits and h_kv and n_head % h_kv == 0):
        raise ValueError(
            f"{n_head} heads on operands "
            f"{[tuple(a.shape) for a in ops]} do not fit")
    if causal and mask is not None:
        raise ValueError("causal and mask exclude each other")
    if differential and not _heads_per_tile(n_head, h_kv, d, True):
        raise ValueError(
            f"the differential pair on the kernels takes heads of "
            f"{_LANES // 2}, in pairs; got {n_head} on {h_kv} of {d}")
    if scale is None:
        scale = d ** -0.5
    what = "causal" if causal else mask
    block_q, block_k = _resolve_blocks(t, block_q, block_k, what)
    return _flash(ops, (what, scale, block_q, block_k, interpret,
                        n_head, h_kv, bool(differential)))


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False, mask=None):
    """``flash_attention_token_major`` for callers that hold head-major
    arrays.  q: (B, H, T, D); k, v: (B, H_kv, T, D) -> (B, H, T, D)."""
    b, h, t, d = q.shape
    if k.shape != v.shape or k.shape[0] != b or k.shape[2:] != (t, d) \
            or h % k.shape[1]:
        raise ValueError(
            f"k/v {k.shape}/{v.shape} do not fit q {q.shape}")
    out = flash_attention_token_major(
        *(jnp.moveaxis(a, 1, 2).reshape(b, t, -1) for a in (q, k, v)),
        n_head=h, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, mask=mask)
    return jnp.moveaxis(out.reshape(b, t, h, d), 2, 1)

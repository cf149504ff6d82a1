"""Flash attention for latent (low-rank K/V) attention — forward and
backward Pallas kernels beside ``ops/pallas_attention.py``, whose tile
walk, masks and conventions they share.

Latent attention (DeepSeek-V2/V3's MLA) forms its keys and values from
one low-rank latent a position, and its logit is a sum of two products::

    s_h = (q_nope_h . k_nope_h  +  q_pe_h . k_pe) * scale

with ``k_nope_h`` (and ``v_h``) a head's own, out of the latent's
up-projection, and ``k_pe`` ONE rotary key a position, shared by every
head.  The kernels take the four operands where the projections wrote
them and form the logit as that sum, tile by tile:

* ``q``     (B, T, >= H·128): the query projection's result, the
  ``q_nope`` heads first (what lies after them is not read);
* ``q_pe``  (B, T, H·64): the queries' rotary parts, rotated;
* ``kv``    (B, T, 2·H·128): the up-projection's result, the ``k_nope``
  heads and then the ``v`` heads (two block offsets into one array);
* ``k_pe``  (B, T, 64): the shared rotary key, rotated.

No (T, H, 192) key is built and ``k_pe`` is not repeated a head.  A grid
step works TWO heads: their 64-wide rotary parts fill one 128-lane tile
(as two 64-wide heads do in ``pallas_attention``), each product with it
taken with the other head's lanes zeroed; ``k_pe`` enters as a 128-lane
tile holding it twice, so the masked product is head ``j``'s own (the
wrapper lays the two copies side by side and autodiff adds the halves of
the cotangent: ``dk_pe`` is the sum over every query head).

The backward pass has two forms, chosen by the sequence length alone
(``_fits_resident``; ``fused_kernel_builds_total{kernel=
"flash_attention_latent_backward",path="one_pass"|"two_pass"}`` says
which a program got):

* **one pass** (``flash_attention_latent_bwd``), wherever one head
  pair's WHOLE ``dq_nope`` and ``dq_pe`` fit ``_RESIDENT_VMEM`` (8,192
  positions in bfloat16 do).  The grid is (batch, head pair, walk), the
  walk ``_tile_pairs``' by-k-tile list: for each key tile its query
  tiles.  P, dP and dS are formed ONCE a (tile pair, head), transposed;
  ``dv``, ``dk_nope`` and the pair's share of ``dk_pe`` accumulate over
  the key tile's query tiles and leave on its last, while ``dq``
  accumulates in float32 scratch addressed by the query tile's rows and
  leaves once, after the pair's last key tile.  ``delta`` = rowsum(dO O)
  is formed before the kernel, and the head pairs' shares of ``dk_pe``
  are summed after it, both by XLA.
* **two passes** (``flash_attention_latent_dq`` + ``_dkv``) past that
  length: the dq kernel walks by query tile (and forms ``delta``), the
  dkv kernel walks, for each key tile, every pair of heads over the key
  tile's query tiles: ``dk_nope`` and ``dv`` leave once a (key tile,
  head pair), ``dk_pe`` once a key tile, summed in VMEM over all heads.
  Each forms P, dP and dS for itself.

Heads of (128 | 64 | 128) only; other sizes take
``latent_attention_dense``, the lax form, which is also what runs off
the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.compile.engine import engine_jit
from analytics_zoo_tpu.ops.fused import count_build, keep_result
from analytics_zoo_tpu.ops.pallas_attention import (
    _FIRST, _LAST, _NT, KEPT_RESULTS, NEG, _col_to_row, _compiler_params,
    _delta_rows, _head_lanes, _masked, _positions, _resolve_blocks,
    _row_to_col, _side_by_side, _statics, _tables, _tile_pairs,
    allowed_pairs)

# lanes of a q_nope / k_nope / v head, and of a rotary part; two heads
# to a grid step
NOPE, ROPE, PER = 128, 64, 2
# flags of the dkv walk, beside the pair's own: the first and the last
# entry of a key tile (over all its head pairs)
_KFIRST, _KLAST = 8, 16
# VMEM the one-pass backward may hold for a head pair's WHOLE dq_nope
# and dq_pe while the pair's key tiles go by: their float32 accumulators
# and the two buffers of each output block (24 MiB of it at 8,192
# positions in bfloat16).  The kernel's own limit is twice this: the
# tiles and the intermediates at block 512 take the other half, under
# the v5e's 128 MiB.  A longer sequence takes the dq and dkv kernels.
_RESIDENT_VMEM = 32 << 20


def kernel_fits(n_head: int, nope_dim: int, rope_dim: int,
                v_dim: int) -> bool:
    """Whether heads of these sizes are what the kernels take."""
    return (nope_dim, rope_dim, v_dim) == (NOPE, ROPE, NOPE) \
        and n_head % PER == 0


def _head(j: int):
    """The lanes of the step's ``j``-th head in a 256-lane tile."""
    return slice(j * NOPE, (j + 1) * NOPE)


def _logits(q, q_pe, k, k_pe, j: int, transposed: bool = False):
    """Head ``j``'s logits tile: its own 128 lanes of q and k, and its
    half of the rotary tile against the shared key."""
    q_pe_j, = _head_lanes(j, PER, q_pe)
    a, b = (k[:, _head(j)], q[:, _head(j)]) if transposed \
        else (q[:, _head(j)], k[:, _head(j)])
    a_pe, b_pe = (k_pe, q_pe_j) if transposed else (q_pe_j, k_pe)
    return (jax.lax.dot_general(a, b, _NT,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(a_pe, b_pe, _NT,
                                  preferred_element_type=jnp.float32))


def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, qpe_ref, k_ref, v_ref,
                kpe_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *, mask,
                mask_all: bool, scale: float, block_q: int, block_k: int,
                half: int):
    """One (q tile, k tile) pair of the online softmax for two heads."""
    p_id = pl.program_id(2)
    flags = fl_ref[p_id]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q, q_pe = q_ref[...] * scale, qpe_ref[...] * scale
    k, v, k_pe = k_ref[...], v_ref[...], kpe_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 0)
    k_pos = _positions(k_start, block_k, 1)
    for j in range(PER):
        s = _masked(_logits(q, q_pe, k, k_pe, j), mask, mask_all, flags,
                    q_pos, k_pos, k_start >= half)
        m = m_ref[j]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[j] = l_ref[j] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[j] = m_new
        acc_ref[:, _head(j)] = acc_ref[:, _head(j)] * corr + jnp.dot(
            p.astype(v.dtype), v[:, _head(j)],
            preferred_element_type=jnp.float32)

    @pl.when((flags & _LAST) != 0)
    def _store():
        for j in range(PER):
            l_safe = jnp.maximum(l_ref[j], 1e-30)
            o_ref[:, _head(j)] = (acc_ref[:, _head(j)] / l_safe
                                  ).astype(o_ref.dtype)
            lse_ref[j] = _col_to_row(m_ref[j] + jnp.log(l_safe))


def _dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, qpe_ref, k_ref, v_ref,
               kpe_ref, do_ref, o_ref, lse_ref, dq_ref, dqpe_ref, delta_ref,
               acc_ref, accpe_ref, lse_col, delta_col, *, mask,
               mask_all: bool, scale: float, block_q: int, block_k: int,
               half: int):
    """dq_nope and dq_pe for one q tile of two heads (and ``delta`` for
    the dkv kernel, as ``pallas_attention``'s dq kernel forms it)."""
    p_id = pl.program_id(2)
    flags = fl_ref[p_id]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        accpe_ref[...] = jnp.zeros_like(accpe_ref)
        do_o = (do_ref[...].astype(jnp.float32)
                * o_ref[...].astype(jnp.float32))
        for j in range(PER):
            lse_col[j] = _row_to_col(lse_ref[j])
            delta_col[j] = jnp.sum(do_o[:, _head(j)], axis=1, keepdims=True)
            delta_ref[j] = _col_to_row(delta_col[j])

    # logits exactly as the forward formed them (see pallas_attention)
    q, q_pe = q_ref[...] * scale, qpe_ref[...] * scale
    k, v, k_pe, do = k_ref[...], v_ref[...], kpe_ref[...], do_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 0)
    k_pos = _positions(k_start, block_k, 1)
    dq_pe = None
    for j in range(PER):
        s = _masked(_logits(q, q_pe, k, k_pe, j), mask, mask_all, flags,
                    q_pos, k_pos, k_start >= half)
        p = jnp.exp(s - lse_col[j])
        dp = jax.lax.dot_general(do[:, _head(j)], v[:, _head(j)], _NT,
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_col[j])).astype(k.dtype)
        acc_ref[:, _head(j)] += jnp.dot(ds, k[:, _head(j)],
                                        preferred_element_type=jnp.float32)
        # ds k_pe lands in both halves of the tile: head j keeps its own
        own, = _head_lanes(j, PER, jnp.dot(
            ds, k_pe, preferred_element_type=jnp.float32))
        dq_pe = own if dq_pe is None else dq_pe + own
    accpe_ref[...] += dq_pe

    @pl.when((flags & _LAST) != 0)
    def _store():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)
        dqpe_ref[...] = (accpe_ref[...] * scale).astype(dqpe_ref.dtype)


def _key_side(j: int, q, q_pe, k, v, k_pe, do, lse, delta, masked, dk_acc,
              dv_acc):
    """Head ``j`` of one tile pair with the logits held transposed,
    ``(block_k, block_q)``, so that ``lse`` and ``delta`` enter as
    lane-dense rows: forms P^T, dP^T and dS^T, adds the head's dv and
    dk_nope to the accumulators and returns dS^T (cast for its
    products) and the head's share of dk_pe.  ``q`` and ``q_pe`` enter
    pre-scaled, so the scale is in dk's accumulation."""
    s_t = masked(_logits(q, q_pe, k, k_pe, j, transposed=True))
    p_t = jnp.exp(s_t - lse)                                # (bk, bq)
    do_j = do[:, _head(j)]
    dv_acc[:, _head(j)] += jnp.dot(p_t.astype(do.dtype), do_j,
                                   preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(v[:, _head(j)], do_j, _NT,
                               preferred_element_type=jnp.float32)
    ds_t = (p_t * (dp_t - delta)).astype(q.dtype)
    dk_acc[:, _head(j)] += jnp.dot(ds_t, q[:, _head(j)],
                                   preferred_element_type=jnp.float32)
    # head j's rotary lanes alone: its share lands in half j of the
    # tile, and the two halves are the two copies' cotangents
    q_pe_j, = _head_lanes(j, PER, q_pe)
    return ds_t, jnp.dot(ds_t, q_pe_j, preferred_element_type=jnp.float32)


def _dkv_kernel(qi_ref, ki_ref, gi_ref, fl_ref, q_ref, qpe_ref, k_ref, v_ref,
                kpe_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                dkpe_ref, dk_acc, dv_acc, dkpe_acc, *, mask, mask_all: bool,
                scale: float, block_q: int, block_k: int, half: int):
    """dk_nope, dv and dk_pe for one k tile: the grid walks the tile's
    head pairs and, innermost, each pair's q tiles (TPU pallas runs the
    grid in order on a core).  dk_nope and dv leave once a head pair;
    dk_pe, the sum over EVERY head, stays in scratch until the key
    tile's last entry.  Logits are held transposed, as in
    ``pallas_attention``'s dkv kernel."""
    p_id = pl.program_id(1)
    flags = fl_ref[p_id]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when((flags & _KFIRST) != 0)
    def _init_shared():
        dkpe_acc[...] = jnp.zeros_like(dkpe_acc)

    q, q_pe = q_ref[...] * scale, qpe_ref[...] * scale
    k, v, k_pe, do = k_ref[...], v_ref[...], kpe_ref[...], do_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_pos = _positions(qi_ref[p_id] * block_q, block_q, 1)
    k_pos = _positions(k_start, block_k, 0)

    def masked(s_t):
        return _masked(s_t, mask, mask_all, flags, q_pos, k_pos,
                       k_start >= half)

    dk_pe = None
    for j in range(PER):
        _, dk_pe_j = _key_side(j, q, q_pe, k, v, k_pe, do, lse_ref[j],
                               delta_ref[j], masked, dk_acc, dv_acc)
        dk_pe = dk_pe_j if dk_pe is None else dk_pe + dk_pe_j
    dkpe_acc[...] += dk_pe

    @pl.when((flags & _LAST) != 0)
    def _store():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((flags & _KLAST) != 0)
    def _store_shared():
        dkpe_ref[...] = dkpe_acc[...].astype(dkpe_ref.dtype)


def _bwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, qpe_ref, k_ref, v_ref,
                kpe_ref, do_ref, lse_ref, delta_ref, dq_ref, dqpe_ref,
                dk_ref, dv_ref, dkpe_ref, dq_acc, dqpe_acc, dk_acc, dv_acc,
                dkpe_acc, *, mask, mask_all: bool, scale: float,
                block_q: int, block_k: int, half: int):
    """All five gradients in one pass over a head pair's walk (key tile
    by key tile, each key tile's q tiles innermost): P, dP and dS are
    formed once a (pair, head), transposed (``_key_side``, the dkv
    kernel's), and feed dv, dk_nope and dk_pe of the key tile and,
    through dS's transpose, dq_nope and dq_pe of the q tile's rows.  The pair's whole
    dq stays in ``dq_acc`` / ``dqpe_acc`` over the walk and leaves on
    its last entry; dk_nope, dv and this PAIR's share of dk_pe leave on
    a key tile's last."""
    p_id = pl.program_id(2)
    flags = fl_ref[p_id]

    @pl.when(p_id == 0)
    def _init_resident():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        dqpe_acc[...] = jnp.zeros_like(dqpe_acc)

    @pl.when((flags & _FIRST) != 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        dkpe_acc[...] = jnp.zeros_like(dkpe_acc)

    q, q_pe = q_ref[...] * scale, qpe_ref[...] * scale
    k, v, k_pe, do = k_ref[...], v_ref[...], kpe_ref[...], do_ref[...]
    k_start = ki_ref[p_id] * block_k
    q_start = pl.multiple_of(qi_ref[p_id] * block_q, block_q)
    q_pos = _positions(q_start, block_q, 1)
    k_pos = _positions(k_start, block_k, 0)
    rows = pl.ds(q_start, block_q)

    def masked(s_t):
        return _masked(s_t, mask, mask_all, flags, q_pos, k_pos,
                       k_start >= half)

    dk_pe = dq_pe = None
    for j in range(PER):
        ds_t, dk_pe_j = _key_side(j, q, q_pe, k, v, k_pe, do, lse_ref[j],
                                  delta_ref[j], masked, dk_acc, dv_acc)
        dk_pe = dk_pe_j if dk_pe is None else dk_pe + dk_pe_j
        ds = ds_t.T                                         # (bq, bk)
        dq_acc[rows, _head(j)] += jnp.dot(
            ds, k[:, _head(j)], preferred_element_type=jnp.float32)
        # ds k_pe lands in both halves of the tile: head j keeps its own
        own, = _head_lanes(j, PER, jnp.dot(
            ds, k_pe, preferred_element_type=jnp.float32))
        dq_pe = own if dq_pe is None else dq_pe + own
    dkpe_acc[...] += dk_pe
    dqpe_acc[rows, :] += dq_pe

    @pl.when((flags & _LAST) != 0)
    def _store():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        dkpe_ref[...] = dkpe_acc[...]

    @pl.when(p_id == pl.num_programs(2) - 1)
    def _store_resident():
        dq_ref[...] = (dq_acc[...] * scale).astype(dq_ref.dtype)
        dqpe_ref[...] = (dqpe_acc[...] * scale).astype(dqpe_ref.dtype)


@functools.lru_cache(maxsize=None)
def _dkv_walk(mask, t: int, block_q: int, block_k: int, tiles: int):
    """The dkv kernel's walk, ``(q_idx, k_idx, head_pair, flags)``: the
    by-k-tile walk of ``_tile_pairs`` with each key tile's run of q
    tiles gone through once a head pair."""
    _, (qi, ki, fl) = _tile_pairs(mask, t, block_q, block_k)
    first = np.flatnonzero(fl & _FIRST)
    last = np.flatnonzero(fl & _LAST) + 1
    out = [[], [], [], []]
    for a, b in zip(first, last):
        for g in range(tiles):
            flags = fl[a:b].copy()
            if g == 0:
                flags[0] |= _KFIRST
            if g == tiles - 1:
                flags[-1] |= _KLAST
            for col, part in zip(out, (qi[a:b], ki[a:b],
                                       np.full(b - a, g, np.int32), flags)):
                col.append(part)
    return tuple(np.concatenate(col).astype(np.int32) for col in out)


def _pair_specs(block_q: int, block_k: int, tiles: int):
    """Block specs of grid ``(batch, head pair, walk entry)``: an
    entry's q tile, k tile (``off`` head pairs on: ``v``), shared
    rotary key and lane-dense ``lse`` / ``delta`` rows."""
    def q_tile(width):
        return pl.BlockSpec((None, block_q, width),
                            lambda b, i, p, qi, ki, fl: (b, qi[p], i))

    def k_tile(width, off=0):
        return pl.BlockSpec((None, block_k, width),
                            lambda b, i, p, qi, ki, fl: (b, ki[p], off + i))

    k_pe = pl.BlockSpec((None, block_k, PER * ROPE),
                        lambda b, i, p, qi, ki, fl: (b, ki[p], 0))
    rows = pl.BlockSpec((PER, 1, block_q),
                        lambda b, i, p, qi, ki, fl: (b * tiles + i, 0, qi[p]))
    return q_tile, k_tile, k_pe, rows


def _fwd_impl(ops, cfg):
    mask, scale, block_q, block_k, interpret, h = cfg
    q, q_pe, kv, k_pe = ops
    b, t = q.shape[:2]
    tiles = h // PER
    by_q, _ = _tile_pairs(mask, t, block_q, block_k)
    q_tile, k_tile, kpe_tile, rows = _pair_specs(block_q, block_k, tiles)
    wide, narrow = PER * NOPE, PER * ROPE
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **_statics(cfg, t)),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * NOPE), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, tiles, len(by_q[0])),
            in_specs=[q_tile(wide), q_tile(narrow), k_tile(wide),
                      k_tile(wide, tiles), kpe_tile],
            out_specs=(q_tile(wide), rows),
            scratch_shapes=[pltpu.VMEM((block_q, wide), jnp.float32),
                            pltpu.VMEM((PER, block_q, 1), jnp.float32),
                            pltpu.VMEM((PER, block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name="flash_attention_latent_fwd",
    )(*_tables(by_q), q, q_pe, kv, kv, k_pe)


# programs of their own, as pallas_attention's: a deep model traces each
# kernel body once a process
_forward = engine_jit(_fwd_impl, static_argnums=(1,),
                      key_hint="flash_attention_latent_forward")


def _latent(ops, cfg):
    """The core on ``(q, q_pe, kv, k_pe twice side by side)``.  The
    forward kernel is an ordinary call whose results carry
    ``pallas_attention.KEPT_RESULTS``' names, so that a recomputed
    layer's policy keeps them (see ``pallas_attention._flash``)."""
    out, lse = map(keep_result, _forward(jax.lax.stop_gradient(ops), cfg),
                   KEPT_RESULTS)
    return _attach(ops, out, lse, cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attach(ops, out, lse, cfg):
    return out


def _attach_fwd(ops, out, lse, cfg):
    return out, (ops, out, lse)


def _fits_resident(t: int, itemsize: int) -> bool:
    """Whether a head pair's whole dq_nope and dq_pe over ``t``
    positions (float32 accumulators, two buffers of each output block)
    stay inside ``_RESIDENT_VMEM``: the one-pass backward's condition."""
    return t * PER * (NOPE + ROPE) * (4 + 2 * itemsize) <= _RESIDENT_VMEM


def _attach_bwd(cfg, res, dout):
    q = res[0][0]
    one_pass = _fits_resident(q.shape[1], q.dtype.itemsize)
    count_build("flash_attention_latent_backward",
                "one_pass" if one_pass else "two_pass")
    return (_backward(res, dout, cfg, one_pass), None, None)


def _bwd_impl(res, dout, cfg, one_pass: bool):
    """The kernels' five gradients, in either form, as the cotangents
    of the four operands."""
    q, h = res[0][0], cfg[-1]
    dq, dq_pe, dk, dv, dk_pe = (_one_pass if one_pass else _two_pass)(
        res, dout, cfg)
    unread = q.shape[-1] - h * NOPE
    if unread:
        dq = jax.lax.pad(dq, jnp.zeros((), dq.dtype),
                         [(0, 0, 0), (0, 0, 0), (0, unread, 0)])
    return dq, dq_pe, _side_by_side([dk, dv]), dk_pe


def _one_pass(res, dout, cfg):
    mask, scale, block_q, block_k, interpret, h = cfg
    (q, q_pe, kv, k_pe), out, lse = res
    b, t = q.shape[:2]
    tiles = h // PER
    _, by_k = _tile_pairs(mask, t, block_q, block_k)
    wide, narrow = PER * NOPE, PER * ROPE
    delta = _delta_rows(dout, out, h)

    q_tile, k_tile, kpe_tile, rows = _pair_specs(block_q, block_k, tiles)

    def whole(width):
        return pl.BlockSpec((None, t, width),
                            lambda b, i, p, qi, ki, fl: (b, 0, i))

    dq, dq_pe, dk, dv, dk_pe = pl.pallas_call(
        functools.partial(_bwd_kernel, **_statics(cfg, t)),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * NOPE), q.dtype),
                   jax.ShapeDtypeStruct(q_pe.shape, q_pe.dtype),
                   jax.ShapeDtypeStruct((b, t, h * NOPE), kv.dtype),
                   jax.ShapeDtypeStruct((b, t, h * NOPE), kv.dtype),
                   jax.ShapeDtypeStruct((b, tiles, t, narrow),
                                        jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, tiles, len(by_k[0])),
            in_specs=[q_tile(wide), q_tile(narrow), k_tile(wide),
                      k_tile(wide, tiles), kpe_tile, q_tile(wide), rows,
                      rows],
            out_specs=(whole(wide), whole(narrow), k_tile(wide),
                       k_tile(wide),
                       pl.BlockSpec(
                           (None, None, block_k, narrow),
                           lambda b, i, p, qi, ki, fl: (b, i, ki[p], 0))),
            scratch_shapes=[pltpu.VMEM((t, wide), jnp.float32),
                            pltpu.VMEM((t, narrow), jnp.float32),
                            pltpu.VMEM((block_k, wide), jnp.float32),
                            pltpu.VMEM((block_k, wide), jnp.float32),
                            pltpu.VMEM((block_k, narrow), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=2 * _RESIDENT_VMEM),
        interpret=interpret,
        name="flash_attention_latent_bwd",
    )(*_tables(by_k), q, q_pe, kv, kv, k_pe, dout, lse, delta)
    # a head pair's share a key tile; the sum over the pairs is XLA's
    return dq, dq_pe, dk, dv, jnp.sum(dk_pe, axis=1).astype(k_pe.dtype)


def _two_pass(res, dout, cfg):
    mask, scale, block_q, block_k, interpret, h = cfg
    (q, q_pe, kv, k_pe), out, lse = res
    b, t = q.shape[:2]
    tiles = h // PER
    by_q, _ = _tile_pairs(mask, t, block_q, block_k)
    static = _statics(cfg, t)
    wide, narrow = PER * NOPE, PER * ROPE

    q_tile, k_tile, kpe_tile, rows = _pair_specs(block_q, block_k, tiles)
    dq, dq_pe, delta = pl.pallas_call(
        functools.partial(_dq_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * NOPE), q.dtype),
                   jax.ShapeDtypeStruct(q_pe.shape, q_pe.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, t), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, tiles, len(by_q[0])),
            in_specs=[q_tile(wide), q_tile(narrow), k_tile(wide),
                      k_tile(wide, tiles), kpe_tile, q_tile(wide),
                      q_tile(wide), rows],
            out_specs=(q_tile(wide), q_tile(narrow), rows),
            scratch_shapes=[pltpu.VMEM((block_q, wide), jnp.float32),
                            pltpu.VMEM((block_q, narrow), jnp.float32),
                            pltpu.VMEM((PER, block_q, 1), jnp.float32),
                            pltpu.VMEM((PER, block_q, 1), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name="flash_attention_latent_dq",
    )(*_tables(by_q), q, q_pe, kv, kv, k_pe, dout, out, lse)

    walk = _dkv_walk(mask, t, block_q, block_k, tiles)

    def qg_tile(width):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b, p, qi, ki, gi, fl: (b, qi[p], gi[p]))

    def kg_tile(width, off=0):
        return pl.BlockSpec(
            (None, block_k, width),
            lambda b, p, qi, ki, gi, fl: (b, ki[p], off + gi[p]))

    kpe_g = pl.BlockSpec((None, block_k, narrow),
                         lambda b, p, qi, ki, gi, fl: (b, ki[p], 0))
    rowg = pl.BlockSpec(
        (PER, 1, block_q),
        lambda b, p, qi, ki, gi, fl: (b * tiles + gi[p], 0, qi[p]))
    dk, dv, dk_pe = pl.pallas_call(
        functools.partial(_dkv_kernel, **static),
        out_shape=(jax.ShapeDtypeStruct((b, t, h * NOPE), kv.dtype),
                   jax.ShapeDtypeStruct((b, t, h * NOPE), kv.dtype),
                   jax.ShapeDtypeStruct(k_pe.shape, k_pe.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, len(walk[0])),
            in_specs=[qg_tile(wide), qg_tile(narrow), kg_tile(wide),
                      kg_tile(wide, tiles), kpe_g, qg_tile(wide), rowg,
                      rowg],
            out_specs=(kg_tile(wide), kg_tile(wide), kpe_g),
            scratch_shapes=[pltpu.VMEM((block_k, wide), jnp.float32),
                            pltpu.VMEM((block_k, wide), jnp.float32),
                            pltpu.VMEM((block_k, narrow), jnp.float32)]),
        compiler_params=_compiler_params("parallel", "arbitrary"),
        interpret=interpret,
        name="flash_attention_latent_dkv",
    )(*_tables(walk), q, q_pe, kv, kv, k_pe, dout, lse, delta)

    return dq, dq_pe, dk, dv, dk_pe


_backward = engine_jit(_bwd_impl, static_argnums=(2, 3),
                       key_hint="flash_attention_latent_backward")
_attach.defvjp(_attach_fwd, _attach_bwd)


def _check(q, q_pe, kv, k_pe, n_head, nope_dim, v_dim):
    b, t, _ = q.shape
    rope_dim = k_pe.shape[-1]
    if not (q.shape[-1] >= n_head * nope_dim
            and q_pe.shape == (b, t, n_head * rope_dim)
            and kv.shape == (b, t, n_head * (nope_dim + v_dim))
            and k_pe.shape == (b, t, rope_dim)):
        raise ValueError(
            f"{n_head} latent heads of ({nope_dim} | {rope_dim} | {v_dim}) "
            f"on operands {[tuple(a.shape) for a in (q, q_pe, kv, k_pe)]} "
            "do not fit")
    return rope_dim


def latent_flash_attention(q, q_pe, kv, k_pe, *, n_head: int,
                           causal: bool = False,
                           scale: Optional[float] = None,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool = False):
    """Latent attention on the kernels (operands: the module's
    docstring) -> (B, T, H·128), head ``h``'s ``softmax(s_h) v_h`` at
    lanes ``[128 h, 128 (h + 1))``.  ``scale`` defaults to
    ``192 ** -0.5``.
    Differentiable in all four operands; the cotangent of the columns of
    ``q`` that are not read is zero."""
    rope_dim = _check(q, q_pe, kv, k_pe, n_head, NOPE, NOPE)
    if not kernel_fits(n_head, NOPE, rope_dim, NOPE):
        raise ValueError(
            f"the latent kernels take an even number of heads of "
            f"({NOPE} | {ROPE} | {NOPE}); got {n_head} with a rotary part "
            f"of {rope_dim}")
    if scale is None:
        scale = (NOPE + ROPE) ** -0.5
    what = "causal" if causal else None
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k, what)
    # the shared key twice, a copy a head of the step: one 128-lane tile
    k_pe = jnp.concatenate([k_pe, k_pe], axis=-1)
    return _latent((q, q_pe, kv, k_pe),
                   (what, scale, block_q, block_k, interpret, n_head))


def latent_attention_dense(q, q_pe, kv, k_pe, *, n_head: int, nope_dim: int,
                           v_dim: int, causal: bool = False,
                           scale: Optional[float] = None):
    """The same attention in plain ``jax.numpy`` for heads of any size
    (softmax in float32): the lax path, and what the tests hold the
    kernels against.  -> (B, T, H·v_dim)."""
    rope_dim = _check(q, q_pe, kv, k_pe, n_head, nope_dim, v_dim)
    b, t, _ = q.shape
    h = n_head
    if scale is None:
        scale = (nope_dim + rope_dim) ** -0.5
    q_nope = q[..., :h * nope_dim].reshape(b, t, h, nope_dim)
    k_nope = kv[..., :h * nope_dim].reshape(b, t, h, nope_dim)
    v = kv[..., h * nope_dim:].reshape(b, t, h, v_dim)
    logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhr,bkr->bhqk", q_pe.reshape(b, t, h, rope_dim),
                           k_pe, preferred_element_type=jnp.float32)) * scale
    if causal:
        logits = jnp.where(jnp.asarray(allowed_pairs("causal", t)), logits,
                           NEG)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h * v_dim).astype(q.dtype)

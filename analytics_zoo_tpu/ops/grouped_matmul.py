"""Pallas grouped matrix product for TPU — forward and both backward
products.

What a dropless mixture-of-experts layer needs (layers/moe.py): the rows
routed to expert ``g`` times that expert's own weight matrix, for every
expert a chip holds, in one kernel, whatever the split of rows between
experts turns out to be at run time.

Layout.  The rows lie in a buffer of STATIC length, sorted by group, and
each group starts on a multiple of ``block_rows`` (``group_layout``
works the starts out from the group sizes of the step).  A row tile
therefore belongs to one group; which one is a scalar-prefetch table,
so the index maps fetch the right expert's weights.  Rows between a
group's end and the next start are padding and must be zero in ``lhs``;
the tiles after the last group are the TAIL (in the expert layer: the
assignments to experts this chip does not hold).  No kernel touches the
tail or the one tile an empty group keeps: their index maps stay on the
last tile in use, so they cost a grid step and neither DMA nor product,
and their output rows are never written — read them through a mask.

``rhs`` is taken as stored (float32 master weights) and cast to
``lhs.dtype`` in VMEM, once per group, so no cast copy of the experts
exists in HBM, and ``drhs`` leaves the kernel in ``rhs.dtype``,
accumulated in float32.  A group's whole matrix is one block where it
fits (8 MB): the grid then has one step a row tile, and the steps of the
tail, which do nothing, stay few (at a 256-wide block they were most of
the 75 kernels' time in the 128-expert cell: my chip run, PR 27).

The three kernels are ``grouped_matmul_fwd`` (``out = lhs @ rhs[g]``),
``grouped_matmul_dlhs`` (``dout @ rhs[g].T``) and ``grouped_matmul_drhs``
(``lhs_g.T @ dout_g`` per group).  Off the TPU (``fused._use_pallas``
false, no ``interpret``) the same products run through
``jax.lax.ragged_dot`` over the same layout.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FIRST, _ROWS = 1, 2      # tile flags: first of its group; group has rows
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


class GroupLayout(NamedTuple):
    """Where the groups lie in a row buffer (all int32 arrays).

    ``starts``/``sizes``: first row and row count of each group;
    ``padded``: rows each group occupies (a multiple of the row block,
    at least one block); ``tile_group``/``tile_flags``: the group a row
    tile belongs to and its ``_FIRST``/``_ROWS`` flags; ``n_active``:
    (1,) the number of tiles the groups occupy."""
    starts: jax.Array
    sizes: jax.Array
    padded: jax.Array
    tile_group: jax.Array
    tile_flags: jax.Array
    n_active: jax.Array


def buffer_rows(rows: int, groups: int, block_rows: int) -> int:
    """Static length of a buffer that holds ``rows`` rows in ``groups``
    block-aligned groups however they are split."""
    return -(-rows // block_rows) * block_rows + groups * block_rows


def group_layout(group_sizes, buffer_len: int,
                 block_rows: int) -> GroupLayout:
    """The layout of ``group_sizes`` (G,) rows in a buffer of
    ``buffer_len`` rows (a multiple of ``block_rows``)."""
    if buffer_len % block_rows:
        raise ValueError(
            f"buffer of {buffer_len} rows, row block {block_rows}")
    sizes = group_sizes.astype(jnp.int32)
    tiles = jnp.maximum(-(-sizes // block_rows), 1)
    ends = jnp.cumsum(tiles)
    n_tiles = buffer_len // block_rows
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(ends, tile, side="right").astype(jnp.int32),
        sizes.shape[0] - 1)
    first = tile == (ends - tiles)[group]
    flags = jnp.where(first, _FIRST, 0) | jnp.where(sizes[group] > 0,
                                                    _ROWS, 0)
    return GroupLayout((ends - tiles) * block_rows, sizes,
                       tiles * block_rows, group, flags.astype(jnp.int32),
                       ends[-1:].astype(jnp.int32))


_BLOCK_BYTES = 8 << 20


def _block(n: int, unit_bytes: int) -> int:
    """The widest block of an ``n``-wide dimension, each unit of which
    takes ``unit_bytes`` of VMEM, within ``_BLOCK_BYTES``."""
    for b in (n, 1024, 512, 256, 128):
        if n % b == 0 and b * unit_bytes <= _BLOCK_BYTES:
            return b
    return n


def _tile(i, n_active):
    """The row tile a grid step works on: its own while the groups
    last, the last one in use after that (no DMA for the tail)."""
    return jnp.maximum(jnp.minimum(i, n_active[0] - 1), 0)


def _in_use(i, flags_ref, n_active_ref):
    return (i < n_active_ref[0]) & ((flags_ref[i] & _ROWS) != 0)


def _cast_once(i, fl_ref, rhs_ref, w_ref):
    """The group's weight block in the rows' dtype, cast when the grid
    reaches the group's first tile and kept in scratch for the rest."""
    @pl.when((fl_ref[i] & _FIRST) != 0)
    def _():
        w_ref[...] = rhs_ref[...].astype(w_ref.dtype)


def _fwd_kernel(tg_ref, fl_ref, na_ref, lhs_ref, rhs_ref, out_ref, w_ref):
    i = pl.program_id(1)

    @pl.when(_in_use(i, fl_ref, na_ref))
    def _():
        _cast_once(i, fl_ref, rhs_ref, w_ref)
        out_ref[...] = jnp.dot(
            lhs_ref[...], w_ref[...],
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _dlhs_kernel(tg_ref, fl_ref, na_ref, dout_ref, rhs_ref, out_ref, w_ref):
    i = pl.program_id(1)

    @pl.when(_in_use(i, fl_ref, na_ref))
    def _():
        _cast_once(i, fl_ref, rhs_ref, w_ref)
        out_ref[...] = jax.lax.dot_general(
            dout_ref[...], w_ref[...], _NT,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _drhs_kernel(tg_ref, fl_ref, na_ref, lhs_ref, dout_ref, out_ref):
    """One row tile's share of its group's ``lhs.T @ dout`` block; the
    output block is revisited while the tiles of a group go by (TPU
    pallas runs the grid in order on a core)."""
    i = pl.program_id(2)
    active = i < na_ref[0]

    @pl.when(active & ((fl_ref[i] & _FIRST) != 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(_in_use(i, fl_ref, na_ref))
    def _():
        out_ref[...] += jax.lax.dot_general(
            lhs_ref[...], dout_ref[...], _TN,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)


def _call(kernel, name, grid, in_specs, out_spec, out_shape, semantics,
          interpret, layout, *operands, scratch=()):
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=list(scratch)),
        # two buffers of an 8 MB weight block, its cast copy and the row
        # tiles: over the 16 MB a kernel gets unasked
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=name,
    )(layout.tile_group, layout.tile_flags, layout.n_active, *operands)


def _fwd_pallas(lhs, rhs, layout, out_dtype, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    bm = m // layout.tile_group.shape[0]
    bn = _block(n, k * rhs.dtype.itemsize)
    # rows innermost: consecutive tiles of a group keep the weight block
    return _call(
        _fwd_kernel, "grouped_matmul_fwd", (n // bn, m // bm),
        [pl.BlockSpec((bm, k), lambda j, i, tg, fl, na: (_tile(i, na), 0)),
         pl.BlockSpec((None, k, bn),
                      lambda j, i, tg, fl, na: (tg[_tile(i, na)], 0, j))],
        pl.BlockSpec((bm, bn), lambda j, i, tg, fl, na: (_tile(i, na), j)),
        jax.ShapeDtypeStruct((m, n), out_dtype), ("parallel", "arbitrary"),
        interpret, layout, lhs, rhs,
        scratch=[pltpu.VMEM((k, bn), lhs.dtype)])


def _dlhs_pallas(dout, rhs, layout, out_dtype, interpret):
    m, n = dout.shape
    k = rhs.shape[1]
    bm = m // layout.tile_group.shape[0]
    bk = _block(k, n * rhs.dtype.itemsize)
    return _call(
        _dlhs_kernel, "grouped_matmul_dlhs", (k // bk, m // bm),
        [pl.BlockSpec((bm, n), lambda j, i, tg, fl, na: (_tile(i, na), 0)),
         pl.BlockSpec((None, bk, n),
                      lambda j, i, tg, fl, na: (tg[_tile(i, na)], j, 0))],
        pl.BlockSpec((bm, bk), lambda j, i, tg, fl, na: (_tile(i, na), j)),
        jax.ShapeDtypeStruct((m, k), out_dtype), ("parallel", "arbitrary"),
        interpret, layout, dout, rhs,
        scratch=[pltpu.VMEM((bk, n), dout.dtype)])


def _drhs_pallas(lhs, dout, layout, groups, out_dtype, interpret):
    m, k = lhs.shape
    n = dout.shape[1]
    bm = m // layout.tile_group.shape[0]
    bn = _block(n, 128 * jnp.dtype(out_dtype).itemsize)
    bk = _block(k, bn * jnp.dtype(out_dtype).itemsize)
    return _call(
        _drhs_kernel, "grouped_matmul_drhs", (k // bk, n // bn, m // bm),
        [pl.BlockSpec((bm, bk),
                      lambda a, b, i, tg, fl, na: (_tile(i, na), a)),
         pl.BlockSpec((bm, bn),
                      lambda a, b, i, tg, fl, na: (_tile(i, na), b))],
        pl.BlockSpec((None, bk, bn),
                     lambda a, b, i, tg, fl, na: (tg[_tile(i, na)], a, b)),
        jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        ("parallel", "parallel", "arbitrary"), interpret, layout, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _grouped(lhs, rhs, layout, out_dtype, interpret):
    return _fwd_pallas(lhs, rhs, layout, out_dtype, interpret)


def _grouped_fwd(lhs, rhs, layout, out_dtype, interpret):
    return (_fwd_pallas(lhs, rhs, layout, out_dtype, interpret),
            (lhs, rhs, layout))


def _grouped_bwd(out_dtype, interpret, res, dout):
    lhs, rhs, layout = res
    dout = dout.astype(lhs.dtype)
    dlhs = _dlhs_pallas(dout, rhs, layout, lhs.dtype, interpret)
    drhs = _drhs_pallas(lhs, dout, layout, rhs.shape[0], rhs.dtype,
                        interpret)
    return dlhs, drhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, layout: GroupLayout, *, out_dtype=None,
                   interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[g(r)]`` for the rows of every group of
    ``layout``: ``lhs`` (M, K) in the layout's buffer, ``rhs`` (G, K, N)
    -> (M, N) in ``out_dtype`` (default ``lhs.dtype``).  Padding rows
    must be zero in ``lhs`` (they then give zero rows and add nothing to
    ``drhs``); the rows of the tail and of an empty group's tile are NOT
    written: mask them where they are read.  Differentiable in ``lhs``
    and ``rhs``."""
    from analytics_zoo_tpu.ops import fused
    m, k = lhs.shape
    if rhs.ndim != 3 or rhs.shape[1] != k \
            or rhs.shape[0] != layout.sizes.shape[0] \
            or m % layout.tile_group.shape[0]:
        raise ValueError(
            f"lhs {lhs.shape}, rhs {rhs.shape} and a layout of "
            f"{layout.sizes.shape[0]} groups over "
            f"{layout.tile_group.shape[0]} tiles do not fit")
    out_dtype = jnp.dtype(out_dtype or lhs.dtype)
    if interpret or fused._use_pallas():
        fused.count_build("grouped_matmul", "pallas")
        return _grouped(lhs, rhs, layout, out_dtype, interpret)
    fused.count_build("grouped_matmul", "lax")
    # the same layout as ragged groups: each group with its padding,
    # then the tail, which ragged_dot leaves at zero
    return jax.lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), layout.padded,
        preferred_element_type=jnp.float32).astype(out_dtype)

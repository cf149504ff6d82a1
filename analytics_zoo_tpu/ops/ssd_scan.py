"""The chunked scan of a Mamba-2 layer (state-space duality), forward and
backward.

For every head ``h`` of ``P`` channels, whose ``N`` states are read and
written through ``B_t`` and ``C_t`` of its GROUP (head ``h`` of ``H``
reads group ``h // (H / G)``), over the positions ``t`` (``dt`` already
positive, ``A`` already negative)::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] B_t x_t[h]^T     (N, P)
    y_t[h] = C_t^T S_t[h]

The decay is ONE number a head and position, so a chunk of ``L``
positions has a matrix form (Dao & Gu 2024, arXiv:2405.21060, section 6):
with ``lam`` the running sum of ``dt A`` from the chunk's start and
``S`` the state before it::

    y     = (C B^T o exp(lam_l - lam_s)[l >= s] o dt_s) x + exp(lam) o C S
    S_end = exp(lam_end) S + (B o exp(lam_end - lam) dt)^T x

products on the MXU, with one state a head carried from chunk to chunk.
``lam`` is formed before the scan, by XLA (``chunk_cumsum``), and its
cotangent is summed back there.

Kernel layout: ``x`` and ``y`` stay where the projection wrote them,
(B, T, H·P); a grid step takes one chunk of one TILE of heads (up to
512 lanes of them), the chunks in order and the tiles innermost.
``C B^T`` is formed once a chunk and group, on the group's first tile,
and kept in VMEM scratch while its tiles go by.  Inside a tile the heads
go by 128-lane groups (two heads of 64 share one): a head's products
take the group's lanes with every other head's zeroed on one operand, as
flash attention's do.  ``dt`` and ``lam`` enter as lane-dense rows a head,
(B, H, 1, T).  The states of all tiles stay in VMEM scratch as (N, lanes)
blocks; the only states that reach HBM are those at each chunk's start,
which the forward pass writes for the backward pass.  The backward pass
walks the chunks last to first with the state's cotangent in scratch,
forms dx, ddt and dlam a tile, and dB and dC once a chunk and group
(``dG`` of the chunk's ``C B^T`` is summed over the group's heads in
scratch).

``ssd_scan`` routes by ``fused._use_pallas()`` like the other kernels:
Pallas on one TPU device, the lax form (the same chunked form in XLA
einsums, a chunk recomputed in the backward pass) elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.compile.engine import engine_jit
from analytics_zoo_tpu.ops import fused
from analytics_zoo_tpu.ops.pallas_attention import (
    _col_to_row, _head_lanes, _row_to_col, _spread)

F32 = jnp.float32
NEG = -1e30
_LANES = 128
# positions a chunk (Mamba-2's ``chunk_size``)
CHUNK = 256
# lanes of heads a grid step takes at most: 8 heads of 64
_STEP_LANES = 512
# what a kernel asks for beside the VMEM it keeps resident (the flash
# kernels' budget: XLA keeps arrays between operations in what no kernel
# claims)
_SCOPED_VMEM = 16 << 20
_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def chunk_cumsum(v, chunk: int):
    """(B, T, H) -> the running sum of ``v`` over positions, restarted at
    every chunk of ``chunk`` positions."""
    b, t, h = v.shape
    return jnp.cumsum(v.reshape(b, t // chunk, chunk, h),
                      axis=2).reshape(b, t, h)


# ------------------------------------------------------------------ lax
def _chunk_step(s, xs, group: int):
    """One chunk for every head: ``s`` (B, H, N, P) the state before it;
    ``x`` (B, L, H, P), ``dt`` and ``lam`` (B, L, H), ``b`` and ``c``
    (B, L, G, N) -> (the state after it, y (B, L, H, P))."""
    x, dt, lam, b, c = xs
    length = x.shape[1]
    g = jnp.repeat(jnp.einsum("blgn,bsgn->bgls", c, b), group, axis=1)
    lam_h = jnp.moveaxis(lam, 1, 2)                           # (B, H, L)
    causal = jnp.tril(jnp.ones((length, length), bool))
    decay = jnp.exp(jnp.where(causal, lam_h[..., :, None]
                              - lam_h[..., None, :], NEG))
    m = g * decay * jnp.moveaxis(dt, 1, 2)[..., None, :]
    c_h, b_h = (jnp.repeat(v, group, axis=2) for v in (c, b))
    y = jnp.einsum("bhls,bshp->blhp", m, x) \
        + jnp.einsum("blhn,bhnp->blhp", c_h, s) * jnp.exp(lam)[..., None]
    end = lam[:, -1]                                          # (B, H)
    w = jnp.exp(end[:, None] - lam) * dt
    s = jnp.exp(end)[..., None, None] * s \
        + jnp.einsum("blhn,blhp->bhnp", b_h * w[..., None], x)
    return s, y


def ssd_scan_lax(x, dt, lam, b, c, state, groups: int, chunk: int):
    """The chunked form in XLA: ``x`` (B, T, H·P), ``dt`` and ``lam``
    (B, T, H), ``b``, ``c`` (B, T, G·N), ``state`` (B, H, N, P), ``T`` a
    multiple of ``chunk`` -> (y (B, T, H·P) in ``x``'s dtype, the last
    state), float32 inside.  A chunk is recomputed in the backward pass,
    which holds one state a chunk."""
    bsz, t, width = x.shape
    h = dt.shape[-1]
    p, n = width // h, b.shape[-1] // groups

    def chunks(v, *tail):
        v = v.astype(F32).reshape(bsz, t // chunk, chunk, *tail)
        return jnp.moveaxis(v, 1, 0)

    xs = (chunks(x, h, p), chunks(dt, h), chunks(lam, h),
          chunks(b, groups, n), chunks(c, groups, n))
    step = jax.checkpoint(functools.partial(_chunk_step,
                                            group=h // groups))
    last, y = jax.lax.scan(step, state.astype(F32), xs)
    return jnp.moveaxis(y, 0, 1).reshape(x.shape).astype(x.dtype), last


# --------------------------------------------------------------- kernels
def _causal(length: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (length, length), 0)
    return rows >= jax.lax.broadcasted_iota(jnp.int32, (length, length), 1)


def _decay(lam_col, lam_row, causal):
    """``exp(lam_l - lam_s)`` where ``l >= s``, else 0: (L, L)."""
    return jnp.exp(jnp.where(causal, lam_col - lam_row, NEG))


def _end(row):
    """The last entry of a (1, L) row, as (1, 1)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _lane_sum(j: int, per: int, v):
    """Sum over head ``j``'s lanes of ``v`` (rows, lanes) -> (rows, 1)."""
    return jnp.sum(_head_lanes(j, per, v)[0], axis=1, keepdims=True)


def _fwd_kernel(x_ref, dt_ref, lam_ref, b_ref, c_ref, s0_ref, y_ref,
                start_ref, last_ref, g_scr, s_scr, *, per: int, lanes: int,
                tiles: int):
    """One chunk of one tile of heads, forward."""
    k, i = pl.program_id(1), pl.program_id(2)
    length, width = x_ref.shape
    dtype = x_ref.dtype

    @pl.when(i % tiles == 0)
    def _group():
        g_scr[...] = jax.lax.dot_general(c_ref[...], b_ref[...], _NT,
                                         preferred_element_type=F32)

    @pl.when(k == 0)
    def _init():
        s_scr[i] = s0_ref[i]

    s_start = s_scr[i]                                        # (N, width)
    start_ref[...] = s_start
    g, causal = g_scr[...], _causal(length)
    c = c_ref[...]
    b_t = b_ref[...].astype(F32).T                            # (N, L)
    p = lanes // per
    for lo in range(0, width, lanes):
        own = slice(lo, lo + lanes)
        x, s = x_ref[:, own], s_start[:, own]
        y = s_new = None
        e_cols, ends = [], []
        for j in range(per):
            lam_row, dt_row = lam_ref[lo // p + j], dt_ref[lo // p + j]
            lam_col = _row_to_col(lam_row)
            m = g * _decay(lam_col, lam_row, causal) * dt_row
            x_j = _head_lanes(j, per, x)[0]
            y_j = jnp.dot(m.astype(dtype), x_j, preferred_element_type=F32)
            end = _end(lam_row)
            w_row = jnp.exp(end - lam_row) * dt_row
            s_j = jnp.dot((b_t * w_row).astype(dtype), x_j,
                          preferred_element_type=F32)
            y = y_j if y is None else y + y_j
            s_new = s_j if s_new is None else s_new + s_j
            e_cols.append(jnp.exp(lam_col))
            ends.append(jnp.exp(end))
        y = y + jnp.dot(c, s.astype(dtype), preferred_element_type=F32) \
            * _spread(e_cols, lanes)
        y_ref[:, own] = y.astype(y_ref.dtype)
        s_scr[i, :, own] = s * _spread(ends, lanes) + s_new

    @pl.when(k == pl.num_programs(1) - 1)
    def _last():
        last_ref[i] = s_scr[i]


def _bwd_kernel(x_ref, dt_ref, lam_ref, b_ref, c_ref, start_ref, dy_ref,
                dlast_ref, dx_ref, ddt_ref, dlam_ref, db_ref, dc_ref,
                ds0_ref, g_scr, dg_scr, db_scr, dc_scr, ds_scr, *, per: int,
                lanes: int, tiles: int):
    """One chunk of one tile of heads, backward (the chunks last to
    first).  ``ds_scr`` holds the cotangent of every tile's state after
    the chunk; ``dg_scr``, ``db_scr`` and ``dc_scr`` the group's sums over
    its heads of the chunk's dG (of ``C B^T``) and of the parts of dB and
    dC that the states give, which leave on the group's last tile."""
    k, i = pl.program_id(1), pl.program_id(2)
    length, width = x_ref.shape
    dtype = x_ref.dtype

    @pl.when(i % tiles == 0)
    def _group():
        g_scr[...] = jax.lax.dot_general(c_ref[...], b_ref[...], _NT,
                                         preferred_element_type=F32)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    @pl.when(k == 0)
    def _init():
        ds_scr[i] = dlast_ref[i]

    g, causal = g_scr[...], _causal(length)
    b, c = b_ref[...], c_ref[...]
    c_t = c.astype(F32).T.astype(dtype)                       # (N, L)
    s_start, ds_end = start_ref[...], ds_scr[i]               # (N, width)
    last_pos = jax.lax.broadcasted_iota(jnp.int32, (1, length), 1) \
        == length - 1
    p = lanes // per
    dg = None
    for lo in range(0, width, lanes):
        own = slice(lo, lo + lanes)
        x, dy = x_ref[:, own], dy_ref[:, own]
        s, ds = s_start[:, own], ds_end[:, own]
        xf, dyf = x.astype(F32), dy.astype(F32)
        # C S and B dS for the lane group's heads at once
        c_s_dy = jnp.dot(c, s.astype(dtype), preferred_element_type=F32) \
            * dyf
        b_ds = jnp.dot(b, ds.astype(dtype), preferred_element_type=F32)
        b_ds_x, s_ds = b_ds * xf, s * ds
        dx = None
        e_cols, w_cols, ends = [], [], []
        for j in range(per):
            h = lo // p + j
            lam_row, dt_row = lam_ref[h], dt_ref[h]
            lam_col, dt_col = _row_to_col(lam_row), _row_to_col(dt_row)
            decay = _decay(lam_col, lam_row, causal)
            m = g * decay * dt_row
            dy_j = _head_lanes(j, per, dy)[0]
            # within the chunk: dM = dy x^T over the head's lanes
            dm = jax.lax.dot_general(dy_j, x, _NT,
                                     preferred_element_type=F32)
            dg_j = dm * decay * dt_row
            dg = dg_j if dg is None else dg + dg_j
            z = dg_j * g                                      # dM o M
            dx_j = jnp.dot(m.T.astype(dtype), dy_j,
                           preferred_element_type=F32)
            # through the state after the chunk
            end = _end(lam_row)
            to_end = jnp.exp(end - lam_col)                   # (L, 1)
            w_col = to_end * dt_col
            dw_col = _lane_sum(j, per, b_ds_x)
            dx_j = dx_j + _head_lanes(j, per, b_ds)[0] * w_col
            dx = dx_j if dx is None else dx + dx_j
            r_col = dw_col * w_col
            e_col = jnp.exp(lam_col)
            dlam_col = jnp.sum(z, axis=1, keepdims=True) - r_col \
                + e_col * _lane_sum(j, per, c_s_dy)
            dlam_end = jnp.sum(r_col, axis=0, keepdims=True) \
                + jnp.exp(end) * jnp.sum(_lane_sum(j, per, s_ds), axis=0,
                                         keepdims=True)
            ddt_ref[h] = jnp.sum(dm * g * decay, axis=0, keepdims=True) \
                + _col_to_row(dw_col * to_end)
            dlam_ref[h] = _col_to_row(dlam_col) \
                - jnp.sum(z, axis=0, keepdims=True) \
                + jnp.where(last_pos, dlam_end, 0.0)
            e_cols.append(e_col)
            w_cols.append(w_col)
            ends.append(jnp.exp(end))
        dx_ref[:, own] = dx.astype(dx_ref.dtype)
        ey = (dyf * _spread(e_cols, lanes)).astype(dtype)
        xw = (xf * _spread(w_cols, lanes)).astype(dtype)
        dc_scr[...] += jax.lax.dot_general(ey, s.astype(dtype), _NT,
                                           preferred_element_type=F32)
        db_scr[...] += jax.lax.dot_general(xw, ds.astype(dtype), _NT,
                                           preferred_element_type=F32)
        ds_scr[i, :, own] = jnp.dot(c_t, ey, preferred_element_type=F32) \
            + ds * _spread(ends, lanes)
    dg_scr[...] += dg

    @pl.when(i % tiles == tiles - 1)
    def _store():
        dg_all = dg_scr[...]
        dc_ref[...] = dc_scr[...] + jnp.dot(
            dg_all.astype(dtype), b, preferred_element_type=F32)
        db_ref[...] = db_scr[...] + jnp.dot(
            dg_all.T.astype(dtype), c, preferred_element_type=F32)

    @pl.when(k == pl.num_programs(1) - 1)
    def _first_chunk():
        ds0_ref[i] = ds_scr[i]


# ------------------------------------------------------------ the calls
def _lane_group(p: int) -> int:
    """Lanes a head's products take: 128 for heads that share them, the
    head's own for a lane multiple."""
    return max(p, _LANES)


def _heads_per_step(h: int, groups: int, p: int) -> int:
    """Heads a grid step takes: the most, up to ``_STEP_LANES`` of lanes
    (or one head where a head is wider), that divide a group and fill
    whole lane groups; 0 where none does."""
    per_group = h // groups
    fits = [n for n in range(1, per_group + 1)
            if per_group % n == 0 and (n * p) % _lane_group(p) == 0
            and n * p <= max(_STEP_LANES, p)]
    return max(fits, default=0)


def pallas_fits(h: int, groups: int, p: int, n: int, chunk: int) -> bool:
    """Whether the kernels take ``h`` heads of ``p`` in ``groups`` groups
    of ``n`` states by chunks of ``chunk``: a head fills or divides the
    128 lanes, whole lane groups a step, a chunk of whole lane tiles, and
    a group's ``B`` and ``C`` a block of lanes of their own."""
    lanes_ok = p % _LANES == 0 or _LANES % p == 0
    return (h % groups == 0 and lanes_ok and chunk % _LANES == 0
            and (groups == 1 or n % _LANES == 0)
            and _heads_per_step(h, groups, p) > 0)


def _layout(cfg, t: int):
    h, groups, p, chunk, _ = cfg
    hp = _heads_per_step(h, groups, p)
    return dict(hp=hp, width=hp * p, tiles=h // hp, n_chunks=t // chunk,
                per_group=(h // groups) // hp, lanes=_lane_group(p),
                per=_lane_group(p) // p)


def _specs(cfg, n: int, lay, rev: bool):
    """Block specs over a grid (batch, chunk, tile of heads); the chunks
    last to first if ``rev``."""
    chunk, nc = cfg[3], lay["n_chunks"]

    def at(f):
        if rev:
            return lambda b, k, i: f(b, nc - 1 - k, i)
        return lambda b, k, i: f(b, k, i)

    tile = pl.BlockSpec((None, chunk, lay["width"]),
                        at(lambda b, k, i: (b, k, i)))
    rows = pl.BlockSpec((None, lay["hp"], 1, chunk),
                        at(lambda b, k, i: (b, i, 0, k)))
    group = pl.BlockSpec((None, chunk, n),
                         at(lambda b, k, i: (b, k, i // lay["per_group"])))
    start = pl.BlockSpec((None, None, None, n, lay["width"]),
                         at(lambda b, k, i: (b, k, i, 0, 0)))
    states = pl.BlockSpec((None, lay["tiles"], n, lay["width"]),
                          at(lambda b, k, i: (b, 0, 0, 0)))
    return tile, rows, group, start, states


def _resident(lay, n: int, chunk: int, arrays: int) -> int:
    """VMEM the kernel keeps over the grid: ``arrays`` float32 copies of
    every tile's state (scratch and the two buffers of the whole-state
    blocks) and the chunk's (L, L) matrices."""
    state = lay["tiles"] * n * lay["width"] * 4
    return arrays * state + 2 * chunk * chunk * 4


def _params(lay, n: int, chunk: int, arrays: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_SCOPED_VMEM + _resident(lay, n, chunk, arrays))


def _fwd_impl(x, dt, lam, b, c, s0, cfg):
    h, groups, p, chunk, interpret = cfg
    bsz, t, _ = x.shape
    n = b.shape[-1] // groups
    lay = _layout(cfg, t)
    tile, rows, group, start, states = _specs(cfg, n, lay, False)
    whole = (bsz, lay["tiles"], n, lay["width"])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, per=lay["per"], lanes=lay["lanes"],
                          tiles=lay["per_group"]),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, lay["n_chunks"], lay["tiles"],
                                         n, lay["width"]), F32),
                   jax.ShapeDtypeStruct(whole, F32)),
        grid=(bsz, lay["n_chunks"], lay["tiles"]),
        in_specs=[tile, rows, rows, group, group, states],
        out_specs=(tile, start, states),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM(whole[1:], F32)],
        compiler_params=_params(lay, n, chunk, 5),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x, dt, lam, b, c, s0)


def _bwd_impl(x, dt, lam, b, c, starts, dy, dlast, cfg):
    h, groups, p, chunk, interpret = cfg
    bsz, t, _ = x.shape
    n = b.shape[-1] // groups
    lay = _layout(cfg, t)
    tile, rows, group, start, states = _specs(cfg, n, lay, True)
    whole = (bsz, lay["tiles"], n, lay["width"])
    dx, ddt, dlam, db, dc, ds0 = pl.pallas_call(
        functools.partial(_bwd_kernel, per=lay["per"], lanes=lay["lanes"],
                          tiles=lay["per_group"]),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, F32),
                   jax.ShapeDtypeStruct(lam.shape, F32),
                   jax.ShapeDtypeStruct(b.shape, F32),
                   jax.ShapeDtypeStruct(c.shape, F32),
                   jax.ShapeDtypeStruct(whole, F32)),
        grid=(bsz, lay["n_chunks"], lay["tiles"]),
        in_specs=[tile, rows, rows, group, group, start, tile, states],
        out_specs=(tile, rows, rows, group, group, states),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, n), F32),
                        pltpu.VMEM((chunk, n), F32),
                        pltpu.VMEM(whole[1:], F32)],
        compiler_params=_params(lay, n, chunk, 5),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x, dt, lam, b, c, starts, dy, dlast)
    return dx, ddt, dlam, db.astype(b.dtype), dc.astype(c.dtype), ds0


# Programs of their own, as the other kernels are: a model traces each
# kernel body once a process.
_forward = engine_jit(_fwd_impl, static_argnums=(6,),
                      key_hint="ssd_scan_forward")
_backward = engine_jit(_bwd_impl, static_argnums=(8,),
                       key_hint="ssd_scan_backward")


# What a recomputed layer keeps of a call (``fused.keep_result``): ``y``
# for whatever reads the scan, the chunk-start states for the backward
# kernel, and the last state, without which the layer's second pass
# would run the forward kernel for it.
KEPT_RESULTS = ("ssd_scan_y", "ssd_scan_starts", "ssd_scan_last")


def _scan(x, dt, lam, b, c, s0, cfg):
    """The kernels' form.  As flash attention's ``_flash``: the forward
    kernel is an ordinary call on operands cut off from differentiation
    and its results carry ``KEPT_RESULTS``' names, so that a recomputed
    layer whose policy saves them runs the forward kernel once;
    ``_attach`` hangs the backward kernel on the results."""
    results = _forward(*jax.lax.stop_gradient((x, dt, lam, b, c, s0)), cfg)
    return _attach(x, dt, lam, b, c, s0,
                   *map(fused.keep_result, results, KEPT_RESULTS), cfg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _attach(x, dt, lam, b, c, s0, y, starts, last, cfg):
    """``(y, the last state)``, the forward kernel's results, as
    functions of the scan's operands: the backward kernel on ``(x, dt,
    lam, b, c, starts)`` gives those their cotangents, from ``y``'s AND
    the last state's; the forward's results get none."""
    return y, last


def _attach_fwd(x, dt, lam, b, c, s0, y, starts, last, cfg):
    return (_attach(x, dt, lam, b, c, s0, y, starts, last, cfg),
            (x, dt, lam, b, c, starts))


def _attach_bwd(cfg, res, cot):
    dy, dlast = cot
    return (*_backward(*res, dy, dlast, cfg), None, None, None)


_attach.defvjp(_attach_fwd, _attach_bwd)


def _rows(v):
    """(B, T, H) -> (B, H, 1, T): a head's values as a lane-dense row."""
    return jnp.moveaxis(v, 1, 2)[:, :, None, :]


def _state_to_kernel(s, hp: int):
    """(B, H, N, P) -> (B, tiles, N, hp·P)."""
    bsz, h, n, p = s.shape
    s = s.reshape(bsz, h // hp, hp, n, p)
    return jnp.moveaxis(s, 3, 2).reshape(bsz, h // hp, n, hp * p)


def _state_from_kernel(s, p: int):
    bsz, tiles, n, width = s.shape
    s = jnp.moveaxis(s.reshape(bsz, tiles, n, width // p, p), 2, 3)
    return s.reshape(bsz, tiles * (width // p), n, p)


def ssd_scan(x, dt, a, b, c, state: Optional[jax.Array] = None, *,
             groups: int = 1, chunk: int = CHUNK, interpret: bool = False):
    """``x`` (B, T, H·P), ``dt`` (B, T, H), ``a`` (H,), ``b``, ``c``
    (B, T, G·N) with ``G = groups``, ``state`` (B, H, N, P) or None
    (zeros) -> (``y`` (B, T, H·P) in ``x``'s dtype, the last state
    (B, H, N, P) in float32); see the module's docstring for the
    recurrence.  ``T`` need not be a multiple of ``chunk``: the tail is
    padded with positions of ``dt = 0``, which neither decay the state
    nor add to it.  Differentiable in everything it takes."""
    bsz, t, width = x.shape
    h = dt.shape[-1]
    p, n = width // h, b.shape[-1] // groups
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                       for v in (x, dt, b, c))
    dt = dt.astype(F32)
    lam = chunk_cumsum(dt * a.astype(F32), chunk)
    if state is None:
        state = jnp.zeros((bsz, h, n, p), F32)
    if (interpret or fused._use_pallas()) \
            and pallas_fits(h, groups, p, n, chunk):
        fused.count_build("ssd_scan", "pallas")
        hp = _heads_per_step(h, groups, p)
        y, last = _scan(x, _rows(dt), _rows(lam), b, c,
                        _state_to_kernel(state.astype(F32), hp),
                        (h, groups, p, chunk, interpret))
        last = _state_from_kernel(last, p)
    else:
        fused.count_build("ssd_scan", "lax")
        y, last = ssd_scan_lax(x, dt, lam, b, c, state, groups, chunk)
    return y[:, :t], last

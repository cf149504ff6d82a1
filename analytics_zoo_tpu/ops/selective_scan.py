"""The selective scan of a state-space layer, forward and backward.

For every channel ``c`` and state ``n``, over the positions ``t`` of a
sequence (``dt`` already positive, ``A`` already negative)::

    s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n s_t[c, n] C_t[n]

The decay differs by channel AND state, so there is no matrix form: the
work is ``T x C x N`` multiply-adds on the vector unit and one ``exp``
each, in order of ``t``.  Everything is float32.

Kernel layout: the channels fill whole registers.  ``x``, ``dt`` and
``y`` enter as (B, T, C / 128, 128) and a grid step takes ``_CHUNK``
positions of one GROUP of 1,024 channels, (8, 128) a position; the state
of a group is ``N`` such registers, carried through the chunk's loop and
from chunk to chunk in VMEM scratch.  ``B_t[n]`` and ``C_t[n]`` are
scalars of SMEM, splat over the register.  The (T, C, N) states never
reach HBM: the forward pass writes the state at each chunk's start
(``T / _CHUNK`` of them), the backward pass walks the chunks last to
first, runs a chunk forward again from its start into VMEM scratch, then
backward through it.

``selective_scan`` routes by ``fused._use_pallas()`` like the other
kernels: Pallas on one TPU device, the lax form (a sequential
``lax.scan``, recomputed by chunk in the backward pass) elsewhere.
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

_LANES, _SUBLANES = 128, 8
_GROUP = _LANES * _SUBLANES
# positions a grid step: the backward pass keeps a chunk's states in
# VMEM, 64 KB a position at 16 states
_CHUNK = 64


# ------------------------------------------------------------------ lax
def _scan_positions(x, dt, a, b, c, state):
    """The recurrence over the positions of ``x`` (T, C), from
    ``state`` (C, N); -> (y (T, C), the last state)."""
    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=1)

    state, y = jax.lax.scan(step, state, (x, dt, b, c))
    return y, state


def selective_scan_lax(x, dt, a, b, c, state=None):
    """The scan as a sequential ``lax.scan``: ``x``, ``dt`` (B, T, C),
    ``a`` (C, N), ``b``, ``c`` (B, T, N), ``state`` (B, C, N) or None
    (zeros) -> (``y`` (B, T, C), the last state (B, C, N)), float32.
    The backward pass recomputes one chunk of positions at a time, so
    it holds ``T / _CHUNK`` states and not ``T``."""
    f32 = jnp.float32
    x, dt, a, b, c = (v.astype(f32) for v in (x, dt, a, b, c))
    bsz, t, ch = x.shape
    if state is None:
        state = jnp.zeros((bsz, ch, a.shape[1]), f32)
    chunk = _CHUNK if t % _CHUNK == 0 else t

    def one(x, dt, b, c, state):
        def piece(s, xs):
            y, s = jax.checkpoint(_scan_positions)(*xs[:2], a, *xs[2:], s)
            return s, y

        parts = tuple(v.reshape(t // chunk, chunk, -1)
                      for v in (x, dt, b, c))
        state, y = jax.lax.scan(piece, state, parts)
        return y.reshape(t, ch), state

    return jax.vmap(one)(x, dt, b, c, state.astype(f32))


# --------------------------------------------------------------- kernels
def _fwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, s0_ref, y_ref,
                start_ref, last_ref, state, *, chunk: int, n: int):
    """One chunk of one channel group, forward."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = s0_ref[...]

    start_ref[...] = state[...]

    def body(t, s):
        dt = dt_ref[t]
        dtx = dt * x_ref[t]
        y, new = None, []
        for i in range(n):
            s_i = jnp.exp(dt * a_ref[i]) * s[i] + dtx * b_ref[t * n + i]
            y_i = s_i * c_ref[t * n + i]
            y = y_i if y is None else y + y_i
            new.append(s_i)
        y_ref[t] = y
        return tuple(new)

    s = jax.lax.fori_loop(0, chunk, body, tuple(state[i] for i in range(n)))
    for i in range(n):
        state[i] = s[i]

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _last():
        last_ref[...] = state[...]


def _bwd_kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, start_ref, dy_ref,
                dlast_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, ds0_ref,
                states, carry, da_acc, *, chunk: int, n: int):
    """One chunk of one channel group, backward.  Grid (batch, chunks
    last to first, groups): the per-position partial sums of dB and dC
    (over the sublanes of this group's channels; the lanes are left to
    the caller) add up over the groups in the output block, which stays
    in VMEM while they go by."""
    k, g = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        carry[g] = dlast_ref[...]
        da_acc[g] = jnp.zeros_like(da_acc[g])

    # the chunk forward again, every state kept: states[t] is the state
    # BEFORE position t, states[t + 1] the one after it
    states[0] = start_ref[...]

    def forward(t, s):
        dt = dt_ref[t]
        dtx = dt * x_ref[t]
        new = []
        for i in range(n):
            s_i = jnp.exp(dt * a_ref[i]) * s[i] + dtx * b_ref[t * n + i]
            states[t + 1, i] = s_i
            new.append(s_i)
        return tuple(new)

    jax.lax.fori_loop(0, chunk, forward,
                      tuple(start_ref[i] for i in range(n)))

    first = g == 0

    def backward(j, h):
        t = chunk - 1 - j
        dt, x, dy = dt_ref[t], x_ref[t], dy_ref[t]
        dtx = dt * x
        g_b = ddt = None
        new = []
        for i in range(n):
            a_i = a_ref[i]
            decay = jnp.exp(dt * a_i)
            g_i = dy * c_ref[t * n + i] + h[i]
            # what is left of the sums over the channels: one row of
            # lanes a state
            dc_i = jnp.sum(states[t + 1, i] * dy, axis=0, keepdims=True)
            db_i = jnp.sum(g_i * dtx, axis=0, keepdims=True)
            dc_ref[t, i:i + 1, :] = jnp.where(
                first, dc_i, dc_ref[t, i:i + 1, :] + dc_i)
            db_ref[t, i:i + 1, :] = jnp.where(
                first, db_i, db_ref[t, i:i + 1, :] + db_i)
            gb_i = g_i * b_ref[t * n + i]
            g_b = gb_i if g_b is None else g_b + gb_i
            # d/d(dt A): through the decay alone
            dexp = g_i * states[t, i] * decay
            ddt_i = dexp * a_i
            ddt = ddt_i if ddt is None else ddt + ddt_i
            da_acc[g, i] += dexp * dt
            new.append(decay * g_i)
        ddt_ref[t] = ddt + g_b * x
        dx_ref[t] = g_b * dt
        return tuple(new)

    h = jax.lax.fori_loop(0, chunk, backward,
                          tuple(carry[g, i] for i in range(n)))
    for i in range(n):
        carry[g, i] = h[i]

    # running sums: the group's last visit, at the first chunk, leaves
    # the whole ones
    da_ref[...] = da_acc[g]
    ds0_ref[...] = carry[g]


# ------------------------------------------------------------ the calls
def _specs(chunk: int, n: int, n_chunks: int, rev: bool):
    """Block specs over the kernel layout, for a grid (batch, chunk,
    group) if ``rev`` (chunks last to first) else (batch, group,
    chunk)."""
    def at(f):
        if rev:
            return lambda b, k, g: f(b, n_chunks - 1 - k, g)
        return lambda b, g, k: f(b, k, g)

    smem = pl.BlockSpec((chunk * n,), at(lambda b, k, g: (b * n_chunks + k,)),
                        memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((None, chunk, _SUBLANES, _LANES),
                        at(lambda b, k, g: (b, k, g, 0)))
    a = pl.BlockSpec((n, _SUBLANES, _LANES), at(lambda b, k, g: (0, g, 0)))
    start = pl.BlockSpec((None, None, n, _SUBLANES, _LANES),
                         at(lambda b, k, g: (b, k, 0, g, 0)))
    group = pl.BlockSpec((None, None, n, _SUBLANES, _LANES),
                         at(lambda b, k, g: (b, g, 0, 0, 0)))
    sums = pl.BlockSpec((None, chunk, n, _LANES),
                        at(lambda b, k, g: (b, k, 0, 0)))
    return smem, rows, a, start, group, sums


def _to_kernel(v):
    """(B, T, C) -> (B, T, C / 128, 128)."""
    return v.reshape(*v.shape[:-1], v.shape[-1] // _LANES, _LANES)


def _state_to_kernel(s):
    """(B, C, N) -> (B, groups, N, 8, 128)."""
    b, ch, n = s.shape
    return jnp.moveaxis(s, 2, 1).reshape(
        b, n, ch // _GROUP, _SUBLANES, _LANES).swapaxes(1, 2)


def _state_from_kernel(s):
    b, groups, n = s.shape[:3]
    return jnp.moveaxis(s.swapaxes(1, 2).reshape(b, n, groups * _GROUP),
                        1, 2)


def _fwd_impl(x, dt, a, b, c, state, interpret: bool):
    bsz, t, ch = x.shape
    n, groups, n_chunks = a.shape[1], ch // _GROUP, t // _CHUNK
    smem, rows, a_spec, start, group, _ = _specs(_CHUNK, n, n_chunks, False)
    f32 = jnp.float32
    tiles = (bsz, t, ch // _LANES, _LANES)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=_CHUNK, n=n),
        out_shape=(jax.ShapeDtypeStruct(tiles, f32),
                   jax.ShapeDtypeStruct(
                       (bsz, n_chunks, n, ch // _LANES, _LANES), f32),
                   jax.ShapeDtypeStruct(
                       (bsz, groups, n, _SUBLANES, _LANES), f32)),
        grid=(bsz, groups, n_chunks),
        in_specs=[smem, smem, rows, rows, a_spec, group],
        out_specs=(rows, start, group),
        scratch_shapes=[pltpu.VMEM((n, _SUBLANES, _LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="selective_scan_fwd",
    )(b.reshape(-1), c.reshape(-1), _to_kernel(x), _to_kernel(dt),
      _to_kernel(a.T), _state_to_kernel(state))


def _bwd_impl(x, dt, a, b, c, starts, dy, dlast, interpret: bool):
    bsz, t, ch = x.shape
    n, groups, n_chunks = a.shape[1], ch // _GROUP, t // _CHUNK
    smem, rows, a_spec, start, group, sums = _specs(_CHUNK, n, n_chunks,
                                                    True)
    f32 = jnp.float32
    tiles = jax.ShapeDtypeStruct((bsz, t, ch // _LANES, _LANES), f32)
    partial = jax.ShapeDtypeStruct((bsz, t, n, _LANES), f32)
    by_group = jax.ShapeDtypeStruct((bsz, groups, n, _SUBLANES, _LANES), f32)
    whole = (groups, n, _SUBLANES, _LANES)
    dx, ddt, db, dc, da, ds0 = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=_CHUNK, n=n),
        out_shape=(tiles, tiles, partial, partial, by_group, by_group),
        grid=(bsz, n_chunks, groups),
        in_specs=[smem, smem, rows, rows, a_spec, start, rows, group],
        out_specs=(rows, rows, sums, sums, group, group),
        scratch_shapes=[
            pltpu.VMEM((_CHUNK + 1, n, _SUBLANES, _LANES), f32),
            pltpu.VMEM(whole, f32), pltpu.VMEM(whole, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan_bwd",
    )(b.reshape(-1), c.reshape(-1), _to_kernel(x), _to_kernel(dt),
      _to_kernel(a.T), starts, _to_kernel(dy), _state_to_kernel(dlast))
    return (dx.reshape(x.shape), ddt.reshape(x.shape),
            jnp.sum(_state_from_kernel(da), axis=0),
            jnp.sum(db, axis=-1), jnp.sum(dc, axis=-1),
            _state_from_kernel(ds0))


# Programs of their own, as the flash kernels are: a model traces each
# kernel body once a process.
_forward = engine_jit(_fwd_impl, static_argnums=(6,),
                      key_hint="selective_scan_forward")
_backward = engine_jit(_bwd_impl, static_argnums=(8,),
                       key_hint="selective_scan_backward")


# What a recomputed layer keeps of a call (``fused.keep_result``): ``y``
# for whatever reads the scan, the chunk-start states for the backward
# kernel, and the last state (``N`` registers a channel group), without
# which the layer's second pass would run the forward kernel for it.
KEPT_RESULTS = ("selective_scan_y", "selective_scan_starts",
                "selective_scan_last")


def _scan(x, dt, a, b, c, state, interpret):
    """The kernels' form of the scan.  As flash attention's ``_flash``:
    the forward kernel is an ordinary call on operands cut off from
    differentiation and its results carry ``KEPT_RESULTS``' names, so
    that a recomputed layer whose policy saves them runs the forward
    kernel once; ``_attach`` hangs the backward kernel on the results."""
    results = _forward(
        *jax.lax.stop_gradient((x, dt, a, b, c, state)), interpret)
    return _attach(x, dt, a, b, c, state,
                   *map(fused.keep_result, results, KEPT_RESULTS), interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
def _attach(x, dt, a, b, c, state, y, starts, last, interpret):
    """``(y, the last state)``, the forward kernel's results in its own
    layout, as functions of the scan's six operands: the backward kernel
    on ``(x, dt, a, b, c, starts)`` gives those their cotangents, from
    ``y``'s AND the last state's; the forward's results get none."""
    return y.reshape(x.shape), _state_from_kernel(last)


def _attach_fwd(x, dt, a, b, c, state, y, starts, last, interpret):
    return (_attach(x, dt, a, b, c, state, y, starts, last, interpret),
            (x, dt, a, b, c, starts))


def _attach_bwd(interpret, res, cot):
    dy, dlast = cot
    return (*_backward(*res, dy, dlast, interpret), None, None, None)


_attach.defvjp(_attach_fwd, _attach_bwd)


def pallas_fits(t: int, channels: int) -> bool:
    """Whether the kernels take a scan of ``t`` positions over
    ``channels``: whole chunks of positions, whole groups of 1,024
    channels."""
    return t % _CHUNK == 0 and channels % _GROUP == 0


def selective_scan(x, dt, a, b, c, state: Optional[jax.Array] = None,
                   interpret: bool = False):
    """``x``, ``dt`` (B, T, C), ``a`` (C, N), ``b``, ``c`` (B, T, N),
    ``state`` (B, C, N) or None (zeros) -> (``y`` (B, T, C), the last
    state (B, C, N)), float32 (see the module's docstring for the
    recurrence).  Differentiable in everything it takes."""
    bsz, t, ch = x.shape
    if (interpret or fused._use_pallas()) and pallas_fits(t, ch):
        fused.count_build("selective_scan", "pallas")
        f32 = jnp.float32
        if state is None:
            state = jnp.zeros((bsz, ch, a.shape[1]), f32)
        return _scan(*(v.astype(f32) for v in (x, dt, a, b, c, state)),
                     interpret)
    fused.count_build("selective_scan", "lax")
    return selective_scan_lax(x, dt, a, b, c, state)

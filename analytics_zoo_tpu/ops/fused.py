"""Fused kernel suite — single-HBM-pass hot-path code.

* **Fused optimizer update** (``build_fused_update``): global-norm
  grad clip + SGD/Adam moment update + parameter apply in ONE pass over
  each leaf.  The optax path the trainer used
  (``optax.global_norm`` → ``tx.update`` → ``optax.apply_updates``)
  materialises a clipped-grads tree, an updates tree, and a new params
  tree — three full HBM sweeps of params+grads per step.  The fused
  path reads each (param, grad, moment) triple once and writes the new
  (param, moment) in place: plain ``jnp`` arithmetic that XLA compiles
  to one loop fusion a leaf, in the leaf's own shape and layout, on
  one device and on a mesh alike.  The math REPRODUCES optax op-for-op
  (same order, same dtypes, same bias-correction formulas), so the
  fused step is numerically the optax step — ``tests/test_fused_kernels.py``
  holds it bit-identical.  It has no Pallas form: the one it had
  needed every leaf as ``(rows, 128)``, a copy in and a copy out
  (PERF.md, Findings, PR 28).

* **Epilogue kernels** (``bias_gelu``, ``layernorm_act``): the
  bias-add→GeLU and LayerNorm→activation tails of the dense/attention
  stacks, computed without a round trip of the intermediate activation
  through HBM.  Each has a Pallas kernel and a lax form beside it; ONE
  capability probe (``pallas_supported``) decides which runs.
  Differentiable: a ``custom_vjp`` runs the Pallas forward and takes
  the backward from the lax form's own derivative.

* The flash-attention kernels live in ``ops/pallas_attention.py`` and
  the cross-chip ring schedule in ``parallel/ring_attention.py`` — this
  module is the single-chip elementwise/reduction half of the suite.

Mode selection (``ops.fused`` config key) — governs the epilogue
kernels' path, and whether the suite is on at all:

* ``auto`` (default) — Pallas kernels on a TPU backend (one eager
  probe; a kernel the TPU compiler refuses is an error, never a quiet
  switch to lax), lax on every other backend.
* ``pallas`` — the Pallas kernels whatever the topology (expert use).
* ``lax``  — always the lax form (same math, XLA fusion does the work).
* ``off``  — disable the suite; call sites fall back to their
  pre-suite code paths (the trainer runs the optax triple pass).

Every call site sits INSIDE an ``engine_jit`` program (train step,
predict step, bench workloads), so the kernels are compiled and
cached with it (docs/aot-compile.md).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.ad_checkpoint import checkpoint_name

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops import activations as acts


# ------------------------------------------------------------------ mode
def _mode() -> str:
    from analytics_zoo_tpu.common.config import get_config
    m = str(get_config().get("ops.fused", "auto") or "auto").lower()
    return m if m in ("auto", "pallas", "lax", "off") else "auto"


def fused_enabled() -> bool:
    """Whether the fused call sites should fire at all."""
    return _mode() != "off"


_PALLAS_OK: Optional[bool] = None


def _probe():
    """ONE representative suite kernel — SMEM scalar operand + grid +
    ``input_output_aliases`` — as ``(jitted function, argument
    shapes)``."""
    def k(s_ref, x_ref, o_ref):
        o_ref[:] = x_ref[:] * s_ref[0]

    blk = pl.BlockSpec((8, 128), lambda i: (i, 0))
    # zoolint: disable=COMPILE011 — capability probe, not an engine program
    fn = jax.jit(lambda s, a: pl.pallas_call(
        k,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        grid=(2,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), blk],
        out_specs=blk,
        input_output_aliases={1: 0},
        name="pallas_probe")(s, a))
    return fn, (jax.ShapeDtypeStruct((4,), jnp.float32),
                jax.ShapeDtypeStruct((16, 128), jnp.float32))


def pallas_supported() -> bool:
    """Whether the suite's kernels run on this backend, decided ONCE.
    They are TPU-Pallas (pltpu memory spaces, TPU tiling), so every
    other backend answers False even where a generic Pallas kernel
    would compile (e.g. the GPU Triton lowering).  On a TPU the answer
    is True or an exception: a probe kernel the compiler refuses
    propagates with the compiler's message — a broken Pallas install
    must not turn the suite (flash attention included) into its lax
    and dense forms without a word.

    The probe is lowered from shapes and compiled, not called: the
    first ask may come from a layer body already under jit tracing,
    where a call would be inlined into the outer program and its
    refusal deferred to the outer compile."""
    global _PALLAS_OK
    if _PALLAS_OK is None:
        if jax.default_backend() == "tpu":
            fn, shapes = _probe()
            fn.lower(*shapes).compile()
            _PALLAS_OK = True
        else:
            _PALLAS_OK = False
    return _PALLAS_OK


def _use_pallas() -> bool:
    m = _mode()
    if m == "lax" or m == "off":
        return False
    if m == "pallas":
        # expert override: trust the caller (e.g. inside a shard_map
        # body, where the per-shard program is single-device again)
        return True
    # auto: pallas_call is not GSPMD-partitionable (the same
    # constraint that keeps flash attention off sharded meshes) — only
    # route to Pallas on a single-device topology; multi-device
    # programs get the lax forms, which XLA fuses and partitions.
    if len(jax.devices()) != 1:
        return False
    return pallas_supported()


def count_build(kernel: str, path: str) -> None:
    """Trace-time accounting: which kernels were built into the live
    programs, on which path (pallas|lax) — obs_report's kernel-suite
    row reads these."""
    from analytics_zoo_tpu.observability import get_registry
    get_registry().counter(
        "fused_kernel_builds_total",
        "fused-suite kernels built into traced programs",
        labels=("kernel", "path")).labels(kernel, path).inc()


# The record of the recomputed layer being traced, if any.
_kept_results = contextvars.ContextVar("kept_results", default=None)


@contextlib.contextmanager
def recording_kept_results():
    """While the body of a recomputed layer is traced: -> the dict that
    ``keep_result`` fills with the bytes under each name."""
    kept: dict = {}
    token = _kept_results.set(kept)
    try:
        yield kept
    finally:
        _kept_results.reset(token)


def keep_result(value, name: str):
    """A kernel's result under the name a recomputed layer's policy
    keeps it by (``jax.checkpoint(..., policy=save_only_these_names)``);
    the identity under no such policy."""
    kept = _kept_results.get()
    if kept is not None:
        kept[name] = kept.get(name, 0) + value.size * value.dtype.itemsize
    return checkpoint_name(value, name)


# Half of the 16 MiB scoped-VMEM limit the v5e compiler enforces on one
# kernel: the rest is headroom for what the model below does not count
# (the (1, d) operands, compiler-internal scratch).
_VMEM_BUDGET = 8 << 20
# Block-sized temporaries a kernel body keeps live besides its operand
# buffers — what the v5e compiler's allocation showed for the GeLU and
# LayerNorm bodies (a 3 MiB block asked for 17.9 MiB: 4 operand buffers
# + 2 temporaries).
_BODY_TEMPS = 2


def _row_block(rows: int, d: int, itemsize: int,
               n_blocked: int) -> Optional[int]:
    """Largest row block (a power of two ≥ 8 that divides ``rows``)
    whose VMEM footprint fits ``_VMEM_BUDGET``: each of the
    ``n_blocked`` (block, d) operands is double-buffered by the
    pipeline, plus ``_BODY_TEMPS`` block-sized temporaries.  None when
    even 8 rows do not fit (the caller takes the lax form)."""
    row_bytes = d * itemsize * (2 * n_blocked + _BODY_TEMPS)
    for br in (1024, 512, 256, 128, 64, 32, 16, 8):
        if rows % br == 0 and br * row_bytes <= _VMEM_BUDGET:
            return br
    return None


# ====================================================== optimizer update
# One pass over each leaf WHERE IT LIES: XLA compiles a leaf's update
# to one loop fusion in the leaf's own shape and tiling, its state
# aliased in place (tests/test_tpu_aot_compile.py holds that for every
# leaf shape the benchmark's cells have).  Keep it free of reshapes: on
# a TPU an array is tiled over its last two dimensions, so another
# shape is a copy of the leaf, not a view.
def _prepared_grad(p, g, clip_scale, clip_const, weight_decay: float):
    """Clip (global-norm scale or constant bounds), then weight decay
    added to the gradient, in optax's order."""
    if clip_scale is not None:
        g = g * clip_scale
    if clip_const:
        g = jnp.clip(g, *clip_const)
    if weight_decay:
        g = g + weight_decay * p
    return g


def _scaled(step_size, u, step_is_schedule: bool):
    return (jnp.array(step_size, dtype=u.dtype) * u if step_is_schedule
            else step_size * u)


def adam_leaf_update(p, g, mu, nu, *, b1: float, b2: float, eps: float,
                     step_size, bias_corr1, bias_corr2,
                     clip_scale=None, weight_decay: float = 0.0,
                     clip_const: Optional[Tuple[float, float]] = None,
                     step_is_schedule: bool = False):
    """One-leaf fused Adam step.  Reproduces
    ``scale_by_adam → scale_by_learning_rate → apply_updates``
    op-for-op; ``bias_corr* = 1 - beta**count_inc`` and ``step_size``
    (the NEGATIVE learning rate) are computed once by the caller.
    Returns ``(new_p, new_mu, new_nu)``."""
    count_build("fused_adam", "lax")
    g = _prepared_grad(p, g, clip_scale, clip_const, weight_decay)
    # optax.tree_update_moment order: (1-decay)*(g**order) + decay*t
    mu_n = (1.0 - b1) * g + b1 * mu
    nu_n = (1.0 - b2) * (g ** 2) + b2 * nu
    mh = mu_n / jnp.asarray(bias_corr1, mu_n.dtype)
    vh = nu_n / jnp.asarray(bias_corr2, nu_n.dtype)
    u = _scaled(step_size, mh / (jnp.sqrt(vh) + eps), step_is_schedule)
    return ((p + u).astype(p.dtype), mu_n, nu_n)


def sgd_leaf_update(p, g, trace, *, momentum: float, nesterov: bool,
                    step_size, clip_scale=None,
                    weight_decay: float = 0.0,
                    clip_const: Optional[Tuple[float, float]] = None,
                    step_is_schedule: bool = False):
    """One-leaf fused SGD(+momentum) step mirroring
    ``trace → scale`` + ``apply_updates``.  ``trace`` may be None
    (momentum 0).  Returns ``(new_p, new_trace_or_None)``."""
    count_build("fused_sgd", "lax")
    g = _prepared_grad(p, g, clip_scale, clip_const, weight_decay)
    if trace is not None:
        tr = g + momentum * trace           # optax.trace: f(g, t)
        u = g + momentum * tr if nesterov else tr
    else:
        tr, u = None, g
    u = _scaled(step_size, u, step_is_schedule)
    return (p + u).astype(p.dtype), tr


# ------------------------------------------------- optax state plumbing
def _optax_states():
    import optax
    return (optax.TraceState, optax.ScaleByAdamState,
            optax.ScaleByScheduleState)


def _map_states(node, fn):
    """Rebuild an optax state pytree, passing each known state object
    through ``fn`` WHOLE (no recursion into its trees)."""
    if isinstance(node, _optax_states()):
        return fn(node)
    if isinstance(node, tuple):
        if hasattr(node, "_fields"):
            return type(node)(*(_map_states(c, fn) for c in node))
        return tuple(_map_states(c, fn) for c in node)
    if isinstance(node, list):
        return [_map_states(c, fn) for c in node]
    if isinstance(node, dict):
        return {k: _map_states(v, fn) for k, v in node.items()}
    return node


def _collect_states(node, out):
    _map_states(node, lambda s: (out.append(s), s)[1])
    return out


def _safe_inc(count):
    # optax numerics.safe_int32_increment
    return jnp.where(count < jnp.iinfo(jnp.int32).max, count + 1, count)


def build_fused_update(optim, clip=None) -> Optional[Callable]:
    """Return ``update(grads, opt_state, params) -> (new_params,
    new_opt_state)`` fusing clip+moments+apply into one pass per leaf,
    or None when the (optimizer, clip) combination isn't supported —
    the trainer then keeps the optax triple pass.

    Supported: the repo's ``SGD`` (momentum/nesterov/weight_decay,
    float or schedule lr, dampening 0) and ``Adam`` (float or schedule
    lr incl. the Keras ``decay`` form) from
    ``pipeline/api/keras/optimizers.py``; ``clip`` is a trainer
    ``ClipSpec`` (const or l2norm) or None.  The optax state pytree
    structure is preserved exactly (checkpoints, shardings and
    ``init_opt_state`` are unaffected)."""
    import optax
    if optim is None or not fused_enabled():
        return None
    kind = type(optim).__name__
    kw = getattr(optim, "_init_kwargs", None)
    if kind not in ("SGD", "Adam") or kw is None:
        return None
    if kind == "SGD" and kw.get("dampening"):
        return None
    if clip is not None and clip.kind not in ("const", "l2norm"):
        return None
    lr = optim.learning_rate
    has_sched = callable(lr)

    # validate the state layout ONCE on a tiny dummy tree: anything
    # beyond {Trace|ScaleByAdam} + optional ScaleBySchedule + empties
    # means a transformation we don't reproduce — decline.
    probe = _collect_states(optim.tx.init({"w": np.zeros(8, np.float32)}),
                            [])
    traces = [s for s in probe if isinstance(s, optax.TraceState)]
    adams = [s for s in probe if isinstance(s, optax.ScaleByAdamState)]
    scheds = [s for s in probe
              if isinstance(s, optax.ScaleByScheduleState)]
    if kind == "Adam" and (len(adams) != 1 or traces):
        return None
    if kind == "SGD" and (adams or len(traces) > 1):
        return None
    if len(scheds) > (1 if has_sched else 0):
        return None
    has_trace = bool(traces)

    weight_decay = float(kw.get("weight_decay") or 0.0) \
        if kind == "SGD" else 0.0
    momentum = float(kw.get("momentum") or 0.0) if kind == "SGD" else 0.0
    nesterov = bool(kw.get("nesterov")) if kind == "SGD" else False
    b1 = float(kw.get("beta_1", 0.9)) if kind == "Adam" else 0.0
    b2 = float(kw.get("beta_2", 0.999)) if kind == "Adam" else 0.0
    eps = float(kw.get("epsilon", 1e-8)) if kind == "Adam" else 0.0
    clip_const = (float(clip.a), float(clip.b)) \
        if (clip is not None and clip.kind == "const") else None

    def update(grads, opt_state, params):
        # one read sweep for the global norm — the only pre-pass left
        clip_scale = None
        if clip is not None and clip.kind == "l2norm":
            gnorm = optax.global_norm(grads)
            clip_scale = jnp.minimum(1.0, clip.a / (gnorm + 1e-12))

        states = _collect_states(opt_state, [])
        sched_state = next((s for s in states if isinstance(
            s, optax.ScaleByScheduleState)), None)
        if has_sched:
            if sched_state is None:
                raise ValueError("schedule lr without schedule state")
            # scale_by_schedule: step_size = fn(count) PRE-increment
            step_size = -1 * lr(sched_state.count)
        else:
            step_size = -1 * float(lr)

        if kind == "Adam":
            st = next(s for s in states
                      if isinstance(s, optax.ScaleByAdamState))
            count_inc = _safe_inc(st.count)
            bc1 = 1 - b1 ** count_inc
            bc2 = 1 - b2 ** count_inc

            flat_p, treedef = jax.tree_util.tree_flatten(params)
            flat_g = treedef.flatten_up_to(grads)
            flat_m = treedef.flatten_up_to(st.mu)
            flat_v = treedef.flatten_up_to(st.nu)
            out = [adam_leaf_update(
                p, g, m, v, b1=b1, b2=b2, eps=eps,
                step_size=step_size, bias_corr1=bc1, bias_corr2=bc2,
                clip_scale=clip_scale, clip_const=clip_const,
                step_is_schedule=has_sched)
                for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
            new_params = jax.tree_util.tree_unflatten(
                treedef, [o[0] for o in out])
            new_mu = jax.tree_util.tree_unflatten(
                treedef, [o[1] for o in out])
            new_nu = jax.tree_util.tree_unflatten(
                treedef, [o[2] for o in out])

            def rebuild(s):
                if isinstance(s, optax.ScaleByAdamState):
                    return optax.ScaleByAdamState(
                        count=count_inc, mu=new_mu, nu=new_nu)
                if isinstance(s, optax.ScaleByScheduleState):
                    return optax.ScaleByScheduleState(
                        count=_safe_inc(s.count))
                return s
            return new_params, _map_states(opt_state, rebuild)

        # SGD
        trace_state = next(
            (s for s in states if isinstance(s, optax.TraceState)),
            None) if has_trace else None
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = treedef.flatten_up_to(grads)
        flat_t = (treedef.flatten_up_to(trace_state.trace)
                  if trace_state is not None
                  else [None] * len(flat_p))
        out = [sgd_leaf_update(
            p, g, t, momentum=momentum, nesterov=nesterov,
            step_size=step_size, clip_scale=clip_scale,
            weight_decay=weight_decay, clip_const=clip_const,
            step_is_schedule=has_sched)
            for p, g, t in zip(flat_p, flat_g, flat_t)]
        new_params = jax.tree_util.tree_unflatten(
            treedef, [o[0] for o in out])
        new_trace = (jax.tree_util.tree_unflatten(
            treedef, [o[1] for o in out])
            if trace_state is not None else None)

        def rebuild(s):
            if isinstance(s, optax.TraceState):
                return optax.TraceState(trace=new_trace)
            if isinstance(s, optax.ScaleByScheduleState):
                return optax.ScaleByScheduleState(
                    count=_safe_inc(s.count))
            return s
        return new_params, _map_states(opt_state, rebuild)

    return update


# ====================================================== epilogue kernels
# Activations the LayerNorm epilogue runs in-kernel: the ones whose
# primitives Mosaic lowers on the installed jaxlib.  erf/erfc
# (``gelu_erf``) and expm1 (``elu``, ``selu``) are not, so those take
# the lax form.  tests/test_tpu_aot_compile.py compiles every member
# for the v5e.
_PALLAS_ACTIVATIONS = frozenset((
    acts.relu, acts.relu6, acts.tanh, acts.sigmoid, acts.hard_sigmoid,
    acts.hard_sigmoid_torch, acts.hard_swish, acts.softmax,
    acts.log_softmax, acts.softplus, acts.softsign, acts.gelu,
    acts.swish, acts.exp))


def _epilogue_row_block(x, d: int) -> Optional[int]:
    """Row block of the (rows, d) layout for an epilogue-eligible
    activation; None = lax.  The last dim must be a 128-lane multiple,
    the collapsed leading dims an 8-sublane multiple (f32 tile), and a
    row block must fit the VMEM budget: x in and y out are the two
    blocked operands."""
    if x.dtype != jnp.float32 or x.ndim < 2 or d % 128:
        return None
    rows = int(np.prod(x.shape[:-1]))
    if rows % 8:
        return None
    return _row_block(rows, d, x.dtype.itemsize, n_blocked=2)


def _row_spec(br: int, d: int):
    return pl.BlockSpec((br, d), lambda i: (i, 0))


def _vec_spec(d: int):
    return pl.BlockSpec((1, d), lambda i: (0, 0))


def _bias_gelu_kernel(x_ref, b_ref, o_ref):
    o_ref[:] = jax.nn.gelu(x_ref[:] + b_ref[:], approximate=True)


def _bias_gelu_lax(x, bias, approximate: bool = True):
    return jax.nn.gelu(x + bias, approximate=approximate)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bias_gelu_pallas(x, bias, br: int, interpret: bool):
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    out = pl.pallas_call(
        _bias_gelu_kernel,
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        grid=(xr.shape[0] // br,),
        in_specs=[_row_spec(br, d), _vec_spec(d)],
        out_specs=_row_spec(br, d),
        interpret=interpret,
        name="bias_gelu",
    )(xr, bias.reshape(1, d))
    return out.reshape(x.shape)


def _bias_gelu_fwd(x, bias, br, interpret):
    return _bias_gelu_pallas(x, bias, br, interpret), (x, bias)


def _bias_gelu_bwd(br, interpret, res, g):
    # the lax form's own derivative: XLA fuses it into one pass
    return jax.vjp(_bias_gelu_lax, *res)[1](g)


_bias_gelu_pallas.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def bias_gelu(x, bias, approximate: bool = True,
              interpret: bool = False):
    """Fused bias-add→GeLU epilogue (the dense/FFN tail).  Lax path is
    literally ``gelu(x + bias)`` — identical numerics to the unfused
    call sites it replaces.  The kernel is the tanh form only: erf has
    no Mosaic lowering, so ``approximate=False`` is always lax."""
    d = x.shape[-1]
    br = _epilogue_row_block(x, d) if approximate else None
    if (interpret or _use_pallas()) and br is not None \
            and bias.shape == (d,) and bias.dtype == x.dtype:
        count_build("bias_gelu", "pallas")
        return _bias_gelu_pallas(x, bias, br, interpret)
    count_build("bias_gelu", "lax")
    return _bias_gelu_lax(x, bias, approximate)


def _layernorm_act_kernel(x_ref, g_ref, b_ref, o_ref, *, eps: float,
                          activation):
    x = x_ref[:]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    y = y * g_ref[:] + b_ref[:]
    if activation is not None:
        y = activation(y)
    o_ref[:] = y.astype(o_ref.dtype)


def _layernorm_act_lax(x, gamma, beta, eps: float, activation):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + eps)
    y = (y * gamma + beta).astype(x.dtype)
    if activation is not None:
        y = activation(y)
    return y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _layernorm_act_pallas(x, gamma, beta, eps: float, activation,
                          br: int, interpret: bool):
    d = x.shape[-1]
    xr = x.reshape(-1, d)
    out = pl.pallas_call(
        functools.partial(_layernorm_act_kernel, eps=eps,
                          activation=activation),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        grid=(xr.shape[0] // br,),
        in_specs=[_row_spec(br, d), _vec_spec(d), _vec_spec(d)],
        out_specs=_row_spec(br, d),
        interpret=interpret,
        name="layernorm_act",
    )(xr, gamma.reshape(1, d), beta.reshape(1, d))
    return out.reshape(x.shape)


def _layernorm_act_fwd(x, gamma, beta, eps, activation, br, interpret):
    out = _layernorm_act_pallas(x, gamma, beta, eps, activation, br,
                                interpret)
    return out, (x, gamma, beta)


def _layernorm_act_bwd(eps, activation, br, interpret, res, g):
    lax_form = functools.partial(_layernorm_act_lax, eps=eps,
                                 activation=activation)
    return jax.vjp(lax_form, *res)[1](g)


_layernorm_act_pallas.defvjp(_layernorm_act_fwd, _layernorm_act_bwd)


def layernorm_act(x, gamma, beta, eps: float = 1e-5,
                  activation: Optional[Callable] = None,
                  interpret: bool = False):
    """Fused LayerNorm→activation.  Lax path mirrors
    ``layers.normalization.LayerNorm.call`` exactly (biased variance,
    same op order) followed by the activation."""
    d = x.shape[-1]
    br = _epilogue_row_block(x, d) if (
        activation is None or activation in _PALLAS_ACTIVATIONS) else None
    if (interpret or _use_pallas()) and br is not None \
            and gamma.shape == (d,) and gamma.dtype == x.dtype:
        count_build("layernorm_act", "pallas")
        return _layernorm_act_pallas(x, gamma, beta, eps, activation,
                                     br, interpret)
    count_build("layernorm_act", "lax")
    return _layernorm_act_lax(x, gamma, beta, eps, activation)

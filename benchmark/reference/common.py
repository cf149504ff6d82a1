"""What the plain references share: the operand hook through which the
control lowers the precision, the losses, the two optimizers, and the
per-leaf norms that the comparison reads.

Nothing here imports the program.  Everything is float32 with matrix
products at ``highest`` precision (on a TPU a float32 product otherwise
runs in bfloat16 passes).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# float8 e4m3 as IEEE lays it out (4 exponent bits, 3 of mantissa): its
# largest finite value is 240
FP8_BITS = (4, 3)
FP8_MAX = 240.0


def round_fp8(a):
    """Round to float8 (e4m3) under a per-tensor scale, as fp8 training
    recipes do, and return float32 again.  ``reduce_precision`` rounds
    exactly as a conversion would and compiles to one cheap operation
    (a conversion through ``float8_e4m3fn`` took the v5e compiler 14
    minutes for the ResNet step)."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    return jax.lax.reduce_precision(a / scale, *FP8_BITS) * scale


def round_bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


ROUNDINGS: Dict[str, Callable] = {"fp8": round_fp8, "bf16": round_bf16}


def product(f: Callable, rounding: Optional[str]):
    """``f(x, w)`` (a matrix product or a convolution) with both
    operands, and in the backward pass the cotangent too, rounded to the
    control's precision.  ``rounding=None`` is the reference itself."""
    if rounding is None:
        return f
    q = ROUNDINGS[rounding]

    @jax.custom_vjp
    def op(x, w):
        return f(q(x), q(w))

    def fwd(x, w):
        xq, wq = q(x), q(w)
        return f(xq, wq), (xq, wq)

    def bwd(res, g):
        xq, wq = res
        _, vjp = jax.vjp(f, xq, wq)
        return vjp(q(g))

    op.defvjp(fwd, bwd)
    return op


def matmul(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def sparse_cross_entropy(logits, labels):
    """Mean over rows of -log softmax(logits)[label]."""
    lsm = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(lsm, labels.reshape(-1, 1), axis=-1)
    return -jnp.mean(ll)


# --------------------------------------------------------------- optimizers
def learning_rate(opt: Dict, step):
    """The configuration's schedule at 0-based ``step``."""
    sched = opt.get("schedule")
    if sched is None:
        return jnp.float32(opt["learning_rate"])
    if sched["kind"] == "warmup_constant":
        t = jnp.asarray(step, jnp.float32)
        return sched["base"] * jnp.minimum(t / sched["warmup_iterations"], 1.0)
    if sched["kind"] == "warmup_poly":
        base, warm = sched["base"], sched["warmup_iterations"]
        t = jnp.asarray(step, jnp.float32)
        ramp = base * t / warm
        frac = jnp.clip((t - warm) / sched["max_iteration"], 0.0, 1.0)
        decay = base * (1.0 - frac) ** sched["power"]
        return jnp.where(t < warm, ramp, decay)
    raise ValueError(f"unknown schedule {sched!r}")


def optimizer_init(opt: Dict, params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    if opt["kind"] == "sgd":
        return {"moment": zeros}
    if opt["kind"] == "adam":
        return {"moment": zeros,
                "second": jax.tree_util.tree_map(jnp.zeros_like, params)}
    raise ValueError(f"unknown optimizer {opt['kind']!r}")


def optimizer_update(opt: Dict, params, grads, state, step):
    """One update at 0-based ``step``; returns (params, state)."""
    lr = learning_rate(opt, step)
    tm = jax.tree_util.tree_map
    if opt["kind"] == "sgd":
        mom = opt["momentum"]
        moment = tm(lambda v, g: g + mom * v, state["moment"], grads)
        return (tm(lambda p, v: p - lr * v, params, moment),
                {"moment": moment})
    b1, b2, eps = opt["beta_1"], opt["beta_2"], opt["epsilon"]
    t = jnp.asarray(step, jnp.float32) + 1.0
    moment = tm(lambda m, g: b1 * m + (1 - b1) * g, state["moment"], grads)
    second = tm(lambda v, g: b2 * v + (1 - b2) * g * g,
                state["second"], grads)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new = tm(lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
             params, moment, second)
    return new, {"moment": moment, "second": second}


def leaf_norms(tree) -> Dict[str, float]:
    """L2 norm of every leaf of a flat ``{name: array}`` tree, read to
    the host in one transfer."""
    names = sorted(tree)
    norms = jax.device_get(_norms([tree[n] for n in names]))
    return {n: float(v) for n, v in zip(names, norms)}


@jax.jit
def _norms(leaves):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for a in leaves])


def follow(step_fn: Callable, opt: Dict, params, batches, moment_after: int,
           moving: Optional[Dict] = None):
    """Drive ``step_fn(params, opt_state, batch, step) -> (params,
    opt_state, loss, grads)`` over ``batches`` from ``params``.

    Returns what the comparison reads: each step's loss, the per-leaf
    norm of the first step's gradient, of the optimizer's first moment
    after ``moment_after`` steps, and of the parameters' change after
    all of them.  ``moving`` is a model's non-trained state (BatchNorm's
    moving statistics), which the step carries under that key; its
    per-leaf change is read at the end too."""
    start = params
    state = optimizer_init(opt, params)
    if moving is not None:
        state["moving"] = moving
        moving_start = {k: jnp.array(v, copy=True) for k, v in moving.items()}
    losses, grad1, moment = [], None, None
    for i, batch in enumerate(batches):
        params, state, loss, grads = step_fn(params, state, batch,
                                             jnp.int32(i))
        losses.append(loss)
        if i == 0:
            grad1 = leaf_norms(grads)
        del grads
        if i + 1 == moment_after:
            moment = leaf_norms(state["moment"])
    change = leaf_norms({k: params[k] - start[k] for k in params})
    out = {"loss": [float(v) for v in jax.device_get(losses)],
           "grad1_norm": grad1, "moment_norm": moment,
           "dparam_norm": change}
    if moving is not None:
        out["dstate_norm"] = leaf_norms(
            {k: v - moving_start[k] for k, v in state["moving"].items()})
    return out

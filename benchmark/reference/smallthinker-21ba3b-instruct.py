"""Plain reference for the ``smallthinker-21ba3b-instruct`` configuration.

One expert-parallel rank of SmallThinker-21BA3B-Instruct (PowerInfer;
arXiv:2507.20984) under next-token supervised fine-tuning.  Float32
with every product at ``highest`` precision: the layers' equations, the
loss, its gradients and the Adam step, with dense attention by blocks
of queries under the layer's mask and the experts as a masked dense
sum.  No kernels, nothing of the program.

One layer ``l``, with ``h`` the residual stream (2560) and ``rms(x, g)
= x / sqrt(mean(x^2) + 1e-6) * g``:

* ``a = rms(h, g1)``; the router reads ``a``: ``p = softmax(a Wr)`` over
  all 64 experts, ``S`` the 6 largest, ``w_e = p_e / sum_S p``.
* ``q = a Wq`` (28 heads of 128), ``k = a Wk``, ``v = a Wv`` (4 heads of
  128; query head ``i`` reads K/V head ``i // 7``).  Where
  ``rope_layout[l]`` is 1, ``q`` and ``k`` are rotated (rotate-half:
  column ``i`` with ``i + 64``, base 1.5e6, positions 0 .. T-1); where 0,
  nothing encodes positions.  Query ``i`` reads keys ``j <= i``, and
  where ``sliding_window_layout[l]`` is 1 only those with ``i - j <
  4096``.  ``h += [softmax(q_h k^T / sqrt(128) + mask) v]_h Wo``.  No
  bias, no q/k norm.
* ``m = rms(h, g2)``; ``h += sum_{e in S, e held} w_e (relu(m Wg_e) * (m
  Wu_e)) Wd_e`` (768-wide ReGLU experts; no shared expert).
* loss ``= mean_{t < T-1} CE(head(rms(h_t, gf)), id_{t+1})`` over the
  held slice of the vocabulary; no auxiliary term.

Departures from the published model, each the configuration file's
(``reduced``, ``assumed``): layers 0-3 of the 52 (one global layer and
three windowed, one period of the layout); the 16 routed experts of
rank 0 of a 4-way expert-parallel group (the router keeps its 64
outputs and its 6 experts a token, and what the 48 absent experts would
have added is left out, here as in the program); ids 0-37,983 of the
151,936.

The control (``rounding``) rounds the operands of every product the
configuration states in bfloat16; the router's product, stated in
float32, is not rounded.

A batch is taken one sequence at a time; each layer is recomputed in
the backward pass, attention goes by blocks of queries (a windowed
layer's block reads the keys of its band, a global layer's all keys,
each under the dense mask), the held experts one at a time, and the
step updates its state in place, so that float32 activations fit the
chip after the program is freed.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common

QUERY_ROWS = 256
LOSS_ROWS = 1024


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, h, kv, hd = _sizes(cfg)
    e_all, e = cfg["moe_num_primary_experts_published"], \
        cfg["experts_held"][1]
    f, ids = cfg["moe_ffn_hidden_size"], cfg["vocab_held"][1]
    out = [("embed", (ids, d), "normal")]
    for l in range(cfg["num_hidden_layers"]):
        p = f"l{l}"
        out += [(f"{p}.ln1.gamma", (d,), "one"),
                (f"{p}.attn.q", (d, h * hd), "normal"),
                (f"{p}.attn.k", (d, kv * hd), "normal"),
                (f"{p}.attn.v", (d, kv * hd), "normal"),
                (f"{p}.attn.o", (h * hd, d), "normal"),
                (f"{p}.ln2.gamma", (d,), "one"),
                (f"{p}.moe.router", (d, e_all), "normal"),
                (f"{p}.moe.gate", (e, d, f), "normal"),
                (f"{p}.moe.up", (e, d, f), "normal"),
                (f"{p}.moe.down", (e, f, d), "normal")]
    out += [("norm_f.gamma", (d,), "one"), ("head", (d, ids), "normal")]
    return out


def param_order(cfg: Dict) -> List[str]:
    return [name for name, _, _ in _spec(cfg)]


def init(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """All weights in one jitted call from the seed: N(0, 0.02) for
    every matrix, unit gains."""
    spec = _spec(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, shape, kind) in enumerate(spec):
            if kind == "normal":
                params[name] = cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                params[name] = jnp.ones(shape, jnp.float32)
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _rotary(x, base):
    """Rotate-half: x (T, heads, D) at positions 0 .. T-1, column ``i``
    turned with column ``i + D / 2`` by ``position * base^(-i / (D /
    2))``."""
    t, _, dim = x.shape
    half = dim // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg: Dict, mm, ps, a, layer: int):
    _, h, kv, hd = _sizes(cfg)
    t, group = a.shape[0], h // kv
    q = mm(a, ps["attn.q"]).reshape(t, h, hd)
    k = mm(a, ps["attn.k"]).reshape(t, kv, hd)
    value = mm(a, ps["attn.v"]).reshape(t, kv, hd)
    if cfg["rope_layout"][layer]:
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][layer] else None
    rows = min(QUERY_ROWS, t)
    # a block of queries reads the keys of its band: all of them under
    # the causal mask, the window and the block's own rows under the
    # window (the mask is evaluated on every pair read)
    span = t if window is None else min(t, window + rows)
    q = q.reshape(t, kv, group, hd).transpose(1, 2, 0, 3)   # (kv, g, T, hd)
    k_t = k.transpose(1, 2, 0)                               # (kv, hd, T)
    value = value.transpose(1, 0, 2)                         # (kv, T, hd)

    @jax.checkpoint
    def block(q_blk, first):
        start = jnp.clip(first + rows - span, 0, t - span)
        k_blk = jax.lax.dynamic_slice_in_dim(k_t, start, span, axis=2)
        v_blk = jax.lax.dynamic_slice_in_dim(value, start, span, axis=1)
        scores = mm(q_blk, k_blk[:, None]) / jnp.sqrt(jnp.float32(hd))
        i = first + jnp.arange(rows)[:, None]
        j = start + jnp.arange(span)[None, :]
        allowed = j <= i
        if window is not None:
            allowed = allowed & (i - j < window)
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return mm(probs, v_blk[:, None])          # (kv, g, rows, hd)

    ctx = jax.lax.map(
        lambda qf: block(*qf),
        (q.reshape(kv, group, t // rows, rows, hd).transpose(2, 0, 1, 3, 4),
         jnp.arange(0, t, rows)))
    # (blocks, kv, g, rows, hd) -> (T, heads in order, hd)
    ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(t, h * hd)
    return mm(ctx, ps["attn.o"])


def route(cfg: Dict, ps, a):
    """(gates, picked), each (T, 6): the softmax router over all 64
    experts on the attention input.  Stated in float32: the control
    does not round it."""
    probs = jax.nn.softmax(common.matmul(a, ps["moe.router"]), axis=-1)
    gates, picked = jax.lax.top_k(probs, cfg["moe_num_active_primary_experts"])
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, picked


def experts(cfg: Dict, mm, ps, gates, picked, m):
    """The held routed experts' part of the sum, ReGLU."""
    first, e_held = cfg["experts_held"]

    @jax.checkpoint
    def one(y, e):
        w = jnp.sum(jnp.where(picked == first + e, gates, 0.0), axis=-1)
        hidden = jax.nn.relu(mm(m, ps["moe.gate"][e])) \
            * mm(m, ps["moe.up"][e])
        return y + w[:, None] * mm(hidden, ps["moe.down"][e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(e_held))
    return y


def forward(cfg: Dict, params, ids, mm=common.matmul):
    """The final hidden states (T, d) of one sequence, after the last
    norm; ``mm`` is the matrix product (the control's rounds its
    operands)."""
    eps = cfg["rms_norm_eps"]
    h = params["embed"][ids - cfg["vocab_held"][0]]
    for l in range(cfg["num_hidden_layers"]):
        p = f"l{l}."
        ps = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(ps, h, l=l):
            a = _rms(h, ps["ln1.gamma"], eps)
            gates, picked = route(cfg, ps, a)
            h = h + attention(cfg, mm, ps, a, l)
            m = _rms(h, ps["ln2.gamma"], eps)
            return h + experts(cfg, mm, ps, gates, picked, m)

        h = layer(ps, h)
    return _rms(h, params["norm_f.gamma"], eps)


def sequence_loss(cfg: Dict, params, ids, rounding: Optional[str] = None,
                  fault: Optional[str] = None):
    """The loss of one record (its int32 row of ``seq_len`` ids)."""
    mm = common.product(common.matmul, rounding)
    h = forward(cfg, params, ids, mm)
    t = ids.shape[0]
    targets = jnp.roll(ids, -1) - cfg["vocab_held"][0]
    weights = (jnp.arange(t) < t - 1).astype(jnp.float32) / (t - 1)
    if fault == "half_batch":
        # half of the step's work left out: a batch is one sequence,
        # so it is the second half of its loss positions
        weights = jnp.where(jnp.arange(t) < t // 2, weights, 0.0)
    rows = LOSS_ROWS if t % LOSS_ROWS == 0 else t

    @jax.checkpoint
    def chunk(h_c, targets_c):
        lsm = jax.nn.log_softmax(mm(h_c, params["head"]), axis=-1)
        return -jnp.take_along_axis(lsm, targets_c[:, None], axis=-1)[:, 0]

    nll = jax.lax.map(lambda a: chunk(*a),
                      (h.reshape(t // rows, rows, -1),
                       targets.reshape(t // rows, rows)))
    return jnp.sum(nll.reshape(t) * weights)


def prepare(cfg: Dict, stages: List[Dict], x):
    if stages:
        raise ValueError(f"unknown stages {stages!r}")
    return x


@functools.lru_cache(maxsize=None)
def _step(cfg_key, rounding, fault):
    cfg = json.loads(cfg_key)
    opt = cfg["optimizer"]

    def step(params, state, rows, i):
        """One update on ``rows`` (batch, seq_len); returns the per-leaf
        norms of the gradient (leaves in sorted order) in the gradient's
        place, so that no second copy of it outlives the update."""
        n = rows.shape[0]
        loss, grads = jnp.float32(0.0), None
        with jax.default_matmul_precision("highest"):
            for row in rows:
                l, g = jax.value_and_grad(
                    lambda p: sequence_loss(cfg, p, row, rounding,
                                            fault) / n)(params)
                loss = loss + l
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(grads[k])))
                           for k in sorted(grads)])
        new, state = common.optimizer_update(opt, params, grads, state, i)
        return new, state, loss, norms

    return jax.jit(step, donate_argnums=(0, 1))


def follow(cfg: Dict, seed: int, batches, moment_after: int,
           rounding: Optional[str] = None, fault: Optional[str] = None):
    """The first ``len(batches)`` training steps from the seed's weights
    on ``batches`` (each ``((ids, unused), labels)``), and what the
    comparison reads of them (as ``common.follow`` gives it).  The
    parameters and the optimizer's state are updated in place and the
    seed's weights made a second time at the end: 657 M parameters with
    Adam's state are 7.9 GB, and a kept copy of the start and of each
    gradient beside them does not fit the chip."""
    step = _step(json.dumps(cfg, sort_keys=True), rounding, fault)
    params = init(cfg, seed)
    names = sorted(params)
    state = common.optimizer_init(cfg["optimizer"], params)
    losses, grad1, moment = [], None, None
    for i, (x, _) in enumerate(batches):
        params, state, loss, norms = step(
            params, state, jnp.asarray(x[0], jnp.int32), jnp.int32(i))
        losses.append(loss)
        if i == 0:
            grad1 = dict(zip(names, map(float, jax.device_get(norms))))
        if i + 1 == moment_after:
            moment = common.leaf_norms(state["moment"])
    del state
    start = init(cfg, seed)
    change = common.leaf_norms({k: params[k] - start[k] for k in names})
    return {"loss": [float(v) for v in jax.device_get(losses)],
            "grad1_norm": grad1, "moment_norm": moment,
            "dparam_norm": change}

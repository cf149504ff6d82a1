"""Plain reference for the ``phi-4-mini-flash-reasoning`` configuration.

One vocabulary-parallel rank of Phi-4-mini-flash-reasoning (Microsoft;
``model_type: phi4flash``; Ren et al. 2025, arXiv:2507.06607) under
next-token fine-tuning.  Float32 at ``highest`` precision: the layers'
equations, the loss, its gradients and the Adam step.  The scan is a
sequential ``lax.scan`` over positions, attention a dense masked softmax
over blocks of query rows.  No kernels, nothing of the program.

``d`` = 2,560; ``LN(x) = (x - mean) / sqrt(var + 1e-5) * g + b``.  Layer
``l`` (its PUBLISHED index): ``h = h + Mixer_l(LN1(h))``, then ``h = h +
MLP(LN2(h))``; after the last, ``LNf``; logits ``= h E^T`` with ``E`` the
embedding (tied, no bias); loss = mean over the ``T - 1`` predicted
positions of ``CE(logits_t, id_{t+1})``.

* MLP: ``[g, u] = x W1``; ``y = (u * silu(g)) W2``; no bias.
* Mamba (even ``l <= 16``): ``[x, z] = u W_in``; ``x = silu(conv(x) +
  b_conv)``, ``conv(x)_t[c] = sum_k w[k, c] x_{t-3+k}[c]``; ``[dt_r, B,
  C] = x W_x``; ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t[c, n] = exp(dt_t[c] A[c, n]) s_{t-1}[c, n] + dt_t[c] B_t[n]
  x_t[c]``; ``y_t[c] = sum_n s_t[c, n] C_t[n] + D[c] x_t[c]``; out ``= (y
  * silu(z)) W_out``.  Layer 16's ``y`` is the memory ``M``.
* Differential attention (odd ``l``): ``[q, k, v] = x W_in + b_in`` (40,
  20 and 20 heads of 64).  Query heads ``2j, 2j+1`` are ``q1_j, q2_j``;
  K/V heads ``2i, 2i+1`` are ``k1_i, k2_i``, ``v1_i, v2_i``; pair ``j``
  reads K/V pair ``j // 2``; ``V_i = [v1_i, v2_i]``.  ``a1 = softmax(q1
  k1^T / 8 + mask) V``, ``a2 = softmax(q2 k2^T / 8 + mask) V``; ``lam =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3
  l)``; ``out_j = rms_128(a1 - lam a2) * g * (1 - lam0)`` (``rms`` with
  eps 1e-5, one gain ``g`` of 128); the pairs side by side, ``W_o + b_o``.
  Mask: ``l < 17`` query ``i`` reads keys ``i - 511 .. i``; ``l = 17``
  keys ``0 .. i``.
* Cross-attention (odd ``l >= 19``): the same with ``q = x W_in + b_in``
  alone and layer 17's ``k`` and ``v``; keys ``0 .. i``.
* Gated memory unit (even ``l >= 18``): ``out = (silu(x W1) * M) W2``.

Departures from the published model, each the configuration file's
(``reduced``, ``assumed``):

* 6 of the 32 layers, those at the published depths the file lists
  (``layer_ids_published``: one of every kind, each with its own depth's
  ``lam0``); ids 0-25,007 of the 200,064 (embedding and tied head hold
  that slice; ids, logits and loss are over it);
* the published config gives no state-space sizes, no pairing of the
  differential heads, no ``lam0``, no reading of ``sliding_window`` and
  no initializer: all are the file's ``assumed``;
* no dropout (the published rates are 0) and no positional encoding of
  any kind (the published config has none).

A batch is taken one sequence at a time, the gradients summed; each
layer is recomputed in the backward pass, the scan by chunks of
positions, attention by blocks of queries, the loss by chunks of rows,
and the step updates its state in place, so that float32 activations fit
the chip beside 11 GB of state after the program is freed.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common

QUERY_ROWS = 512
SCAN_CHUNK = 128
LOSS_ROWS = 1024


def kind_of(index: int, published: int) -> str:
    half = published // 2
    if index % 2 == 0:
        return "mamba" if index <= half else "memory_unit"
    return "attention" if index <= half + 1 else "cross_attention"


def _sizes(cfg: Dict):
    m = cfg["mamba"]
    return (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], m["d_inner"], m["d_state"], m["d_conv"],
            m["dt_rank"], cfg["vocab_held"][1])


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, ff, h, hkv, hd, c, n, kc, r, v = _sizes(cfg)
    out = [("embed", (v, d), "normal")]
    for l in cfg["layer_ids_published"]:
        p, kind = f"l{l}", kind_of(l, cfg["num_hidden_layers_published"])
        out += [(f"{p}.ln1.gamma", (d,), "one"), (f"{p}.ln1.beta", (d,), "zero")]
        if kind == "mamba":
            out += [(f"{p}.mamba.in", (d, 2 * c), "normal"),
                    (f"{p}.mamba.conv", (kc, c), "conv"),
                    (f"{p}.mamba.conv_bias", (c,), "zero"),
                    (f"{p}.mamba.x", (c, r + 2 * n), "normal"),
                    (f"{p}.mamba.dt", (r, c), "normal"),
                    (f"{p}.mamba.dt_bias", (c,), "dt_bias"),
                    (f"{p}.mamba.a_log", (c, n), "a_log"),
                    (f"{p}.mamba.d", (c,), "one"),
                    (f"{p}.mamba.out", (c, d), "normal")]
        elif kind == "memory_unit":
            out += [(f"{p}.gmu.in", (d, c), "normal"),
                    (f"{p}.gmu.out", (c, d), "normal")]
        else:
            width = h * hd if kind == "cross_attention" \
                else (h + 2 * hkv) * hd
            out += [(f"{p}.attn.in", (d, width), "normal"),
                    (f"{p}.attn.in_bias", (width,), "zero"),
                    (f"{p}.attn.lq1", (hd,), "lambda"),
                    (f"{p}.attn.lk1", (hd,), "lambda"),
                    (f"{p}.attn.lq2", (hd,), "lambda"),
                    (f"{p}.attn.lk2", (hd,), "lambda"),
                    (f"{p}.attn.subln", (2 * hd,), "one"),
                    (f"{p}.attn.out", (h * hd, d), "normal"),
                    (f"{p}.attn.out_bias", (d,), "zero")]
        out += [(f"{p}.ln2.gamma", (d,), "one"), (f"{p}.ln2.beta", (d,), "zero"),
                (f"{p}.mlp.w1", (d, 2 * ff), "normal"),
                (f"{p}.mlp.w2", (ff, d), "normal")]
    out += [("norm_f.gamma", (d,), "one"), ("norm_f.beta", (d,), "zero")]
    return out


def param_order(cfg: Dict) -> List[str]:
    return [name for name, _, _ in _spec(cfg)]


def init(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """All weights in one jitted call from the seed (the file's
    ``assumed.init``)."""
    spec, f32 = _spec(cfg), jnp.float32

    @jax.jit
    def make(key):
        params = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                a = cfg["initializer_range"] * jax.random.normal(k, shape, f32)
            elif kind == "lambda":
                a = 0.1 * jax.random.normal(k, shape, f32)
            elif kind == "conv":
                bound = 1.0 / math.sqrt(shape[0])
                a = jax.random.uniform(k, shape, f32, -bound, bound)
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, f32, math.log(1e-3), math.log(1e-1)))
                a = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "a_log":
                a = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)), shape)
            else:
                a = jnp.full(shape, 1.0 if kind == "one" else 0.0, f32)
            params[name] = a
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _ln(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def scan(x, dt, a, b, c):
    """The recurrence, one position after another; recomputed by chunks
    of positions in the backward pass (a kept state a position would be
    2.7 GB)."""
    t, ch = x.shape
    chunk = SCAN_CHUNK if t % SCAN_CHUNK == 0 else t

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], axis=1)

    @jax.checkpoint
    def piece(s, xs):
        return jax.lax.scan(step, s, xs)

    _, y = jax.lax.scan(
        piece, jnp.zeros((ch, a.shape[1]), jnp.float32),
        tuple(v.reshape(t // chunk, chunk, -1) for v in (x, dt, b, c)))
    return y.reshape(t, ch)


def mamba(cfg: Dict, mm, ps, u):
    """-> (the mixer's output, the scan's output before the gate)."""
    *_, n, kc, r, _ = _sizes(cfg)
    t = u.shape[0]
    x, z = jnp.split(mm(u, ps["mamba.in"]), 2, axis=-1)
    pad = jnp.pad(x, ((kc - 1, 0), (0, 0)))
    x = sum(pad[k:k + t] * ps["mamba.conv"][k] for k in range(kc)) \
        + ps["mamba.conv_bias"]
    x = jax.nn.silu(x)
    dt_r, b, c = jnp.split(mm(x, ps["mamba.x"]), [r, r + n], axis=-1)
    dt = jax.nn.softplus(mm(dt_r, ps["mamba.dt"]) + ps["mamba.dt_bias"])
    y = scan(x, dt, -jnp.exp(ps["mamba.a_log"]), b, c) + ps["mamba.d"] * x
    return mm(y * jax.nn.silu(z), ps["mamba.out"]), y


def allowed(t: int, window: Optional[int]):
    """The (t, t) boolean mask: query i may read key j."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = j <= i
    return ok if window is None else ok & (j > i - window)


def attention(cfg: Dict, mm, ps, x, index: int, window: Optional[int],
              kv=None):
    """-> (the mixer's output, (k, v) as projected)."""
    _, _, h, hkv, hd, *_ = _sizes(cfg)
    t, eps = x.shape[0], cfg["layer_norm_eps"]
    proj = mm(x, ps["attn.in"]) + ps["attn.in_bias"]
    if kv is None:
        q, k, v = jnp.split(proj, [h * hd, (h + hkv) * hd], axis=-1)
    else:
        q, (k, v) = proj, kv
    pairs, kv_pairs = h // 2, hkv // 2
    group = pairs // kv_pairs
    # (K/V pair, pairs of the group, which of the two, T, D)
    q_p = q.reshape(t, kv_pairs, group, 2, hd).transpose(1, 2, 3, 0, 4)
    k_t = k.reshape(t, kv_pairs, 2, hd).transpose(1, 2, 3, 0)[:, None]
    v_p = v.reshape(t, kv_pairs, 2 * hd).transpose(1, 0, 2)[:, None, None]
    mask = allowed(t, window)
    rows = min(QUERY_ROWS, t)

    @jax.checkpoint
    def block(q_blk, mask_blk):
        scores = mm(q_blk, k_t) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(mask_blk, scores, -jnp.inf), -1)
        return mm(probs, v_p)              # (kv pairs, group, 2, rows, 2 D)

    maps = jax.lax.map(
        lambda qm: block(*qm),
        (q_p.reshape(kv_pairs, group, 2, t // rows, rows, hd
                     ).transpose(3, 0, 1, 2, 4, 5),
         mask.reshape(t // rows, rows, t)))
    # -> (T, pair, which, 2 D)
    maps = maps.transpose(0, 4, 1, 2, 3, 5).reshape(t, pairs, 2, 2 * hd)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(ps["attn.lq1"] * ps["attn.lk1"])) \
        - jnp.exp(jnp.sum(ps["attn.lq2"] * ps["attn.lk2"])) + lam0
    diff = maps[:, :, 0] - lam * maps[:, :, 1]
    diff = diff * jax.lax.rsqrt(
        jnp.mean(jnp.square(diff), axis=-1, keepdims=True) + eps) \
        * ps["attn.subln"] * (1.0 - lam0)
    out = mm(diff.reshape(t, h * hd), ps["attn.out"]) + ps["attn.out_bias"]
    return out, (k, v)


def memory_unit(mm, ps, x, memory):
    return mm(jax.nn.silu(mm(x, ps["gmu.in"])) * memory, ps["gmu.out"])


def mlp(mm, ps, x):
    g, u = jnp.split(mm(x, ps["mlp.w1"]), 2, axis=-1)
    return mm(u * jax.nn.silu(g), ps["mlp.w2"])


def forward(cfg: Dict, params, ids, mm=common.matmul):
    """The final hidden states (T, d) of one sequence, after ``LNf``;
    ``mm`` is the matrix product (the control's rounds its operands)."""
    eps, published = cfg["layer_norm_eps"], cfg["num_hidden_layers_published"]
    h = params["embed"][ids - cfg["vocab_held"][0]]
    memory = kv = None
    for l in cfg["layer_ids_published"]:
        p, kind = f"l{l}.", kind_of(l, published)
        ps = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(ps, h, memory, kv, l=l, kind=kind):
            a = _ln(h, ps["ln1.gamma"], ps["ln1.beta"], eps)
            if kind == "mamba":
                mixed, memory = mamba(cfg, mm, ps, a)
            elif kind == "memory_unit":
                mixed = memory_unit(mm, ps, a, memory)
            elif kind == "attention":
                full = l == published // 2 + 1
                mixed, kv = attention(
                    cfg, mm, ps, a, l,
                    None if full else cfg["sliding_window"])
            else:
                mixed, _ = attention(cfg, mm, ps, a, l, None, kv)
            h = h + mixed
            h = h + mlp(mm, ps, _ln(h, ps["ln2.gamma"], ps["ln2.beta"], eps))
            return h, memory, kv

        # only layer 16's memory and layer 17's K/V are read later
        h, m_new, kv_new = layer(ps, h, memory, kv)
        if kind == "mamba" and l == published // 2:
            memory = m_new
        if kind == "attention" and l == published // 2 + 1:
            kv = kv_new
    return _ln(h, params["norm_f.gamma"], params["norm_f.beta"], eps)


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
    table_t = params["embed"].T

    @jax.checkpoint
    def chunk(h_c, targets_c):
        lsm = jax.nn.log_softmax(mm(h_c, table_t), axis=-1)
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
        for row in rows:
            l, g = jax.value_and_grad(
                lambda p: sequence_loss(cfg, p, row, rounding, fault) / n
            )(params)
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
    seed's weights made a second time at the end: 697 M parameters with
    Adam's state are 8.4 GB, and a kept copy of the start and of each
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

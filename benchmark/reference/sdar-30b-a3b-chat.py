"""Plain reference for the ``sdar-30b-a3b-chat`` configuration.

One expert-parallel rank of SDAR-30B-A3B-Chat (JetLM; ``model_type:
sdar_moe``) under block-diffusion supervised fine-tuning.  Float32 at
``highest`` precision: the layers' equations, the loss, its gradients
and the Adam step, with dense attention under an explicit boolean mask
and the experts as a masked dense sum.  No kernels, nothing of the
program.

One layer, with ``h`` the residual stream (2048) and
``rms(x, g) = x / sqrt(mean(x^2) + 1e-6) * g``:

* ``a = rms(h, g1)``; ``q = a Wq`` (32 heads of 128), ``k = a Wk``,
  ``v = a Wv`` (4 heads of 128), no bias; q and k are RMS-normed over
  each head's 128 (gains ``gq``, ``gk``), then rotated (rotary, base 1e6,
  rotate-half form) at the position ids; query head ``i`` reads K/V head
  ``i // 8``; ``h += softmax(q k^T / sqrt(128) + mask) v Wo``.
* ``m = rms(h, g2)``; ``p = softmax(m Wr)`` over all 128 experts; ``S``
  the 8 largest; ``w_e = p_e / sum_S p``; ``h += sum_{e in S, e held}
  w_e (silu(m Wg_e) * (m Wu_e)) Wd_e``.

Block-diffusion step for a sequence ``x0`` of L ids, block length B,
mask id M, draws ``u_i`` a position and ``s_b`` a block in [0, 1):
``t_b = t_min + (1 - t_min) s_b``; position i of block b is masked iff
``u_i < t_b``; the layers see ``[xt ; x0]`` at position ids ``[0..L-1 ;
0..L-1]``; query i reads key j iff, with ``blk(i) = (i mod L) // B``,
(both noisy and ``blk(j) = blk(i)``) or (i noisy, j clean, ``blk(j) <
blk(i)``) or (both clean, ``blk(j) <= blk(i)``).  Loss = ``(1/L)
sum_{i masked} (1/t_blk(i)) CE(head(rms(h_i, gf)), x0_i)`` over the
noisy half, no shift, plus ``router_aux_loss_coef`` times the sum over
layers of ``128 sum_e f_e pbar_e`` (f: share of the sequence's
assignments, pbar: mean router probability).

Departures from the published model, each the configuration file's
(``reduced``, ``assumed``):

* 4 of the 48 layers; the 16 experts of rank 0 of an 8-way
  expert-parallel group (the router keeps its 128 outputs and its 8
  experts a token, and what the 112 absent experts would have added is
  left out, here as in the program); ids 0-18,991 of the 151,936 (the
  embedding and the head hold that slice, id 18,991 is the mask id, and
  the loss is over the slice);
* block length 4, the linear schedule with ``t_min`` 1e-3, no shift, the
  per-head q/k norm and the auxiliary coefficient 1e-3 are assumed: the
  published config gives none of them;
* the draws ``u`` and ``s`` come with the record (an id below 18,991 is
  read as ``id / 18991``), not from a generator, so that a step is a
  function of the seed's weights and rows alone.

A batch is taken one sequence at a time, the gradients summed; each
layer is recomputed in the backward pass, attention goes by blocks of
queries, and the step updates its state in place, so that float32
activations fit the chip after the program is freed.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common

QUERY_ROWS = 512


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["num_experts_published"], cfg["experts_held"][1],
            cfg["moe_intermediate_size"], cfg["vocab_held"][1])


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, h, hkv, hd, e_all, e, f, v = _sizes(cfg)
    out = [("embed", (v, d), "normal")]
    for l in range(cfg["num_hidden_layers"]):
        p = f"l{l}"
        out += [(f"{p}.ln1.gamma", (d,), "one"),
                (f"{p}.attn.q", (d, h * hd), "normal"),
                (f"{p}.attn.k", (d, hkv * hd), "normal"),
                (f"{p}.attn.v", (d, hkv * hd), "normal"),
                (f"{p}.attn.o", (h * hd, d), "normal"),
                (f"{p}.attn.q_norm", (hd,), "one"),
                (f"{p}.attn.k_norm", (hd,), "one"),
                (f"{p}.ln2.gamma", (d,), "one"),
                (f"{p}.moe.router", (d, e_all), "normal"),
                (f"{p}.moe.gate", (e, d, f), "normal"),
                (f"{p}.moe.up", (e, d, f), "normal"),
                (f"{p}.moe.down", (e, f, d), "normal")]
    out += [("norm_f.gamma", (d,), "one"), ("head", (d, v), "normal")]
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


def _rotary(x, positions, base):
    """x: (T, heads, D); rotate-half form."""
    half = x.shape[-1] // 2
    inv_freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def noise(cfg: Dict, row):
    """``(tokens (2L,), positions (2L,), x0 (L,), weights (L,))`` of one
    record: the sequence, its draws, the rule of the docstring."""
    L, B = cfg["seq_len"], cfg["block_length"]
    mask_id = cfg["vocab_held"][0] + cfg["vocab_held"][1] - 1
    scale = jnp.float32(1.0 / (cfg["vocab_size"] - 1))
    x0 = row[:L]
    u = row[L:2 * L].astype(jnp.float32) * scale
    s = row[2 * L:].astype(jnp.float32) * scale
    t = jnp.repeat(cfg["t_min"] + (1.0 - cfg["t_min"]) * s, B)
    masked = u < t
    xt = jnp.where(masked, mask_id, x0)
    positions = jnp.tile(jnp.arange(L, dtype=jnp.int32), 2)
    weights = jnp.where(masked, 1.0 / (t * L), 0.0)
    return jnp.concatenate([xt, x0]), positions, x0, weights


def allowed(cfg: Dict):
    """The (2L, 2L) boolean mask: query i may read key j."""
    L, B = cfg["seq_len"], cfg["block_length"]
    i = jnp.arange(2 * L)
    blk, noisy = (i % L) // B, i < L
    bi, bj, ni, nj = blk[:, None], blk[None, :], noisy[:, None], noisy[None, :]
    return (ni & nj & (bj == bi)) | (ni & ~nj & (bj < bi)) \
        | (~ni & ~nj & (bj <= bi))


def attention(cfg: Dict, mm, ps, a, positions, mask):
    d, h, hkv, hd, *_ = _sizes(cfg)
    t, group, eps = a.shape[0], h // hkv, cfg["rms_norm_eps"]
    q = mm(a, ps["attn.q"]).reshape(t, h, hd)
    k = mm(a, ps["attn.k"]).reshape(t, hkv, hd)
    v = mm(a, ps["attn.v"]).reshape(t, hkv, hd)
    q = _rotary(_rms(q, ps["attn.q_norm"], eps), positions,
                cfg["rope_theta"])
    k = _rotary(_rms(k, ps["attn.k_norm"], eps), positions,
                cfg["rope_theta"])
    # (K/V heads, group, T, D): query head i reads K/V head i // group
    q = q.reshape(t, hkv, group, hd).transpose(1, 2, 0, 3)
    k_t = k.transpose(1, 2, 0)[:, None]                 # (hkv, 1, D, T)
    v = v.transpose(1, 0, 2)[:, None]                   # (hkv, 1, T, D)
    rows = min(QUERY_ROWS, t)

    @jax.checkpoint
    def block(q_blk, mask_blk):
        scores = mm(q_blk, k_t) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(mask_blk, scores, -jnp.inf), -1)
        return mm(probs, v)                             # (hkv, g, rows, D)

    ctx = jax.lax.map(
        lambda qm: block(*qm),
        (q.reshape(hkv, group, t // rows, rows, hd).transpose(2, 0, 1, 3, 4),
         mask.reshape(t // rows, rows, t)))
    ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(t, h * hd)
    return mm(ctx, ps["attn.o"])


def experts(cfg: Dict, mm, ps, m):
    """``(the held experts' part of the sum, the auxiliary term)``."""
    _, _, _, _, e_all, e_held, _, _ = _sizes(cfg)
    first, top_k = cfg["experts_held"][0], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(mm(m, ps["moe.router"]), axis=-1)
    gates, picked = jax.lax.top_k(probs, top_k)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    @jax.checkpoint
    def one(y, e):
        w = jnp.sum(jnp.where(picked == first + e, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(mm(m, ps["moe.gate"][e])) \
            * mm(m, ps["moe.up"][e])
        return y + w[:, None] * mm(hidden, ps["moe.down"][e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(e_held))
    share = jnp.mean(jnp.sum(jax.nn.one_hot(picked, e_all), axis=1),
                     axis=0) / top_k
    aux = e_all * jnp.sum(share * jnp.mean(probs, axis=0))
    return y, aux


def sequence_loss(cfg: Dict, params, row, rounding: Optional[str] = None,
                  fault: Optional[str] = None):
    """The loss of one record (its int32 row)."""
    mm = common.product(common.matmul, rounding)
    L, eps = cfg["seq_len"], cfg["rms_norm_eps"]
    tokens, positions, x0, weights = noise(cfg, row)
    if fault == "half_batch":
        # half of the step's work left out: a batch is one sequence,
        # so it is the second half of its loss positions
        weights = jnp.where(jnp.arange(L) < L // 2, weights, 0.0)
    first = cfg["vocab_held"][0]
    mask = allowed(cfg)
    h = params["embed"][tokens - first]

    @jax.checkpoint
    def layer(ps, h):
        h = h + attention(cfg, mm, ps, _rms(h, ps["ln1.gamma"], eps),
                          positions, mask)
        y, aux = experts(cfg, mm, ps, _rms(h, ps["ln2.gamma"], eps))
        return h + y, aux

    aux_sum = jnp.float32(0.0)
    for l in range(cfg["num_hidden_layers"]):
        p = f"l{l}."
        h, aux = layer({k[len(p):]: v for k, v in params.items()
                        if k.startswith(p)}, h)
        aux_sum = aux_sum + aux
    logits = mm(_rms(h[:L], params["norm_f.gamma"], eps), params["head"])
    ll = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                             (x0 - first)[:, None], axis=-1)[:, 0]
    return -jnp.sum(weights * ll) + cfg["router_aux_loss_coef"] * aux_sum


def prepare(cfg: Dict, stages: List[Dict], x):
    if stages:
        raise ValueError(f"unknown stages {stages!r}")
    return x


@functools.lru_cache(maxsize=None)
def _step(cfg_key, rounding, fault):
    cfg = json.loads(cfg_key)
    opt = cfg["optimizer"]

    def step(params, state, rows, i):
        """One update on ``rows`` (batch, record); returns the per-leaf
        norms of the gradient (leaves in sorted order) in the gradient's
        place, so that no second copy of it outlives the update."""
        n = rows.shape[0]

        def body(carry, row):
            loss, grads = carry
            l, g = jax.value_and_grad(
                lambda p: sequence_loss(cfg, p, row, rounding, fault) / n
            )(params)
            return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(body, (jnp.float32(0.0), zero),
                                        rows)
        norms = jnp.stack([jnp.sqrt(jnp.sum(jnp.square(grads[k])))
                           for k in sorted(grads)])
        new, state = common.optimizer_update(opt, params, grads, state, i)
        return new, state, loss, norms

    return jax.jit(step, donate_argnums=(0, 1))


def follow(cfg: Dict, seed: int, batches, moment_after: int,
           rounding: Optional[str] = None, fault: Optional[str] = None):
    """The first ``len(batches)`` training steps from the seed's weights
    on ``batches`` (each ``((records, unused), labels)``), and what the
    comparison reads of them (as ``common.follow`` gives it).  The
    parameters and the optimizer's state are updated in place and the
    seed's weights made a second time at the end: half a billion
    parameters with Adam's state are 7-9 GB, and a kept copy of the
    start and of each gradient beside them does not fit the chip."""
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

"""Plain reference for the ``kanana-2-30b-a3b-instruct-2601``
configuration.

One expert-parallel rank of kanana-2-30b-a3b-instruct-2601 (kakaocorp;
``model_type: deepseek_v3``) under next-token supervised fine-tuning.
Float32 with every product at ``highest`` precision: the layers'
equations, the loss, its gradients and the Adam step, with the
192-wide keys written out plainly, dense attention by blocks of query
rows and the experts as a masked dense sum.  No kernels, nothing of the
program.

One layer, with ``h`` the residual stream (2048) and ``rms(x, g) = x /
sqrt(mean(x^2) + 1e-6) * g``:

* ``a = rms(h, g1)``; ``q = a Wq`` (32 heads of ``[q_nope (128) | q_pe
  (64)]``); ``[c | k_pe] = a Wkva`` (512 | 64); ``c = rms(c, gc)``;
  ``[k_nope_h | v_h] = c Wkvb`` (32 heads of 128 | 128); ``q_pe_h`` and
  the ONE ``k_pe`` are rotated (rotary, base 1e6, the published
  interleaved pairs ``(2 i, 2 i + 1)``, positions 0 .. T-1); ``k_h =
  [k_nope_h | k_pe]`` (192 wide, ``k_pe`` the same in every head);
  ``h += [softmax(q_h k_h^T / sqrt(192) + causal) v_h]_h Wo``.  No bias.
* ``m = rms(h, g2)``; layer 0: ``h += (silu(m Wg) * (m Wu)) Wd`` (6144);
  layers >= 1: ``s = sigmoid(m Wr)`` over all 128 experts; ``S`` the 6
  with the largest ``s + b`` (``b`` the layer's selection bias: state,
  not a parameter, no gradient); ``w_e = s_e / sum_S s * 2.448``; ``h +=
  sum_{e in S, e held} w_e (silu(m Wg_e) * (m Wu_e)) Wd_e + (silu(m Wsg)
  * (m Wsu)) Wsd`` (768-wide routed experts, one 1536-wide shared one).
  ``n_group = topk_group = 1``: group-limited selection is a no-op.
* loss ``= mean_{t < T-1} CE(head(rms(h_t, gf)), id_{t+1})`` over the
  held slice of the vocabulary; no auxiliary term.

The stored matrices have the PROGRAM's column order, a permutation of
the published one (random weights do not see it; a checkpoint would be
permuted where it is laid in): ``attn.q`` holds every head's ``q_nope``
and then every head's rotary part, ``attn.kv_b`` every head's ``k_nope``
and then every head's ``v``, ``mlp.gate_up`` / ``moe.shared_gate_up``
the gate and then the up projection, and each 64-wide rotary part is in
rotate-half order (stored column ``i < 32`` is the published column ``2
i``, stored ``32 + i`` the published ``2 i + 1``).  ``_published`` puts
a rotary part back in the published order and ``_rotary`` is the
published rotation of neighbouring pairs.

Departures from the published model, each the configuration file's
(``reduced``, ``assumed``): layers 0-5 of the 48; the 16 routed experts
of rank 0 of an 8-way expert-parallel group (the router keeps its 128
outputs, its 6 experts a token and its scale, and what the 112 absent
experts would have added is left out, here as in the program; the shared
experts are whole); ids 0-16,031 of the 128,256; the selection bias
drawn N(0, 0.01) from the configuration's own seed and frozen.

The control (``rounding``) rounds the operands of every product the
configuration states in bfloat16; the router's product, stated in
float32, is not rounded.

A batch is taken one sequence at a time; each layer is recomputed in the
backward pass, attention goes by blocks of queries, the held experts one
at a time, and the step updates its state in place, so that float32
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
LOSS_ROWS = 1024


def _sizes(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def _sparse(cfg: Dict, layer: int) -> bool:
    return layer >= cfg["first_k_dense_replace"]


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, h, n, r, v, rank = _sizes(cfg)
    e_all, e = cfg["n_routed_experts_published"], cfg["experts_held"][1]
    f, ids = cfg["moe_intermediate_size"], cfg["vocab_held"][1]
    shared = cfg["n_shared_experts"] * f
    out = [("embed", (ids, d), "normal")]
    for l in range(cfg["num_hidden_layers"]):
        p = f"l{l}"
        out += [(f"{p}.ln1.gamma", (d,), "one"),
                (f"{p}.attn.q", (d, h * (n + r)), "normal"),
                (f"{p}.attn.kv_a", (d, rank + r), "normal"),
                (f"{p}.attn.kv_a_norm", (rank,), "one"),
                (f"{p}.attn.kv_b", (rank, h * (n + v)), "normal"),
                (f"{p}.attn.o", (h * v, d), "normal"),
                (f"{p}.ln2.gamma", (d,), "one")]
        if _sparse(cfg, l):
            out += [(f"{p}.moe.router", (d, e_all), "normal"),
                    (f"{p}.moe.gate", (e, d, f), "normal"),
                    (f"{p}.moe.up", (e, d, f), "normal"),
                    (f"{p}.moe.down", (e, f, d), "normal"),
                    (f"{p}.moe.shared_gate_up", (d, 2 * shared), "normal"),
                    (f"{p}.moe.shared_down", (shared, d), "normal")]
        else:
            i = cfg["intermediate_size"]
            out += [(f"{p}.mlp.gate_up", (d, 2 * i), "normal"),
                    (f"{p}.mlp.down", (i, d), "normal")]
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


def selection_bias(cfg: Dict) -> Dict[str, jax.Array]:
    """The non-trained state: each sparse layer's selection bias, N(0,
    std) over the 128 experts from the configuration's own seed folded
    with the layer's depth (not from ``--seed``: see the configuration's
    ``assumed``)."""
    spec = cfg["selection_bias"]
    key = jax.random.PRNGKey(spec["seed"])
    return {f"l{l}.moe.bias": spec["std"] * jax.random.normal(
        jax.random.fold_in(key, l), (cfg["n_routed_experts_published"],),
        jnp.float32)
        for l in range(cfg["num_hidden_layers"]) if _sparse(cfg, l)}


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def _published(x):
    """A rotary part's columns, stored in rotate-half order, back in the
    published order: published ``2 i`` is stored ``i``, published ``2 i
    + 1`` is stored ``r / 2 + i``."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _rotary(x, base):
    """The published rotation: x (T, heads, r) at positions 0 .. T-1,
    the neighbouring pair ``(2 i, 2 i + 1)`` turned by ``position *
    base^(-2 i / r)``."""
    t, _, r = x.shape
    inv_freq = base ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(cfg: Dict, mm, ps, a):
    d, h, n, r, v_dim, rank = _sizes(cfg)
    t, eps, base = a.shape[0], cfg["rms_norm_eps"], cfg["rope_theta"]
    q = mm(a, ps["attn.q"])
    down = mm(a, ps["attn.kv_a"])
    up = mm(_rms(down[:, :rank], ps["attn.kv_a_norm"], eps), ps["attn.kv_b"])
    q_nope = q[:, :h * n].reshape(t, h, n)
    q_pe = _rotary(_published(q[:, h * n:].reshape(t, h, r)), base)
    k_nope = up[:, :h * n].reshape(t, h, n)
    k_pe = _rotary(_published(down[:, rank:].reshape(t, 1, r)), base)
    value = up[:, h * n:].reshape(t, h, v_dim)
    # the keys written out: every head's own 128 and the shared 64
    q = jnp.concatenate([q_nope, q_pe], axis=-1).transpose(1, 0, 2)
    k_t = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (t, h, r))],
                          axis=-1).transpose(1, 2, 0)       # (h, 192, T)
    value = value.transpose(1, 0, 2)                        # (h, T, v)
    rows = min(QUERY_ROWS, t)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(q_blk, mask_blk):
        scores = mm(q_blk, k_t) / jnp.sqrt(jnp.float32(n + r))
        probs = jax.nn.softmax(jnp.where(mask_blk, scores, -jnp.inf), -1)
        return mm(probs, value)                             # (h, rows, v)

    ctx = jax.lax.map(
        lambda qm: block(*qm),
        (q.reshape(h, t // rows, rows, n + r).transpose(1, 0, 2, 3),
         causal.reshape(t // rows, rows, t)))
    return mm(ctx.transpose(0, 2, 1, 3).reshape(t, h * v_dim), ps["attn.o"])


def gated(mm, m, gate_up, down):
    g, u = jnp.split(mm(m, gate_up), 2, axis=-1)
    return mm(jax.nn.silu(g) * u, down)


def experts(cfg: Dict, mm, ps, bias, m):
    """The held routed experts' part of the sum and the shared experts."""
    first, e_held = cfg["experts_held"]
    top_k = cfg["num_experts_per_tok"]
    # the router is stated in float32: the control does not round it
    scores = jax.nn.sigmoid(common.matmul(m, ps["moe.router"]))
    _, picked = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), top_k)
    gates = jnp.take_along_axis(scores, picked, axis=-1)
    if cfg["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    gates = gates * cfg["routed_scaling_factor"]

    @jax.checkpoint
    def one(y, e):
        w = jnp.sum(jnp.where(picked == first + e, gates, 0.0), axis=-1)
        hidden = jax.nn.silu(mm(m, ps["moe.gate"][e])) \
            * mm(m, ps["moe.up"][e])
        return y + w[:, None] * mm(hidden, ps["moe.down"][e]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(e_held))
    return y + gated(mm, m, ps["moe.shared_gate_up"], ps["moe.shared_down"])


def forward(cfg: Dict, params, state, ids, mm=common.matmul):
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
            h = h + attention(cfg, mm, ps, _rms(h, ps["ln1.gamma"], eps))
            m = _rms(h, ps["ln2.gamma"], eps)
            if _sparse(cfg, l):
                return h + experts(cfg, mm, ps, state[p + "moe.bias"], m)
            return h + gated(mm, m, ps["mlp.gate_up"], ps["mlp.down"])

        h = layer(ps, h)
    return _rms(h, params["norm_f.gamma"], eps)


def sequence_loss(cfg: Dict, params, state, ids,
                  rounding: Optional[str] = None,
                  fault: Optional[str] = None):
    """The loss of one record (its int32 row of ``seq_len`` ids)."""
    mm = common.product(common.matmul, rounding)
    h = forward(cfg, params, state, ids, mm)
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
        bias = selection_bias(cfg)
        loss, grads = jnp.float32(0.0), None
        with jax.default_matmul_precision("highest"):
            for row in rows:
                l, g = jax.value_and_grad(
                    lambda p: sequence_loss(cfg, p, bias, row, rounding,
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
    seed's weights made a second time at the end: 687 M parameters with
    Adam's state are 8.3 GB, and a kept copy of the start and of each
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

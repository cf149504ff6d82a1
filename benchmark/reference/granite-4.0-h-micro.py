"""Plain reference for the ``granite-4.0-h-micro`` configuration.

One pipeline stage's vocabulary rank of Granite 4.0-H Micro
(ibm-granite; ``model_type`` granitemoehybrid) under next-token
supervised fine-tuning.  Float32 with every product at ``highest``
precision: the layers' equations, the loss, its gradients and the Adam
step.  No kernels, nothing of the program.

With ``h`` the residual stream (2048), ``rms(x, g) = x / sqrt(mean(x^2)
+ 1e-5) * g`` and ``u = rms(h, g1)``, a layer is

* Mamba-2 (``layer_types[l] == "mamba"``): ``[z, xBC, dt_r] = u W_in``
  (4096 | 4352 | 64); ``xBC = silu(causal depthwise conv_4(xBC) + b)``,
  then ``x`` (64 heads of 64), ``B``, ``C`` (128 each, one group);
  ``dt = softplus(dt_r + dt_bias)``, ``A = -exp(A_log)``; for each head
  ``S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T``, ``y_t = C_t^T S_t + D
  x_t``; ``h += 0.22 rms(y * silu(z), gn) W_out``;
* attention (index 5): ``q = u Wq`` (32 heads of 64), ``k = u Wk``, ``v
  = u Wv`` (8 heads; query head ``i`` reads K/V head ``i // 4``), no
  positions, causal, ``h += 0.22 [softmax(q k^T / 64) v] Wo``;

then ``m = rms(h, g2)``, ``h += 0.22 (silu(m Wg) * (m Wu)) Wd`` (8192
wide).  The embedding is ``12 E[id]``; loss ``= mean_{t < T-1} CE(rms(h_t,
gf) E^T / 8, id_{t+1})`` over the held slice of the tied table.

The scan is computed in the chunked form of the Mamba-2 paper's own
minimal listing (Dao & Gu 2024, arXiv:2405.21060, "ssd_minimal"): within
each chunk of 256 positions the quadratic form ``(C B^T o
exp(segsum(dt A))) (dt x)``, the chunks' states from ``B``, the
recurrence between chunks by ``exp(segsum)`` of the chunks' total
decays, and the states' part of ``y``; the two per-chunk einsums go a
chunk at a time so that its gradients fit.  Attention goes by blocks of
queries.  ``tests/benchmark/test_bench_granite.py`` ties the form to the
recurrence a position at a time.

Departures from the published model, each the configuration file's
(``reduced``, ``assumed``): layers 0-9 of the 40 (one period of
``layer_types``); ids 0-12,543 of the 100,352.

The control (``rounding``) rounds the operands of every product the
configuration states in bfloat16, the scan's included.

A batch is taken one sequence at a time; each layer is recomputed in
the backward pass and the step updates its state in place, so that
float32 activations fit the chip after the program is freed.
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


def _kinds(cfg: Dict) -> List[str]:
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def _widths(cfg: Dict):
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    states = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, inner + 2 * states


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, ff = cfg["hidden_size"], cfg["shared_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, heads = d // h, cfg["mamba_n_heads"]
    inner, conv = _widths(cfg)
    out = [("embed", (cfg["vocab_held"][1], d), "normal")]
    for l, kind in enumerate(_kinds(cfg)):
        p = f"l{l}"
        out.append((f"{p}.ln1.gamma", (d,), "one"))
        if kind == "mamba":
            out += [(f"{p}.mamba.in", (d, inner + conv + heads), "normal"),
                    (f"{p}.mamba.conv", (cfg["mamba_d_conv"], conv), "normal"),
                    (f"{p}.mamba.conv_bias", (conv,), "zero"),
                    (f"{p}.mamba.dt_bias", (heads,), "dt_bias"),
                    (f"{p}.mamba.a_log", (heads,), "a_log"),
                    (f"{p}.mamba.d", (heads,), "one"),
                    (f"{p}.mamba.norm", (inner,), "one"),
                    (f"{p}.mamba.out", (inner, d), "normal")]
        else:
            out += [(f"{p}.attn.q", (d, h * hd), "normal"),
                    (f"{p}.attn.k", (d, kv * hd), "normal"),
                    (f"{p}.attn.v", (d, kv * hd), "normal"),
                    (f"{p}.attn.o", (h * hd, d), "normal")]
        out += [(f"{p}.ln2.gamma", (d,), "one"),
                (f"{p}.mlp.gate_up", (d, 2 * ff), "normal"),
                (f"{p}.mlp.down", (ff, d), "normal")]
    return out + [("norm_f.gamma", (d,), "one")]


def param_order(cfg: Dict) -> List[str]:
    return [name for name, _, _ in _spec(cfg)]


def init(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """All weights in one jitted call from the seed: N(0, 0.02) for every
    matrix and the convolution's taps, unit gains and ``D``, a zero
    convolution bias, ``A_log = log(1..64)``, and ``dt_bias`` the inverse
    softplus of a dt drawn log-uniform in [1e-3, 1e-1]."""
    spec = _spec(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                v = cfg["initializer_range"] * jax.random.normal(
                    k, shape, jnp.float32)
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif kind == "a_log":
                v = jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
            elif kind == "zero":
                v = jnp.zeros(shape, jnp.float32)
            else:
                v = jnp.ones(shape, jnp.float32)
            params[name] = v
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _rms(x, gamma, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * gamma


def segsum(x):
    """``exp(segsum(x))`` is the (L, L) lower-triangular matrix of
    ``exp(x_{s+1} + ... + x_l)``: the listing's stable form, each entry
    a masked sum of its own terms, not a difference of running sums."""
    length = x.shape[-1]
    x = jnp.repeat(x[..., None], length, axis=-1)             # (..., l, s)
    below = jnp.tril(jnp.ones((length, length), bool), -1)
    x = jnp.where(below, x, 0.0)
    sums = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((length, length), bool)), sums,
                     -jnp.inf)


def ssd_minimal(x, a, b, c, chunk: int, rounding=None, state=None):
    """The listing's ``ssd_minimal_discrete`` for one sequence: ``x``
    (T, H, P) already times ``dt``, ``a`` (T, H) = ``dt A``, ``b``, ``c``
    (T, H, N) (the group's, a head) -> (y (T, H, P), the last state (H, P,
    N)).  ``state``: the state before the first position (zeros)."""
    t, heads, p = x.shape
    n = b.shape[-1]
    nc = t // chunk
    prod = functools.partial(_product, rounding)
    x, a, b, c = (v.reshape(nc, chunk, *v.shape[1:]) for v in (x, a, b, c))
    a = jnp.moveaxis(a, 2, 0)                                # (H, nc, L)
    a_cumsum = jnp.cumsum(a, axis=-1)

    # 1. the diagonal blocks: outputs within each chunk, a chunk at a time
    @jax.checkpoint
    def diagonal(xs):
        x_c, a_c, b_c, c_c = xs               # (L,H,P) (H,L) (L,H,N) x2
        scores = prod("lhn,shn->hls", c_c, b_c) * jnp.exp(segsum(a_c))
        return prod("hls,shp->lhp", scores, x_c)

    y_diag = jax.lax.map(diagonal, (x, jnp.moveaxis(a, 1, 0), b, c))

    # 2. each chunk's state from its own inputs
    decay_states = jnp.exp(a_cumsum[..., -1:] - a_cumsum)     # (H, nc, L)

    @jax.checkpoint
    def chunk_state(xs):
        x_c, b_c, decay_c = xs
        return prod("lhn,lhp->hpn", b_c * jnp.moveaxis(decay_c, 0, 1)[..., None],
                    x_c)

    states = jax.lax.map(chunk_state, (x, b, jnp.moveaxis(decay_states, 1, 0)))
    # 3. the recurrence between chunks
    if state is None:
        state = jnp.zeros((heads, p, n), jnp.float32)
    states = jnp.concatenate([state[None], states], axis=0)  # (nc+1, H, P, N)
    decay_chunk = jnp.exp(segsum(jnp.pad(a_cumsum[..., -1], ((0, 0), (1, 0)))))
    new_states = jnp.einsum("hzc,chpn->zhpn", decay_chunk, states,
                            precision=common.HIGHEST)
    states, last = new_states[:-1], new_states[-1]

    # 4. the states' part of each chunk's output
    @jax.checkpoint
    def off_diagonal(xs):
        c_c, s_c, a_c = xs
        return prod("lhn,hpn->lhp", c_c, s_c) \
            * jnp.moveaxis(jnp.exp(a_c), 0, 1)[..., None]

    y_off = jax.lax.map(off_diagonal,
                        (c, states, jnp.moveaxis(a_cumsum, 1, 0)))
    return (y_diag + y_off).reshape(t, heads, p), last


def _product(rounding, spec, lhs, rhs):
    def f(u, v):
        return jnp.einsum(spec, u, v, precision=common.HIGHEST)
    return common.product(f, rounding)(lhs, rhs)


def mamba(cfg: Dict, mm, ps, u, rounding):
    t = u.shape[0]
    heads, p, n = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"])
    groups, taps = cfg["mamba_n_groups"], cfg["mamba_d_conv"]
    inner, conv = _widths(cfg)
    proj = mm(u, ps["mamba.in"])
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + conv], \
        proj[:, inner + conv:]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[k:k + t] * ps["mamba.conv"][k]
                          for k in range(taps)) + ps["mamba.conv_bias"])
    x = xbc[:, :inner].reshape(t, heads, p)
    b, c = (jnp.repeat(v.reshape(t, groups, n), heads // groups, axis=1)
            for v in (xbc[:, inner:inner + groups * n],
                      xbc[:, inner + groups * n:]))
    dt = jax.nn.softplus(dt + ps["mamba.dt_bias"])             # (T, H)
    a = -jnp.exp(ps["mamba.a_log"])
    y, _ = ssd_minimal(x * dt[..., None], dt * a, b, c,
                       cfg["mamba_chunk_size"], rounding)
    y = (y + ps["mamba.d"][:, None] * x).reshape(t, inner)
    gated = _rms(y * jax.nn.silu(z), ps["mamba.norm"], cfg["rms_norm_eps"])
    return mm(gated, ps["mamba.out"])


def attention(cfg: Dict, mm, ps, u):
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    t, hd, group = u.shape[0], d // h, h // kv
    q = mm(u, ps["attn.q"]).reshape(t, kv, group, hd).transpose(1, 2, 0, 3)
    k_t = mm(u, ps["attn.k"]).reshape(t, kv, hd).transpose(1, 2, 0)
    value = mm(u, ps["attn.v"]).reshape(t, kv, hd).transpose(1, 0, 2)
    rows = min(QUERY_ROWS, t)

    @jax.checkpoint
    def block(q_blk, first):
        scores = mm(q_blk, k_t[:, None]) * cfg["attention_multiplier"]
        i = first + jnp.arange(rows)[:, None]
        allowed = jnp.arange(t)[None, :] <= i
        probs = jax.nn.softmax(jnp.where(allowed, scores, -jnp.inf), -1)
        return mm(probs, value[:, None])           # (kv, g, rows, hd)

    ctx = jax.lax.map(
        lambda qf: block(*qf),
        (q.reshape(kv, group, t // rows, rows, hd).transpose(2, 0, 1, 3, 4),
         jnp.arange(0, t, rows)))
    ctx = ctx.transpose(0, 3, 1, 2, 4).reshape(t, h * hd)
    return mm(ctx, ps["attn.o"])


def forward(cfg: Dict, params, ids, mm=common.matmul, rounding=None):
    """The final hidden states (T, d) of one sequence, after the last
    norm; ``mm`` is the matrix product (the control's rounds its
    operands, ``rounding`` the scan's)."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = cfg["embedding_multiplier"] * params["embed"][
        ids - cfg["vocab_held"][0]]
    for l, kind in enumerate(_kinds(cfg)):
        p = f"l{l}."
        ps = {k[len(p):]: v for k, v in params.items() if k.startswith(p)}

        @jax.checkpoint
        def layer(ps, h, kind=kind):
            u = _rms(h, ps["ln1.gamma"], eps)
            mixed = mamba(cfg, mm, ps, u, rounding) if kind == "mamba" \
                else attention(cfg, mm, ps, u)
            h = h + r * mixed
            m = _rms(h, ps["ln2.gamma"], eps)
            gu = mm(m, ps["mlp.gate_up"])
            ff = gu.shape[-1] // 2
            return h + r * mm(jax.nn.silu(gu[:, :ff]) * gu[:, ff:],
                              ps["mlp.down"])

        h = layer(ps, h)
    return _rms(h, params["norm_f.gamma"], eps)


def sequence_loss(cfg: Dict, params, ids, rounding: Optional[str] = None,
                  fault: Optional[str] = None):
    """The loss of one record (its int32 row of ``seq_len`` ids)."""
    mm = common.product(common.matmul, rounding)
    h = forward(cfg, params, ids, mm, rounding)
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
        logits = mm(h_c, params["embed"].T) / cfg["logits_scaling"]
        lsm = jax.nn.log_softmax(logits, axis=-1)
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
    seed's weights made a second time at the end: 772 M parameters with
    Adam's state are 9.3 GB, and a kept copy of the start and of each
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

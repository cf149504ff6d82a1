"""Plain reference for the ``openai-gpt`` configuration.

The first GPT (Radford et al. 2018, "Improving Language Understanding by
Generative Pre-Training") as a sequence classifier: 12 post-LN decoder
blocks 768 wide, 12 heads, FFN 3072 with the tanh GeLU, one table for
tokens and positions (positions are the table's last ``n_positions``
rows), causal attention, and a linear classifier on the last token's
final state.  Float32 at ``highest`` precision with dense attention, the
loss, its gradients and the Adam step: no kernels, nothing of the
program.

A batch is taken in blocks of rows whose gradients are summed (no layer
ties rows together), and each block is recomputed in the backward pass,
so that float32 activations fit the chip.
"""

from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import common

BLOCK_ROWS = 8


def _spec(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], str]]:
    d, ffn = cfg["n_embd"], cfg["n_inner"]
    out = [("embed", (cfg["vocab_size"] + cfg["n_positions"], d),
            "normal")]
    for l in range(cfg["n_layer"]):
        p = f"h{l}"
        out += [(f"{p}.attn.qkv.kernel", (d, 3 * d), "normal"),
                (f"{p}.attn.qkv.bias", (3 * d,), "zero"),
                (f"{p}.attn.out.kernel", (d, d), "normal"),
                (f"{p}.attn.out.bias", (d,), "zero"),
                (f"{p}.ln1.gamma", (d,), "one"),
                (f"{p}.ln1.beta", (d,), "zero"),
                (f"{p}.ffn.up.kernel", (d, ffn), "normal"),
                (f"{p}.ffn.up.bias", (ffn,), "zero"),
                (f"{p}.ffn.down.kernel", (ffn, d), "normal"),
                (f"{p}.ffn.down.bias", (d,), "zero"),
                (f"{p}.ln2.gamma", (d,), "one"),
                (f"{p}.ln2.beta", (d,), "zero")]
    out += [("cls.kernel", (d, cfg["num_classes"]), "normal"),
            ("cls.bias", (cfg["num_classes"],), "zero")]
    return out


def param_order(cfg: Dict) -> List[str]:
    return [name for name, _, _ in _spec(cfg)]


def init(cfg: Dict, seed: int) -> Dict[str, jax.Array]:
    """All weights in one jitted call from the seed: N(0, 0.02) as the
    paper has it, zero biases, unit LayerNorm."""
    spec = _spec(cfg)

    @jax.jit
    def make(key):
        params = {}
        for i, (name, shape, kind) in enumerate(spec):
            if kind == "normal":
                params[name] = cfg["initializer_range"] * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif kind == "one":
                params[name] = jnp.ones(shape, jnp.float32)
            else:
                params[name] = jnp.zeros(shape, jnp.float32)
        return params

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _layer_norm(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gamma + beta


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def forward(cfg: Dict, params, tokens, positions,
            rounding: Optional[str] = None):
    """Logits of the classifier for a block of rows."""
    mm = common.product(common.matmul, rounding)
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b, t = tokens.shape
    h = params["embed"][tokens] + params["embed"][positions]
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def block(ps, h):
        d = h.shape[-1]
        qkv = mm(h, ps["attn.qkv.kernel"]) + ps["attn.qkv.bias"]
        q, k, v = (a.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)
                   for a in jnp.split(qkv, 3, axis=-1))
        scores = mm(q, k.transpose(0, 1, 3, 2)) / jnp.sqrt(d // heads)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        ctx = mm(probs, v).transpose(0, 2, 1, 3).reshape(b, t, d)
        a = mm(ctx, ps["attn.out.kernel"]) + ps["attn.out.bias"]
        h = _layer_norm(h + a, ps["ln1.gamma"], ps["ln1.beta"], eps)
        f = _gelu_tanh(mm(h, ps["ffn.up.kernel"]) + ps["ffn.up.bias"])
        f = mm(f, ps["ffn.down.kernel"]) + ps["ffn.down.bias"]
        return _layer_norm(h + f, ps["ln2.gamma"], ps["ln2.beta"], eps)

    for l in range(cfg["n_layer"]):
        p = f"h{l}."
        h = block({k[len(p):]: v for k, v in params.items()
                   if k.startswith(p)}, h)
    return mm(h[:, -1], params["cls.kernel"]) + params["cls.bias"]


def prepare(cfg: Dict, stages: List[Dict], x):
    if stages:
        raise ValueError(f"unknown stages {stages!r}")
    return x


@functools.lru_cache(maxsize=None)
def _step(cfg_key, rounding, fault):
    cfg = json.loads(cfg_key)
    opt = cfg["optimizer"]

    def step(params, state, batch, i):
        (tokens, positions), labels = batch
        if fault == "half_batch":
            half = tokens.shape[0] // 2
            tokens, positions, labels = (tokens[:half], positions[:half],
                                         labels[:half])
        rows = tokens.shape[0]
        size = min(BLOCK_ROWS, rows)
        n = rows // size
        cut = lambda a: a.reshape((n, size) + a.shape[1:])

        def objective(p, blk):
            tok, pos, lab = blk
            return common.sparse_cross_entropy(
                forward(cfg, p, tok, pos, rounding), lab) / n

        def body(carry, blk):
            loss, grads = carry
            l, g = jax.value_and_grad(objective)(params, blk)
            return (loss + l, jax.tree_util.tree_map(jnp.add, grads, g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            body, (jnp.float32(0.0), zero),
            (cut(tokens), cut(positions), cut(labels)))
        new, state = common.optimizer_update(opt, params, grads, state, i)
        return new, state, loss, grads

    return jax.jit(step, donate_argnums=(1,))


def follow(cfg: Dict, seed: int, batches, moment_after: int,
           rounding: Optional[str] = None, fault: Optional[str] = None):
    """The first ``len(batches)`` training steps from the seed's weights
    on ``batches`` (each ``((token ids, position ids), labels)``)."""
    step = _step(json.dumps(cfg, sort_keys=True), rounding, fault)
    batches = (((jnp.asarray(x[0], jnp.int32), jnp.asarray(x[1], jnp.int32)),
                jnp.asarray(y, jnp.int32).reshape(-1)) for x, y in batches)
    return common.follow(step, cfg["optimizer"], init(cfg, seed), batches,
                         moment_after)

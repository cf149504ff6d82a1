"""Long-context training with ring-attention sequence parallelism.

A FIRST-CLASS new capability of the TPU build (SURVEY.md §5: the
reference has no long-context story — its sequence models are RNNs and
single-device BERT).  Here the sequence axis of the device mesh shards
Q/K/V along TIME: each device holds T/seq tokens, K/V blocks rotate
around the ring via ``ppermute`` with online-softmax accumulation
(parallel/ring_attention.py), so attention memory per device is
O(T·T/seq) instead of O(T²) — context length scales with the mesh.

The workflow, step by step:

1. **Mesh** — ``{"data": d, "seq": s}``: batch sharded over ``data``,
   sequence sharded over ``seq``.  On one device it degrades to dense
   attention transparently (same code).
2. **Exactness** — ring attention is EXACT attention: the example
   checks ``ring_attention`` against the dense reference to 1e-4 on
   the same inputs before training with it.
3. **Train** — a causal transformer block over a long sequence, via
   the standard trainer; the attention layer auto-routes to the ring
   when the mesh's seq axis is >1 (layers/attention.py).

Run (simulating 8 devices on CPU):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/distributed/long_context_example.py
"""

import argparse
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--smoke", action="store_true")
    args = p.parse_args(argv)
    if args.smoke:
        args.seq_len, args.steps = 128, 2

    import jax
    import jax.numpy as jnp
    import numpy as np

    from analytics_zoo_tpu.common import zoo_context
    from analytics_zoo_tpu.ops.attention import (
        scaled_dot_product_attention)
    from analytics_zoo_tpu.parallel.ring_attention import ring_attention
    from analytics_zoo_tpu.parallel.trainer import DistributedTrainer
    from analytics_zoo_tpu.pipeline.api.keras import Input, Model
    from analytics_zoo_tpu.pipeline.api.keras import objectives
    from analytics_zoo_tpu.pipeline.api.keras.layers import Dense
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        transformer_block)
    from analytics_zoo_tpu.pipeline.api.keras.layers.core import Lambda
    from analytics_zoo_tpu.pipeline.api.keras.optimizers import Adam

    # step 1 — mesh with a sequence axis: as many ways as devices allow
    n = jax.device_count()
    seq = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    ctx = zoo_context.init_zoo_context(
        mesh_shape={"data": n // seq, "seq": seq})
    T, D = args.seq_len, args.hidden
    print(f"[long-context] devices={n} mesh={dict(ctx.mesh.shape)} "
          f"T={T} (each device holds {T // seq} tokens)")

    # step 2 — exactness check vs dense attention
    rng = jax.random.PRNGKey(0)
    B, H, hd = 2, 4, D // 4
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i),
                                 (B, H, T, hd), jnp.float32)
               for i in range(3))
    ring = ring_attention(q, k, v, ctx.mesh, causal=True)
    dense = scaled_dot_product_attention(q, k, v, causal=True)
    diff = float(jnp.max(jnp.abs(ring - dense)))
    print(f"[long-context] ring vs dense max |diff| = {diff:.2e}")
    assert diff < 1e-4, diff

    # step 3 — train a causal block over the long sequence
    inp = Input(shape=(T, D))
    x = transformer_block(inp, None, hidden_size=D, n_head=4,
                          intermediate_size=2 * D, dropout=0.0,
                          causal=True)
    x = Lambda(lambda t: t.mean(axis=1), output_shape=(D,))(x)
    out = Dense(2)(x)
    model = Model(inp, out)
    trainer = DistributedTrainer(
        model,
        objectives.get("sparse_categorical_crossentropy_with_logits"),
        optim_method=Adam(lr=1e-3), mesh=ctx.mesh)
    var = model.init(jax.random.PRNGKey(0))
    params = trainer.place_params(var["params"])
    state = trainer.replicate(var["state"])
    opt_state = trainer.init_opt_state(params)

    rs = np.random.RandomState(0)
    bs = max(2, n // seq)
    xb = rs.randn(bs, T, D).astype(np.float32)
    yb = (xb[:, :, 0].mean(-1) > 0).astype(np.int32)[:, None]
    losses = []
    for step in range(args.steps):
        batch = trainer.put_batch((xb, yb))
        params, opt_state, state, loss = trainer.train_step(
            params, opt_state, state, batch, jax.random.PRNGKey(step))
        losses.append(float(loss))
    print(f"[long-context] losses: {[round(l, 4) for l in losses]}")
    assert losses[-1] <= losses[0] + 1e-3
    return {"max_diff": diff, "losses": losses}


if __name__ == "__main__":
    sys.exit(0 if main() else 1)

"""Mixture-of-Experts with expert parallelism.

No reference analogue (the reference is CPU data-parallel only) — this
is TPU-native scale capability in the public GShard/Switch formulation:
a learned router picks top-k experts per token, tokens dispatch to
per-expert buffers through ONE-HOT EINSUMS (dense dispatch — static
shapes, MXU-friendly, no gather/scatter), the expert FFNs run batched
over a stacked expert dimension, and a combine einsum returns gated
outputs.

Expert parallelism is pure GSPMD: the stacked expert weights carry a
``PartitionSpec("expert")`` on their leading axis (``param_pspecs``),
so under a mesh with an ``expert`` axis XLA shards the expert FFN
einsums and inserts the token all_to_all automatically.

The router's load-balancing auxiliary loss (Switch eq. 4) is returned
by ``aux_loss()`` after a forward — add it to the objective via
``CustomLoss`` / a lambda criterion.

``DroplessMoE`` is the layer of the current open decoders: any
``top_k`` of any number of experts with renormalised gates, gated
experts (SiLU or ReLU gate), no capacity and no dropped token, rows
sorted by expert and multiplied by ``ops.grouped_matmul``; told which
experts it holds (``experts_held``), it computes their part of the sum
only — what one rank of an expert-parallel group runs.  Its router has
two forms: the softmax over all experts with the ``top_k`` largest
probabilities, and (``scoring="sigmoid"``) independent sigmoid scores
selected under a bias the weights do not see, normalised and scaled,
beside shared experts that every token passes through.  The router may
read another input than the experts do: the caller forms the routing
with ``route`` and hands it to ``apply``.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from analytics_zoo_tpu.ops import activations as acts
from analytics_zoo_tpu.ops.dtypes import get_policy
from analytics_zoo_tpu.parallel.mesh import EXPERT_AXIS
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    Layer, Params, State,
)


class MoE(Layer):
    """Switch/GShard feed-forward: router → top-k dispatch → per-expert
    2-layer FFN → gated combine.  Input (..., d) keeps its shape."""

    def __init__(self, num_experts: int, hidden_dim: int,
                 top_k: int = 1, capacity_factor: float = 1.25,
                 activation="relu", init="glorot_uniform", **kwargs):
        super().__init__(**kwargs)
        if top_k not in (1, 2):
            raise ValueError("top_k must be 1 or 2")
        self.num_experts = int(num_experts)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        self.capacity_factor = float(capacity_factor)
        self.activation = acts.get(activation)
        self.kernel_init = init
        self._last_aux = None

    def build(self, rng, input_shape) -> Params:
        d = input_shape[-1]
        e, h = self.num_experts, self.hidden_dim
        params: Params = {}
        self.add_weight(params, rng, "router", (d, e),
                        init=self.kernel_init)
        self.add_weight(params, rng, "w1", (e, d, h),
                        init=self.kernel_init)
        self.add_weight(params, rng, "b1", (e, h), init="zero")
        self.add_weight(params, rng, "w2", (e, h, d),
                        init=self.kernel_init)
        self.add_weight(params, rng, "b2", (e, d), init="zero")
        # expert parallelism: shard the stacked expert dim
        for name in ("w1", "b1", "w2", "b2"):
            self.param_pspecs[name] = P(EXPERT_AXIS)
        return params

    def _route(self, probs, tokens: int):
        """probs (T, E) → (combine (T, E, C), aux scalar)."""
        e = self.num_experts
        cap = max(int(math.ceil(
            tokens * self.top_k / e * self.capacity_factor)), 1)

        def one_round(probs, taken):
            """Assign each token its best remaining expert with
            capacity bookkeeping; returns gate-weighted combine slab."""
            expert = jnp.argmax(probs, axis=-1)               # (T,)
            gate = jnp.max(probs, axis=-1)                    # (T,)
            onehot = jax.nn.one_hot(expert, e)                # (T, E)
            # position of each token within its expert's buffer
            pos = jnp.cumsum(onehot, axis=0) - 1.0 + taken[None, :]
            pos_tok = jnp.sum(pos * onehot, axis=-1)          # (T,)
            keep = pos_tok < cap
            slot = jax.nn.one_hot(pos_tok.astype(jnp.int32), cap)
            combine = (gate * keep)[:, None, None] \
                * onehot[:, :, None] * slot[:, None, :]       # (T,E,C)
            new_taken = taken + jnp.sum(onehot * keep[:, None], axis=0)
            return combine, onehot, new_taken

        taken = jnp.zeros((e,), probs.dtype)
        combine, onehot1, taken = one_round(probs, taken)
        if self.top_k == 2:
            probs2 = probs * (1.0 - onehot1)      # mask the 1st choice
            combine2, _, taken = one_round(probs2, taken)
            combine = combine + combine2
        # Switch load-balancing loss: E * sum_e f_e * p_e
        f = jnp.mean(onehot1, axis=0)             # fraction routed
        p = jnp.mean(probs, axis=0)               # mean router prob
        aux = e * jnp.sum(f * p)
        return combine, aux

    def _call_impl(self, params, x, training=False, rng=None):
        policy = get_policy()
        shape = x.shape
        d = shape[-1]
        xt = x.reshape(-1, d)                     # (T, d)
        t = xt.shape[0]

        logits = policy.cast_compute(xt) @ policy.cast_compute(
            params["router"])
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        combine, aux = self._route(probs, t)
        # _trace_aux is the same-trace value consumed by call_with_aux;
        # aux_loss() only sees CONCRETE values — a tracer banked across
        # the trace boundary would leak (and go stale on cached
        # executions)
        self._trace_aux = aux
        self._last_aux = aux if not isinstance(aux, jax.core.Tracer) \
            else None
        dispatch = (combine > 0).astype(xt.dtype)  # (T, E, C)

        # dispatch → per-expert buffers (E, C, d); all_to_all under
        # GSPMD when tokens are data-sharded and experts expert-sharded
        buf = jnp.einsum("tec,td->ecd", dispatch,
                         policy.cast_compute(xt))
        h = jnp.einsum("ecd,edh->ech", buf,
                       policy.cast_compute(params["w1"])) \
            + params["b1"][:, None, :]
        h = self.activation(h) if self.activation else h
        out = jnp.einsum("ech,eho->eco", policy.cast_compute(h),
                         policy.cast_compute(params["w2"])) \
            + params["b2"][:, None, :]
        y = jnp.einsum("tec,eco->to", combine.astype(out.dtype), out)
        return y.reshape(shape).astype(x.dtype)

    def aux_loss(self):
        """Load-balancing loss of the most recent EAGER forward (add to
        the objective, scaled ~1e-2).  Inside jit, use
        ``call_with_aux`` — values stored across a trace boundary
        would be stale tracers."""
        if self._last_aux is None:
            raise ValueError(
                "aux_loss(): no eager forward has run — under jit use "
                "call_with_aux(params, x) to get (output, aux) in the "
                "same trace")
        return self._last_aux

    def call_with_aux(self, params, x, training=False, rng=None):
        """(output, load_balancing_aux) in one trace — the jit-safe
        route for adding the Switch auxiliary loss to an objective."""
        y = self._call_impl(params, x, training=training, rng=rng)
        return y, self._trace_aux

    call = _call_impl

    def compute_output_shape(self, input_shape):
        return input_shape


# ------------------------------------------------------------- dropless
class _Route(NamedTuple):
    """One step's routing as int32 index arrays, both ways, so that
    dispatch, combine and their backward passes are all GATHERS (a
    scatter of 65,536 rows serialises on the TPU).  ``N`` tokens with
    ``k = top_k`` picks each, ``R`` buffer rows.  The assignment side
    is kept ``(k, N)``, tokens along the lanes: the sum over a token's
    picks is then over a leading axis (a ``(N, k, d)`` view would
    re-tile every row), and no index vector changes rank on its way (a
    1-D to 2-D reshape of an index vector is a relayout that took the
    v5e 0.5 ms apiece, forty a step: my chip run, PR 27)."""
    dest: jax.Array        # (k, N) buffer row of an assignment
    held: jax.Array        # (k, N) bool: its expert is held here
    row_pick: jax.Array    # (R,) the pick a buffer row holds
    row_token: jax.Array   # (R,) its token; N where the row holds none


# the experts' gate: SwiGLU and ReGLU
_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _with_zero_row(a):
    """``a`` with one more row, of zeros: what an index of ``N`` reads,
    so that a row that holds no assignment needs no mask."""
    return jnp.concatenate([a, jnp.zeros((1,) + a.shape[1:], a.dtype)])


@jax.custom_vjp
def _dispatch(xt, route: _Route):
    """Token rows (N, d) into the expert-sorted buffer (R, d); rows that
    hold no assignment are zero."""
    return _with_zero_row(xt)[route.row_token]


def _dispatch_fwd(xt, route):
    return _dispatch(xt, route), route


def _dispatch_bwd(route, d_buf):
    rows = jnp.where(route.held[..., None], d_buf[route.dest], 0)
    return jnp.sum(rows, axis=0, dtype=jnp.float32).astype(d_buf.dtype), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y_buf, gates, route: _Route):
    """Each token's gated sum (N, d) float32 of the buffer rows its held
    assignments produced; ``gates`` (k, N) float32."""
    return jnp.einsum("kn,knd->nd", gates, _assigned_rows(y_buf, route))


def _assigned_rows(y_buf, route):
    return jnp.where(route.held[..., None], y_buf[route.dest],
                     0).astype(jnp.float32)


def _combine_fwd(y_buf, gates, route):
    return _combine(y_buf, gates, route), (y_buf, gates, route)


def _combine_bwd(res, dy):
    y_buf, gates, route = res
    gate_row = _with_zero_row(gates.T)[route.row_token, route.row_pick]
    # the rows are gathered in the buffer's dtype: gathered in float32
    # they are 570 MB a layer, 3.3 ms on the v5e (my chip run, PR 27)
    d_buf = gate_row[:, None] * _with_zero_row(
        dy.astype(y_buf.dtype))[route.row_token]
    d_gates = jnp.einsum("nd,knd->kn", dy, _assigned_rows(y_buf, route))
    return d_buf.astype(y_buf.dtype), d_gates, None


_combine.defvjp(_combine_fwd, _combine_bwd)


class DroplessMoE(Layer):
    """Router over all ``num_experts`` → the ``top_k`` largest
    probabilities (renormalised to sum to one under ``norm_topk_prob``)
    → gated experts ``(act(x Wg) * (x Wu)) Wd`` → gated sum, ``act``
    the ``activation``: ``"silu"`` (SwiGLU) or ``"relu"`` (ReGLU).  Input
    ``(B, T, d)``; outputs ``[y, aux]``: ``y`` of the input's shape and
    the load-balance term of each sequence, ``(B,)``:
    ``num_experts * sum_e f_e * p_e`` with ``f_e`` the share of the
    sequence's assignments that went to expert ``e`` and ``p_e`` its
    mean router probability.

    Dropless: the ``B T top_k`` assignments are sorted by expert into a
    buffer of static length (``ops.grouped_matmul.buffer_rows``), so no
    capacity exists and no token is dropped at any imbalance.

    ``experts_held=(first, count)`` (default: all): the layer holds the
    weights of experts ``first .. first + count - 1`` only.  The router
    keeps its full width; assignments to the other experts fall in the
    buffer's tail, which no kernel touches, and ``y`` is the held
    experts' part of the sum.  Nothing stands in for the absent chips
    or their exchange: under an ``expert`` mesh axis this is what each
    rank runs between the two all-to-alls, which are not built here.

    ``scoring="sigmoid"`` (the auxiliary-loss-free router): the scores
    are ``s = sigmoid(float32(x) Wr)``, each expert's own; the ``top_k``
    experts with the largest ``s + b`` are selected, ``b`` the state
    leaf ``selection_bias`` (zeros until a checkpoint or a balancing
    rule sets it: it is no parameter and gets no gradient); the weights
    are the UNBIASED scores of the selected, normalised to sum to one
    under ``norm_topk_prob``, times ``routed_scaling_factor``.  The
    product is float32 at the highest precision (a rounding that swaps
    the sixth and seventh expert of a token changes which rows exist).
    ``aux`` is then zero: this router is balanced through ``b``, not
    through the loss.

    ``shared_hidden``: one more gated expert of that width which every
    token passes through, ``y += (silu(x Wg) * (x Wu)) Ws`` (several
    shared experts of a published model are one of their summed width).
    It is whole on every rank: the ranks' shares of ``y`` add up to the
    uncut layer's once the shared part is counted once.

    Routed from another input: ``apply(..., routing=route(router, a))``
    sends ``x`` to the experts that ``a`` selected, with ``a``'s gates
    (a router placed before attention reads the attention's input);
    ``aux`` is then the routing's.

    State (not trained, carried like BatchNorm's moving statistics):
    ``rows_routed`` (count + 1,) int32 — assignments so far to each
    held expert and, last, to all the others; wraps at 2**32.
    ``observability.moe_stats`` publishes it at the host's syncs."""

    def __init__(self, num_experts: int, hidden_dim: int, top_k: int = 2,
                 norm_topk_prob: bool = True, experts_held=None,
                 block_rows: int = 256, init="glorot_uniform",
                 scoring: str = "softmax",
                 routed_scaling_factor: float = 1.0,
                 shared_hidden: int = 0, activation: str = "silu",
                 **kwargs):
        super().__init__(**kwargs)
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
        if activation not in _GATES:
            raise ValueError(f"activation {activation!r}: silu or relu")
        self.scoring = scoring
        self.activation = activation
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.shared_hidden = int(shared_hidden)
        self.num_experts = int(num_experts)
        self.hidden_dim = int(hidden_dim)
        self.top_k = int(top_k)
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k {top_k} of {num_experts} experts")
        self.norm_topk_prob = bool(norm_topk_prob)
        self.first, self.count = (0, self.num_experts) \
            if experts_held is None else map(int, experts_held)
        if not 0 <= self.first < self.first + self.count \
                <= self.num_experts:
            raise ValueError(
                f"experts_held {experts_held} outside "
                f"0..{self.num_experts}")
        self.block_rows = int(block_rows)
        self.kernel_init = init

    def build(self, rng, input_shape) -> Params:
        d, e, h = input_shape[-1], self.count, self.hidden_dim
        params: Params = {}
        self.add_weight(params, rng, "router", (d, self.num_experts),
                        init=self.kernel_init)
        self.add_weight(params, rng, "gate", (e, d, h),
                        init=self.kernel_init)
        self.add_weight(params, rng, "up", (e, d, h),
                        init=self.kernel_init)
        self.add_weight(params, rng, "down", (e, h, d),
                        init=self.kernel_init)
        if self.shared_hidden:
            self.add_weight(params, rng, "shared_gate_up",
                            (d, 2 * self.shared_hidden),
                            init=self.kernel_init)
            self.add_weight(params, rng, "shared_down",
                            (self.shared_hidden, d), init=self.kernel_init)
        return params

    def init_state(self, input_shape) -> State:
        state = {"rows_routed": jnp.zeros((self.count + 1,), jnp.int32)}
        if self.scoring == "sigmoid":
            state["selection_bias"] = jnp.zeros((self.num_experts,),
                                                jnp.float32)
        return state

    def compute_output_shape(self, input_shape):
        return [tuple(input_shape), (input_shape[0],)]

    def route(self, router, x, bias=None, precision=None):
        """``(gates (N, k) float32, experts (N, k) int32, aux (B,))``
        for ``x`` (B, T, d): the router in float32 over all experts;
        ``bias`` (num_experts,): the sigmoid scores' selection bias.
        The router's product is float32 at ``precision`` where one is
        given (the sigmoid router's always, at the highest), else in the
        compute dtype accumulated in float32."""
        policy = get_policy()
        b, t, d = x.shape
        if self.scoring == "sigmoid":
            precision = jax.lax.Precision.HIGHEST
        if precision is not None:
            logits = jnp.matmul(x.reshape(b * t, d).astype(jnp.float32),
                                router.astype(jnp.float32),
                                precision=precision)
        else:
            logits = jax.lax.dot_general(
                policy.cast_compute(x.reshape(b * t, d)),
                policy.cast_compute(router), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        if self.scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, experts = jax.lax.top_k(
                jax.lax.stop_gradient(scores + bias), self.top_k)
            gates = jnp.take_along_axis(scores, experts, axis=-1)
            if self.norm_topk_prob:
                gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
            return (gates * self.routed_scaling_factor, experts,
                    jnp.zeros((b,), jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk_prob:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        picked = jnp.sum(jax.nn.one_hot(experts, self.num_experts,
                                        dtype=jnp.float32), axis=1)
        share = jnp.mean(picked.reshape(b, t, -1), axis=1) / self.top_k
        mean_prob = jnp.mean(probs.reshape(b, t, -1), axis=1)
        aux = self.num_experts * jnp.sum(share * mean_prob, axis=-1)
        return gates, experts, aux

    def layout(self, experts):
        """The step's ``(_Route, GroupLayout, rows (count + 1,))`` from
        the experts picked, (N, k) int32."""
        from analytics_zoo_tpu.ops import grouped_matmul as gmm
        count = self.count
        picked = experts.T                                   # (k, N)
        k, n = picked.shape
        held = (picked >= self.first) & (picked < self.first + count)
        local = jnp.where(held, picked - self.first, count)
        # an assignment's rank among those to the same expert (pick by
        # pick, tokens in order): a running count per group, no sort
        onehot = (local[None] == jnp.arange(count + 1)[:, None, None]
                  ).astype(jnp.int32)                        # (G + 1, k, N)
        running = jnp.cumsum(onehot, axis=2)
        per_pick = running[:, :, -1]                         # (G + 1, k)
        before = jnp.cumsum(per_pick, axis=1) - per_pick
        rank = jnp.sum(onehot * (running - 1 + before[:, :, None]), axis=0)
        rows = jnp.sum(per_pick, axis=1)
        buf = gmm.buffer_rows(k * n, count, self.block_rows)
        layout = gmm.group_layout(rows[:count], buf, self.block_rows)
        start = jnp.sum(onehot[:count] * layout.starts[:, None, None],
                        axis=0)
        dest = jnp.where(held, start + rank, buf)
        pick = jax.lax.broadcasted_iota(jnp.int32, (k, n), 0)
        token = jax.lax.broadcasted_iota(jnp.int32, (k, n), 1)
        row_pick = jnp.zeros((buf,), jnp.int32).at[dest].set(
            pick, mode="drop")
        row_token = jnp.full((buf,), n, jnp.int32).at[dest].set(
            token, mode="drop")
        route = _Route(jnp.minimum(dest, buf - 1), held, row_pick,
                       row_token)
        return route, layout, rows

    def apply(self, params, x, state=None, training=False, rng=None,
              routing=None):
        """``routing``: what ``route`` gave for another input of the
        same tokens; default, ``x`` routes itself."""
        from analytics_zoo_tpu.ops.fused import count_build
        from analytics_zoo_tpu.ops.grouped_matmul import grouped_matmul
        compute = get_policy().compute_dtype
        b, t, d = x.shape
        if routing is None:
            routing = self.route(
                params["router"], x,
                state["selection_bias"] if self.scoring == "sigmoid"
                else None)
        gates, experts, aux = routing
        route, layout, rows = self.layout(experts)
        gate_act = _GATES[self.activation]
        if self.activation == "relu":
            count_build("moe_experts", "reglu")

        def held_experts(xt, gates, gate, up, down):
            # recomputed in the backward pass: the buffers are sized for
            # the worst split (every assignment held here), 0.8 GB a
            # layer if kept
            x_buf = _dispatch(xt, route)
            g = grouped_matmul(x_buf, gate, layout)
            u = grouped_matmul(x_buf, up, layout)
            h = (gate_act(g.astype(jnp.float32))
                 * u.astype(jnp.float32)).astype(compute)
            return _combine(grouped_matmul(h, down, layout), gates, route)

        y = jax.checkpoint(held_experts)(
            x.reshape(b * t, d).astype(compute), gates.T, params["gate"],
            params["up"], params["down"])
        y = y.reshape(x.shape)
        if self.shared_hidden:
            from analytics_zoo_tpu.pipeline.api.keras.layers.ssm import (
                gated_feed_forward)
            y = y + gated_feed_forward(x, params["shared_gate_up"],
                                       params["shared_down"])
        new_state = state
        if state is not None:
            new_state = {**state,
                         "rows_routed": state["rows_routed"] + rows}
        return [y.astype(x.dtype), aux], new_state

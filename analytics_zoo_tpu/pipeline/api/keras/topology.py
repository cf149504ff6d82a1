"""Sequential / graph Model containers + the KerasNet training surface.

Reference: zoo/pipeline/api/keras/models/Topology.scala —
``KerasNet`` (compile/fit/evaluate/predict, :64-601), graph ``Model``
(:603-824), ``Sequential`` with shape inference on add (:826-959).

TPU redesign: containers are pure-functional (see engine.py); the
training surface lowers to one jit-compiled train step over the device
mesh (parallel/trainer.py) instead of the reference's
InternalDistriOptimizer Spark job per iteration.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.observability.tracing import get_tracer
from analytics_zoo_tpu.pipeline.api.keras.engine import (
    Container, KTensor, Layer, Node, Params, State, fold_name,
    tap_activation, to_batch_shape, _is_shape,
)


def _count_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


class KerasNet(Container):
    """Training/eval/predict facade shared by Sequential and Model.

    Mirrors KerasNet (Topology.scala:64-601): ``compile`` captures
    optimizer/loss/metrics; ``fit`` dispatches to the distributed
    estimator; checkpoint/tensorboard/clipping setters carry through.
    """

    def __init__(self, **kwargs):
        super().__init__(**kwargs)
        self.optim_method = None
        self.loss = None
        self.metrics = None
        self._tb_log_dir = None
        self._tb_app_name = None
        self._checkpoint_path = None
        self._checkpoint_trigger = None
        self._overwrite_checkpoint = True
        self._gradient_clipping = None   # ("const", min, max) | ("l2norm", v)
        self._variables = None           # {"params":..., "state":...}
        self._rng = jax.random.PRNGKey(0)

    # ------------------------------------------------------------ variables
    def init(self, rng=None, input_shape=None):
        rng = rng if rng is not None else self._rng
        variables = super().init(rng, input_shape)
        self._variables = variables
        return variables

    def get_variables(self):
        if self._variables is None:
            # every leaf is drawn here, on the default device: a
            # start-up phase of its own (a caller that then lays its
            # own weights over them has paid for both)
            with get_tracer().span("startup_init_variables"):
                self.init()
        return self._variables

    def set_variables(self, variables):
        self._variables = variables

    def get_weights(self) -> List[np.ndarray]:
        leaves = jax.tree_util.tree_leaves(self.get_variables()["params"])
        return [np.asarray(w) for w in leaves]

    def set_weights(self, weights: Sequence[np.ndarray]):
        variables = self.get_variables()
        leaves, treedef = jax.tree_util.tree_flatten(variables["params"])
        assert len(leaves) == len(weights), \
            f"expected {len(leaves)} arrays, got {len(weights)}"
        new = [jnp.asarray(w).reshape(l.shape).astype(l.dtype)
               for l, w in zip(leaves, weights)]
        variables["params"] = jax.tree_util.tree_unflatten(treedef, new)
        self._variables = variables

    # -------------------------------------------------------------- compile
    def compile(self, optimizer, loss, metrics=None):
        """Configure training (Topology.scala:136-160).

        optimizer: name ("sgd"/"adam"/...) or optimizers.OptimMethod
        loss: name ("mse"/...) or objectives.Objective or callable
        metrics: list of names / metrics.Metric
        """
        from analytics_zoo_tpu.pipeline.api.keras import optimizers as opt_lib
        from analytics_zoo_tpu.pipeline.api.keras import objectives as obj_lib
        from analytics_zoo_tpu.pipeline.api.keras import metrics as met_lib
        self.optim_method = opt_lib.get(optimizer)
        self.loss = obj_lib.get(loss)
        self.metrics = [met_lib.get(m) for m in (metrics or [])]
        return self

    # -------------------------------------------------- training facilities
    def set_tensorboard(self, log_dir: str, app_name: str):
        self._tb_log_dir = log_dir
        self._tb_app_name = app_name

    def set_checkpoint(self, path: str, over_write: bool = True,
                       trigger=None):
        self._checkpoint_path = path
        self._overwrite_checkpoint = over_write
        self._checkpoint_trigger = trigger

    def set_constant_gradient_clipping(self, min_value: float,
                                       max_value: float):
        self._gradient_clipping = ("const", float(min_value), float(max_value))

    def set_gradient_clipping_by_l2_norm(self, clip_norm: float):
        self._gradient_clipping = ("l2norm", float(clip_norm))

    def clear_gradient_clipping(self):
        self._gradient_clipping = None

    # ------------------------------------------------------------------ fit
    def fit(self, x, y=None, batch_size: int = 32, nb_epoch: int = 10,
            validation_data=None, validation_split: float = 0.0,
            shuffle: bool = True, rng=None):
        """Train on ndarrays, a FeatureSet, or a resumable DataPipeline
        (Topology.scala:344-492; docs/data.md)."""
        from analytics_zoo_tpu.data import DataPipeline
        from analytics_zoo_tpu.pipeline.estimator import Estimator
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        from analytics_zoo_tpu.common.triggers import MaxEpoch, EveryEpoch

        if isinstance(x, (FeatureSet, DataPipeline)):
            if validation_split:
                raise ValueError(
                    "validation_split is not supported when x is a "
                    "FeatureSet/DataPipeline; pass validation_data "
                    "instead")
            train_set = x
        else:
            x_arr, y_arr = x, y
            if validation_split and validation_data is None:
                n = len(jax.tree_util.tree_leaves(x_arr)[0])
                cut = int(n * (1 - validation_split))
                take = lambda t, s: jax.tree_util.tree_map(lambda a: a[s], t)
                validation_data = (take(x_arr, slice(cut, None)),
                                   take(y_arr, slice(cut, None)))
                x_arr = take(x_arr, slice(0, cut))
                y_arr = take(y_arr, slice(0, cut))
            train_set = FeatureSet.from_ndarrays(
                x_arr, y_arr, shuffle=shuffle)

        val_set = None
        if validation_data is not None:
            if isinstance(validation_data, (FeatureSet, DataPipeline)):
                val_set = validation_data
            else:
                vx, vy = validation_data
                val_set = FeatureSet.from_ndarrays(vx, vy, shuffle=False)

        estimator = Estimator(self, optim_method=self.optim_method,
                              model_dir=self._checkpoint_path)
        if self._gradient_clipping is not None:
            kind = self._gradient_clipping[0]
            if kind == "const":
                estimator.set_constant_gradient_clipping(
                    *self._gradient_clipping[1:])
            else:
                estimator.set_l2_norm_gradient_clipping(
                    self._gradient_clipping[1])
        if self._tb_log_dir is not None:
            estimator.set_tensorboard(self._tb_log_dir, self._tb_app_name)

        # Always report at least the validation loss, Keras-style.
        validation_method = list(self.metrics or [])
        if val_set is not None and not validation_method:
            from analytics_zoo_tpu.pipeline.api.keras.metrics import Loss
            validation_method = [Loss(self.loss)]

        estimator.train(
            train_set, self.loss, end_trigger=MaxEpoch(nb_epoch),
            checkpoint_trigger=self._checkpoint_trigger or EveryEpoch(),
            validation_set=val_set,
            validation_method=validation_method,
            batch_size=batch_size, rng=rng)
        self._variables = estimator.variables
        return estimator.history

    # ------------------------------------------------------------- evaluate
    def evaluate(self, x, y=None, batch_size: int = 32):
        """Compute loss + metrics over a dataset (Topology.scala:497-536)."""
        from analytics_zoo_tpu.feature.feature_set import FeatureSet
        if isinstance(x, FeatureSet):
            data = x
        else:
            data = FeatureSet.from_ndarrays(x, y, shuffle=False)
        return self._infer_estimator().evaluate(
            data, self.loss, validation_method=self.metrics or [],
            batch_size=batch_size)

    # -------------------------------------------------------------- predict
    def _infer_estimator(self):
        """Cached inference estimator: the jitted predict/eval programs
        compile once per model, not once per call."""
        if not hasattr(self, "_cached_infer_estimator"):
            from analytics_zoo_tpu.pipeline.estimator import Estimator
            self._cached_infer_estimator = Estimator(self, optim_method=None)
        return self._cached_infer_estimator

    def predict(self, x, batch_size: int = 256):
        """Batched distributed inference (Predictor.scala:37-224 analogue:
        the model is already resident on every device via replicated
        params; batches are sharded over the mesh's data axis)."""
        return self._infer_estimator().predict(x, batch_size=batch_size)

    def predict_classes(self, x, batch_size: int = 256,
                        zero_based_label: bool = True):
        out = self.predict(x, batch_size=batch_size)
        classes = np.argmax(np.asarray(out), axis=-1)
        return classes if zero_based_label else classes + 1

    # ------------------------------------------------------- quantization
    def quantize(self, calib_data, batch_size: int = 32,
                 max_batches: int = 8, min_size: int = 1024):
        """Calibrated int8 conversion IN PLACE: record per-layer input
        ranges over ``calib_data`` (eager forwards), rewrite eligible
        kernels to int8 + per-output-channel scales in the
        params-driven layout (ops/quant.py), and install the quantized
        variables on this model — every later ``predict``/serving call
        executes ``quantized_matmul`` on the MXU (int8 peak is 2x bf16
        on v5e, and weight HBM traffic drops 4x — the recommendation
        zoo's bandwidth-starvation lever).  Training on a quantized
        model is not supported; re-``init`` or reload weights to go
        back to f32.  Returns self."""
        from analytics_zoo_tpu.ops.quant import (
            calibrate_model, quantize_model)
        ranges = calibrate_model(self, calib_data,
                                 batch_size=batch_size,
                                 max_batches=max_batches)
        self.set_variables(quantize_model(
            self.get_variables(), ranges, min_size=min_size))
        # drop the cached inference estimator: its jitted predict was
        # traced over the f32 params signature
        if hasattr(self, "_cached_infer_estimator"):
            del self._cached_infer_estimator
        return self

    @property
    def is_quantized(self) -> bool:
        params = (self._variables or {}).get("params", {})
        return any("kernel_scale" in p for p in params.values()
                   if isinstance(p, dict))

    def predict_mc(self, x, n_samples: int = 10, batch_size: int = 256,
                   rng=None):
        """Monte-Carlo (training-mode) prediction for uncertainty
        estimation: runs the forward pass with dropout active."""
        import jax as _jax
        if rng is None:
            rng = _jax.random.PRNGKey(0)
        variables = self.get_variables()
        xd = jnp.asarray(x)
        # sliding-window fetch (the predict_in_batches idiom): pulling
        # per iteration would block the dispatch pipeline on every MC
        # sample, while keeping all n_samples outputs on device risks
        # HBM for big batches — `window` samples stay in flight
        window = 8
        outs, in_flight = [], []
        for i in range(n_samples):
            out, _ = self.apply(variables["params"], xd,
                                state=variables["state"], training=True,
                                rng=_jax.random.fold_in(rng, i))
            in_flight.append(out)
            if len(in_flight) >= window:
                outs.append(_jax.device_get(in_flight.pop(0)))
        outs.extend(_jax.device_get(in_flight))
        return np.stack(outs)

    # -------------------------------------------------------------- summary
    def summary(self, line_length: int = 100):
        """Print a layer table (Topology.scala summary)."""
        variables = self.get_variables()
        print("_" * line_length)
        print(f"{'Layer (type)':40s}{'Output Shape':30s}{'Param #':12s}")
        print("=" * line_length)
        total = 0
        for l in self.layers:
            p = variables["params"].get(l.name, {})
            n = _count_params(p)
            total += n
            try:
                shape = str(l.get_output_shape())
            except ValueError:
                shape = "?"
            print(f"{l.name + ' (' + type(l).__name__ + ')':40s}"
                  f"{shape:30s}{n:<12d}")
        print("=" * line_length)
        print(f"Total params: {total}")
        print("_" * line_length)
        return total

    # ------------------------------------------- transfer-learning surgery
    def freeze(self, *names: str) -> "KerasNet":
        """Mark layers non-trainable (NetUtils.scala:267 ``freeze``).

        With no names, freezes every layer.  Frozen layers keep their
        params bit-identical through training: their params are
        wrapped in ``stop_gradient`` during the forward pass and the
        training engine masks their optimizer update.  Call before
        ``fit`` (each fit builds a fresh trainer from current flags).
        """
        targets = self._layers_by_names(names) if names else self.layers
        for l in targets:
            l.trainable = False
        return self

    def unfreeze(self, *names: str) -> "KerasNet":
        """Re-enable training (NetUtils.scala:276 ``unFreeze``); no
        names = all layers."""
        targets = self._layers_by_names(names) if names else self.layers
        for l in targets:
            l.trainable = True
        return self

    def frozen_layer_names(self):
        return {l.name for l in self.layers
                if not getattr(l, "trainable", True)}

    def init_from(self, donor: "KerasNet", rng=None):
        """Init this net, then adopt the donor's variables for every
        layer shared (by name) — the transfer-learning init: stack a
        new head on ``new_graph(...)`` outputs, then
        ``ft.init_from(pretrained)`` before ``fit``."""
        self.init(rng)
        dv = donor.get_variables()
        for l in self.layers:
            if l.name in dv["params"]:
                self._variables["params"][l.name] = dv["params"][l.name]
                if l.name in dv.get("state", {}):
                    self._variables["state"][l.name] = dv["state"][l.name]
        return self._variables

    def _layers_by_names(self, names):
        by_name = {l.name: l for l in self.layers}
        missing = [n for n in names if n not in by_name]
        if missing:
            raise ValueError(
                f"no such layer(s): {missing}; have {sorted(by_name)}")
        return [by_name[n] for n in names]

    @staticmethod
    def _layer_params(params, layer):
        """Layer params with stop_gradient applied when frozen."""
        p = params[layer.name]
        if not getattr(layer, "trainable", True):
            p = jax.tree_util.tree_map(jax.lax.stop_gradient, p)
        return p

    # ------------------------------------------------------------ save/load
    def save_model(self, path: str, over_write: bool = True):
        from analytics_zoo_tpu.utils.serialization import save_variables
        save_variables(path, self.get_variables(), over_write=over_write)

    def load_weights(self, path: str):
        from analytics_zoo_tpu.utils.serialization import load_variables
        self._variables = load_variables(path, like=self.get_variables())
        return self


class Sequential(KerasNet):
    """Layer stack with shape inference on ``add``
    (Topology.scala:826-959)."""

    def __init__(self, name: Optional[str] = None):
        super().__init__(name=name)
        self._running_shape = None

    def add(self, layer: Layer) -> "Sequential":
        if not self.layers:
            shape = layer.batch_input_shape
            if shape is None and isinstance(layer, Sequential):
                shape = layer.layers[0].batch_input_shape if layer.layers \
                    else None
            if shape is None:
                raise ValueError(
                    f"first layer {layer.name} needs input_shape")
            self.batch_input_shape = shape
            self._running_shape = shape
        else:
            if layer.batch_input_shape is None:
                layer.batch_input_shape = (
                    self._running_shape if _is_shape(self._running_shape)
                    else None)
        self._running_shape = layer.compute_output_shape(
            layer.batch_input_shape if layer.batch_input_shape is not None
            else self._running_shape)
        self.layers.append(layer)
        self._check_duplicate()
        self._output_shape = self._running_shape
        return self

    def compute_output_shape(self, input_shape):
        shape = input_shape
        for l in self.layers:
            shape = l.compute_output_shape(shape)
        return shape

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        self._sub_state = {}
        shape = input_shape
        for l in self.layers:
            sub = l.init(fold_name(rng, l.name), shape)
            params[l.name] = sub["params"]
            self._sub_state[l.name] = sub["state"]
            shape = l.compute_output_shape(shape)
        return params

    def init_state(self, input_shape) -> State:
        # build() has already collected sub-states in order.
        return getattr(self, "_sub_state", {})

    def apply(self, params, inputs, state=None, training=False, rng=None):
        state = state or {}
        new_state = dict(state)
        x = inputs
        for i, l in enumerate(self.layers):
            sub_rng = fold_name(rng, l.name) if rng is not None else None
            tap_activation(l.name, x)
            x, s = l.apply(self._layer_params(params, l), x,
                           state=state.get(l.name),
                           training=training, rng=sub_rng)
            if s is not None:
                new_state[l.name] = s
        return x, new_state


class Model(KerasNet):
    """Multi-input/multi-output static graph (Topology.scala:603-824)."""

    def __init__(self, input, output, name: Optional[str] = None):
        super().__init__(name=name)
        self.inputs: List[KTensor] = (
            list(input) if isinstance(input, (list, tuple)) else [input])
        self.outputs: List[KTensor] = (
            list(output) if isinstance(output, (list, tuple)) else [output])
        self._single_input = not isinstance(input, (list, tuple))
        self._single_output = not isinstance(output, (list, tuple))
        self._topo: List[Node] = self._topological_sort()
        self.layers = []
        seen = set()
        for node in self._topo:
            if node.layer.name not in seen:
                seen.add(node.layer.name)
                self.layers.append(node.layer)
        self._check_duplicate()
        in_shapes = [t.shape for t in self.inputs]
        self.batch_input_shape = in_shapes[0] if self._single_input \
            else in_shapes
        out_shapes = [t.shape for t in self.outputs]
        self._output_shape = out_shapes[0] if self._single_output \
            else out_shapes

    def _topological_sort(self) -> List[Node]:
        order: List[Node] = []
        visited = set()
        input_ids = {id(t) for t in self.inputs}

        def visit(t: KTensor):
            if id(t) in input_ids or t.node is None:
                if t.node is None and id(t) not in input_ids:
                    raise ValueError(
                        "graph reaches a placeholder not listed in inputs")
                return
            node = t.node
            if id(node) in visited:
                return
            visited.add(id(node))
            for src in node.inbound:
                visit(src)
            order.append(node)

        for t in self.outputs:
            visit(t)
        return order

    def compute_output_shape(self, input_shape):
        return self._output_shape

    # ------------------------------------------- transfer-learning surgery
    def freeze_up_to(self, *names: str) -> "Model":
        """Freeze every layer from the inputs up to AND including the
        named layers (NetUtils.scala:267 ``freezeUpTo``) — the usual
        "freeze the backbone, fine-tune the head" move."""
        self._layers_by_names(names)   # validate
        targets = set(names)
        frozen_layers = set()
        visited = set()   # node ids — a shared layer's nodes each get
        # their own ancestor walk

        def visit(node: Node):
            if id(node) in visited:
                return
            visited.add(id(node))
            frozen_layers.add(node.layer.name)
            for t in node.inbound:
                if t.node is not None:
                    visit(t.node)

        for node in self._topo:
            if node.layer.name in targets:
                visit(node)
        for l in self.layers:
            if l.name in frozen_layers:
                l.trainable = False
        return self

    def new_graph(self, outputs) -> "Model":
        """Subgraph extraction (NetUtils.scala:82 ``newGraph``): a new
        Model over the SAME layer objects whose outputs are the named
        layers' outputs — cut a trained net at an intermediate layer
        and stack a new head on ``m.outputs`` for transfer learning.
        Trained variables of retained layers carry over; freeze flags
        are shared with the parent (same layer objects).  For a layer
        applied more than once, the last call's output is used.
        """
        names = [outputs] if isinstance(outputs, str) else list(outputs)
        tensor_of = {}
        for node in self._topo:
            tensor_of[node.layer.name] = (
                node.outputs[0] if len(node.outputs) == 1
                else list(node.outputs))
        missing = [n for n in names if n not in tensor_of]
        if missing:
            raise ValueError(
                f"no such layer(s): {missing}; have {sorted(tensor_of)}")
        outs: List[KTensor] = []
        for n in names:
            t = tensor_of[n]
            outs.extend(t if isinstance(t, list) else [t])
        sub = Model(self.inputs if not self._single_input
                    else self.inputs[0],
                    outs if len(outs) > 1 else outs[0])
        if self._variables is not None:
            params = self._variables["params"]
            state = self._variables["state"]
            sub._variables = {
                "params": {l.name: params[l.name] for l in sub.layers
                           if l.name in params},
                "state": {l.name: state[l.name] for l in sub.layers
                          if l.name in state},
            }
        return sub

    def build(self, rng, input_shape) -> Params:
        params: Params = {}
        self._sub_state: State = {}
        shapes: Dict[int, Shape] = {id(t): t.shape for t in self.inputs}
        built = set()
        for node in self._topo:
            in_shapes = [shapes[id(t)] for t in node.inbound]
            l = node.layer
            if l.name not in built:
                built.add(l.name)
                shape_arg = in_shapes[0] if len(in_shapes) == 1 else in_shapes
                sub = l.init(fold_name(rng, l.name), shape_arg)
                params[l.name] = sub["params"]
                self._sub_state[l.name] = sub["state"]
            for t in node.outputs:
                shapes[id(t)] = t.shape
        return params

    def init_state(self, input_shape) -> State:
        return getattr(self, "_sub_state", {})

    def apply(self, params, inputs, state=None, training=False, rng=None):
        state = state or {}
        new_state = dict(state)
        in_list = [inputs] if not isinstance(inputs, (list, tuple)) \
            else list(inputs)
        if len(in_list) != len(self.inputs):
            raise ValueError(
                f"model {self.name} expects {len(self.inputs)} inputs, "
                f"got {len(in_list)}")
        values: Dict[int, Any] = {
            id(t): v for t, v in zip(self.inputs, in_list)}
        for node in self._topo:
            l = node.layer
            args = [values[id(t)] for t in node.inbound]
            x = args[0] if len(args) == 1 else args
            sub_rng = fold_name(rng, l.name) if rng is not None else None
            tap_activation(l.name, x)
            out, s = l.apply(self._layer_params(params, l), x,
                             state=state.get(l.name),
                             training=training, rng=sub_rng,
                             **node.call_kwargs)
            if s is not None:
                new_state[l.name] = s
            outs = out if isinstance(out, (list, tuple)) else [out]
            for t, v in zip(node.outputs, outs):
                values[id(t)] = v
        results = [values[id(t)] for t in self.outputs]
        return (results[0] if self._single_output else results), new_state


Shape = Any  # re-exported typing convenience

"""One run of one cell: load, warm up, measure, check, print one line.

The window drives the user's entry: a compiled keras ``Model``, then
``Estimator(model, optim_method=...).train(data, loss, end_trigger=...,
batch_size=...)`` under the default config.  The harness's only hook
into the loop is its own end trigger (``WindowTrigger``), which the
program calls at every epoch boundary on the scan engines and after
every step on the ``DataPipeline`` path.

Facts of the program this file relies on (see README.md):

* ``Estimator.train`` tests ``isinstance(end_trigger, MaxEpoch)`` before
  it takes the scan engines, so the trigger subclasses ``MaxEpoch``;
* ``Estimator.train`` keeps the live training state in locals named
  ``params``, ``opt_state`` and ``loss``; the trigger reads them from
  the caller's frame (it changes nothing) to take the state that the
  comparison needs after the first steps;
* the optimizer state is an optax state whose first moment sits under an
  attribute named ``trace`` (SGD) or ``mu`` (Adam).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchmarkError(RuntimeError):
    """The run cannot give a result (no chip, a compile in the window,
    a file missing): the command exits non-zero and prints no line."""


# ------------------------------------------------------------------ files
def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` by the name ``BENCHMARK.json``
    gives (names may hold ``-`` and ``.``, so not an import statement)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no file {path}")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    mod_name = "benchmark.%s.%s" % (
        kind, "".join(c if c.isalnum() else "_" for c in name))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    importlib.import_module(f"benchmark.{kind}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: Optional[Dict] = None) -> Tuple[Dict, Dict, Dict]:
    """(the cell's entry, its file, its configuration's file)."""
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        raise BenchmarkError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    cell = load_json(os.path.join(HERE, "workloads", name + ".json"))
    cfg_entry = [c for c in bench["configs"] if c["name"] == entry["config"]][0]
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    return entry, cell, cfg


def peaks_for(kind: str) -> Dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["kinds"]
    if kind not in table:
        raise BenchmarkError(
            f"device kind {kind!r} is not in peaks.json: add its published "
            "peaks with their source, do not default")
    return table[kind]


# ----------------------------------------------------------- program tree
def program_leaves(params) -> List[Tuple[str, str]]:
    """(layer, key) of every parameter of the program's tree, in the
    order the layers were created and their weights added."""
    return [(layer, key) for layer, sub in params.items() for key in sub]


def to_program(order: List[str], ref_params: Dict, like) -> Dict:
    """The reference's flat ``{name: array}`` laid into the program's
    ``{layer: {key: array}}`` tree, leaf for leaf in creation order
    (``like`` is the model's own tree: a mapped copy sorts its keys)."""
    slots = program_leaves(like)
    if len(slots) != len(order):
        raise BenchmarkError(
            f"the program has {len(slots)} parameters, the reference "
            f"{len(order)}")
    out = {layer: {} for layer in like}
    for (layer, key), name in zip(slots, order):
        a = ref_params[name]
        if tuple(a.shape) != tuple(like[layer][key].shape):
            raise BenchmarkError(
                f"{layer}/{key} {like[layer][key].shape} is not "
                f"{name} {a.shape}")
        out[layer][key] = a
    return out


def from_program(order: List[str], slots: List[Tuple[str, str]],
                 tree) -> Dict:
    """A tree of the program's under the reference's names; ``slots``
    is ``program_leaves`` of the model's own tree."""
    return {name: tree[layer][key]
            for (layer, key), name in zip(slots, order)}


STATE_KEYS = (("moving_mean", ".mean"), ("moving_var", ".var"))


def state_from_program(reference, cfg: Dict, order_of_layers, tree) -> Dict:
    """The program's non-trained state (BatchNorm's moving statistics)
    under the reference's names: the layers that hold any, in creation
    order, against the reference's ``state_init`` order."""
    names = list(reference.state_init(cfg))
    layers = [l for l in order_of_layers if tree.get(l)]
    out = {}
    for i, layer in enumerate(layers):
        for key, suffix in STATE_KEYS:
            name = names[2 * i + (suffix == ".var")]
            if not name.endswith(suffix):
                raise BenchmarkError(f"state order: {layer}/{key} vs {name}")
            out[name] = tree[layer][key]
    if len(out) != len(names):
        raise BenchmarkError(
            f"the program holds {len(out)} state leaves, the reference "
            f"{len(names)}")
    return out


def first_moment(opt_state):
    """The params-shaped first-moment tree of an optax state."""
    import jax
    found = []

    def visit(node):
        for attr in ("trace", "mu"):
            if hasattr(node, attr) and isinstance(getattr(node, attr), dict):
                found.append(getattr(node, attr))
                return
        if isinstance(node, (tuple, list)):
            for child in node:
                visit(child)
        elif isinstance(node, dict):
            for child in node.values():
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise BenchmarkError(
            f"expected one first-moment tree in the optimizer state, "
            f"found {len(found)}: {jax.tree_util.tree_structure(opt_state)}")
    return found[0]


def host_norms(tree: Dict, minus: Optional[Dict] = None) -> Dict[str, float]:
    """Per-leaf L2 norms (of ``tree - minus``) on the host: the
    difference in float32 as both sides hold it, the squares summed in
    float32 over blocks of 2**16 and the blocks in float64 (a float64
    pass over 117M parameters took the host tens of seconds)."""
    out = {}
    for name, a in tree.items():
        a = np.asarray(a, np.float32).ravel()
        if minus is not None:
            a = a - np.asarray(minus[name], np.float32).ravel()
        blocks = [np.dot(a[i:i + 65536], a[i:i + 65536])
                  for i in range(0, a.size, 65536)]
        out[name] = float(np.sqrt(np.sum(blocks, dtype=np.float64)))
    return out


# ------------------------------------------------------------ the trigger
def _train_frame():
    """The running ``Estimator.train`` frame's locals."""
    f = sys._getframe(2)
    while f is not None:
        if f.f_code.co_name == "train" and "opt_state" in f.f_locals \
                and "params" in f.f_locals:
            return f.f_locals
        f = f.f_back
    raise BenchmarkError(
        "Estimator.train no longer keeps `params` and `opt_state` in its "
        "locals: the harness cannot read the state after the first steps")


def make_trigger(plan: Dict):
    """The end trigger: a ``MaxEpoch`` that never reaches its epoch and
    answers by the harness's plan instead."""
    from analytics_zoo_tpu.common.triggers import MaxEpoch
    import jax

    class WindowTrigger(MaxEpoch):
        def __init__(self):
            super().__init__(2 ** 62)
            self.last_iteration = 0
            self.answer = False
            self.losses: List[Tuple[int, int, float]] = []
            self.moment = None
            self.moment_after = None
            self.params_after = None
            self.state_after = None
            self.t_open = None
            self.it_open = None
            self.on_open: Optional[Callable] = None
            self.window_boundaries = 0
            self.tracing = False
            # steps the reference is to follow: the cell's figure on the
            # per-step path, the whole first dispatch on a scan engine
            self.follow = plan["follow_steps"]

        def __call__(self, ts) -> bool:
            it = int(ts.iteration)
            if it == self.last_iteration:
                return self.answer
            prev, self.last_iteration = self.last_iteration, it
            if self.follow is None:
                self.follow = it
            follow = self.follow
            if prev < follow:
                if it > follow:
                    raise BenchmarkError(
                        f"no boundary at step {follow}: {prev} -> {it}")
                loc = _train_frame()
                self.losses.append((prev, it, float(loc["loss"])))
                if self.moment is None:
                    self.moment_after = it
                    self.moment = jax.device_get(
                        first_moment(loc["opt_state"]))
                if it == follow:
                    self.params_after = jax.device_get(loc["params"])
                    self.state_after = jax.device_get(loc["state"])
            now = time.perf_counter()
            if self.tracing:
                with jax.profiler.TraceAnnotation("bench_boundary"):
                    pass
            if self.t_open is None:
                if it >= plan["warmup_steps"] and it >= follow:
                    jax.block_until_ready(_train_frame()["params"])
                    if self.on_open is not None:
                        self.on_open()
                    self.t_open, self.it_open = time.perf_counter(), it
            else:
                self.window_boundaries += 1
                if now - self.t_open >= plan["seconds"]:
                    self.answer = True
            return self.answer

    return WindowTrigger()


class CompileClock:
    """Backend compiles as ``jax.monitoring`` reports them: their
    seconds, and when each ended (a compile inside the window voids the
    run)."""

    def __init__(self):
        self.seconds = 0.0
        self.events: List[Tuple[float, str, float]] = []
        self.cache_hits = 0

    def install(self) -> None:
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, event: str, duration: float, fun_name: str = "?", **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.events.append((time.perf_counter(), fun_name, duration))

    def _hit(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def between(self, t0: float, t1: float) -> List[str]:
        return [name for t, name, _ in self.events if t0 < t <= t1]


def registry_snapshot() -> Dict:
    from analytics_zoo_tpu.observability import get_registry
    return get_registry().snapshot()


def counter_delta(after: Dict, before: Dict, prefix: str) -> Dict[str, float]:
    """Counters under ``prefix`` that moved, by their label part."""
    a, b = after["counters"], before["counters"]
    return {k[len(prefix):]: v - b.get(k, 0.0) for k, v in a.items()
            if k.startswith(prefix) and v != b.get(k, 0.0)}


def histogram_delta(after: Dict, before: Dict, prefix: str) -> Dict[str, float]:
    """Summed ``sum`` and ``count`` of the histograms under ``prefix``."""
    out = {"sum": 0.0, "count": 0.0}
    for k, h in after["histograms"].items():
        if k.startswith(prefix):
            h0 = before["histograms"].get(k, {"sum": 0.0, "count": 0})
            out["sum"] += h["sum"] - h0["sum"]
            out["count"] += h["count"] - h0["count"]
    return out


# ------------------------------------------------------------------ a run
def device_record(devices) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak(devices) -> Tuple[int, int, int]:
    """Peak bytes on the fullest chip, with its two parts.  The TPU
    runtime counts the buffers the process holds (``peak_bytes_in_use``:
    parameters, optimizer state, cached data) apart from the scratch it
    reserves for running programs (``peak_bytes_reserved``: a step's
    activations and temporaries); a training step holds both at once,
    so the peak is their sum."""
    best = (0, 0, 0)
    for d in devices:
        stats = d.memory_stats() or {}
        used = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        best = max(best, (used + reserved, used, reserved))
    return best


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None, require_chip: bool = True,
             cell_override: Optional[Dict] = None,
             cfg_override: Optional[Dict] = None,
             bench: Optional[Dict] = None) -> Dict:
    """Run one cell once and return the result line as a dict.

    ``require_chip=False`` and the overrides are for the tests, which
    drive the same code at toy sizes on the CPU."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry, cell, cfg = load_cell(name, bench)
    cell = {**cell, **(cell_override or {})}
    cfg = {**cfg, **(cfg_override or {})}

    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) != int(entry["chips"])):
        raise BenchmarkError(
            f"{name} needs {entry['chips']} TPU chip(s); JAX reports "
            f"{device_record(devices)}")

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.config import get_config
    from analytics_zoo_tpu.pipeline.api.keras.engine import Layer
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    from . import data as data_lib
    from . import correctness, trace_reduce

    clock = CompileClock()
    clock.install()
    init_zoo_context()
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))

    # --- the model, with the seed's weights -------------------------------
    model_file = load_module("configs", cfg["name"])
    reference = load_module("reference", cfg["name"])
    Layer.reset_name_counters()
    model = model_file.build(cfg)
    variables = model.get_variables()
    order = reference.param_order(cfg)
    slots = program_leaves(variables["params"])
    state_layers = list(variables["state"])
    state_start = jax.device_get(variables["state"])
    model.set_variables({
        "params": to_program(order, reference.init(cfg, seed),
                             variables["params"]),
        "state": variables["state"]})
    del variables

    # --- the rows, through the program's input layer ----------------------
    batch = int(cfg["batch_size"])
    spec = model_file.input_spec(cfg)
    x, y = data_lib.make_rows(spec, cell, seed)
    shuffle_seed = int(get_config().get("data.shuffle_seed"))
    source, step_rows, steps_per_epoch = data_lib.build_source(
        cell, x, y, batch, shuffle_seed)
    scan = cell["engine"] == "epoch_scan"
    plan = {
        "seconds": min(float(seconds), float(cell["trace_seconds"]))
        if trace else float(seconds),
        "follow_steps": None if scan else int(cell["follow_steps"]),
        "warmup_steps": int(cell["warmup_boundaries"])
        * (steps_per_epoch if scan else 1),
    }
    trigger = make_trigger(plan)
    marks: Dict[str, Any] = {}
    trace_dir = os.path.join(ROOT, ".bench_trace")

    def on_open():
        marks["before"] = registry_snapshot()
        marks["compile_s"] = clock.seconds
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            trigger.tracing = True

    trigger.on_open = on_open

    # --- the run ------------------------------------------------------------
    est = Estimator(model, optim_method=model.optim_method)
    try:
        est.train(source, model.loss, end_trigger=trigger, batch_size=batch)
        jax.block_until_ready(est.variables)
        t_close = time.perf_counter()
    finally:
        if trace and "before" in marks:
            jax.profiler.stop_trace()
        if hasattr(source, "close"):
            source.close()
    if trigger.t_open is None:
        raise BenchmarkError("the run ended before the window opened")
    after = registry_snapshot()
    peak, peak_in_use, peak_reserved = memory_peak(devices)
    window_s = t_close - trigger.t_open
    steps = trigger.last_iteration - trigger.it_open
    compiled = clock.between(trigger.t_open, t_close)
    if compiled:
        raise BenchmarkError(
            f"compiled inside the measured window: {compiled}")

    engines = counter_delta(after, marks["before"], "train_steps_total")
    want = 'path="%s"' % cell["engine"]
    if not engines or any(want not in k for k in engines):
        raise BenchmarkError(
            f"{name} is defined on engine {cell['engine']!r}; the "
            f"program's train_steps_total moved as {engines}")
    failed = int(sum(counter_delta(after, marks["before"],
                                   "train_nonfinite_total").values()))

    run = {
        "cell": cell, "cfg": cfg, "device": device_record(devices),
        "window_s": window_s, "steps": steps, "records": steps * batch,
        "boundaries": trigger.window_boundaries,
        "before": marks["before"], "after": after,
        "compile_s": marks["compile_s"], "peak_bytes": peak,
        "peak_in_use": peak_in_use, "peak_reserved": peak_reserved,
        "setup_s": trigger.t_open - t_start,
    }

    # --- what the program produced in its first steps, then free it --------
    program = {
        "loss": trigger.losses,
        "moment_after": trigger.moment_after,
        "moment_norm": host_norms(from_program(order, slots, trigger.moment)),
        "params_after": from_program(order, slots, trigger.params_after),
    }
    if hasattr(reference, "state_init"):
        program["dstate_norm"] = host_norms(
            state_from_program(reference, cfg, state_layers,
                               trigger.state_after),
            minus=state_from_program(reference, cfg, state_layers,
                                     state_start))
    follow = trigger.follow
    data_lib.check_order(step_rows, follow, int(cell["rows"]))
    batches = [(data_lib.take_rows(x, rows), y[rows])
               for rows in map(step_rows, range(follow))]
    del est, model, source, trigger.moment, trigger.params_after, x, y
    trigger.state_after = None
    gc.collect()
    jax.clear_caches()

    reduced = None
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir, window_s)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # --- the plain reference over the same first steps ----------------------
    t_ref = time.perf_counter()
    stages = cell.get("stages", [])
    ref_batches = ((reference.prepare(cfg, stages, bx), by)
                   for bx, by in batches)
    ref = reference.follow(cfg, seed, ref_batches, program["moment_after"])
    t_followed = time.perf_counter()
    start = reference.init(cfg, seed)
    program["dparam_norm"] = host_norms(program.pop("params_after"),
                                        minus=jax.device_get(start))
    del start
    numbers = correctness.compare(program, ref)
    checks = correctness.judge(numbers, cell["limits"])
    run["reference_s"] = time.perf_counter() - t_ref
    run["reference_follow_s"] = t_followed - t_ref

    # --- the line -----------------------------------------------------------
    metrics: Dict[str, Dict] = {}
    device = dict(run["device"], memory_peak_bytes=peak)
    line: Dict[str, Any] = {}
    if not trace:
        for m in bench["end_to_end"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            value = {"setup_s": run["setup_s"],
                     "train_records_per_s": run["records"] / window_s
                     }[m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        run["trace"] = reduced
        run["peaks"] = peaks_for(device["kind"]) if require_chip else None
        run["flops"] = load_module("flops", cfg["name"])
        for m in bench["per_layer"]:
            if "workloads" in m and name not in m["workloads"]:
                continue
            value = load_module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = trace_reduce.breakdown(reduced, cell["engine"])
    observed, checks = checks["observed"], checks["compared"]
    correct = bool(checks) and all(c["ok"] for c in checks.values())
    line = {"correct": bool(correct), "attempted": int(steps),
            "failed": failed, "metrics": metrics, "device": device,
            **line,
            "info": {"workload": name, "seed": seed, "engine": engines,
                     "window_s": window_s, "steps": steps,
                     "reference_s": run["reference_s"],
                     "reference_follow_s": run["reference_follow_s"],
                     "compile_s": run["compile_s"],
                     "persistent_cache_hits": clock.cache_hits,
                     "peak_bytes_in_use": run["peak_in_use"],
                     "peak_bytes_reserved": run["peak_reserved"]},
            "observed": observed, "compared": checks}
    for key, c in checks.items():
        print(f"compared {key}: {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    return line

"""``parallel/mesh.fetch_global``: every addressable leaf's copy to the
host is started before any leaf is waited on, the result is the
per-leaf ``jax.device_get`` result bit for bit, a leaf whose shards
live on other processes still goes through ``process_allgather`` in
tree order, and ``Estimator.train`` hands its state back in one fetch
(``host_fetch_bytes_total{path,site}``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.observability import get_registry, reset_registry
from analytics_zoo_tpu.parallel import mesh as mesh_lib


@pytest.fixture(autouse=True)
def fresh_registry():
    reset_registry()
    yield
    reset_registry()


def fetched(path, site="other"):
    """Bytes counted under ``path`` and ``site``."""
    counters = get_registry().snapshot()["counters"]
    return counters.get(
        'host_fetch_bytes_total{path="%s",site="%s"}' % (path, site), 0.0)


class RecordingLeaf(jax.Array):
    """An addressable array that logs when its copy is started and when
    its value is read."""

    is_fully_addressable = True
    nbytes = property(lambda self: self.value.nbytes)
    dtype = property(lambda self: self.value.dtype)

    def __init__(self, name, value, log):
        self.name, self.value, self.log = name, np.asarray(value), log

    def copy_to_host_async(self):
        self.log.append(("copy", self.name))

    def __array__(self, dtype=None, copy=None):
        self.log.append(("read", self.name))
        return self.value


class RemoteLeaf(jax.Array):
    """An array with shards on another process's devices."""

    is_fully_addressable = False
    nbytes = property(lambda self: self.value.nbytes)
    dtype = property(lambda self: self.value.dtype)

    def __init__(self, name, value):
        self.name, self.value = name, np.asarray(value)


def test_every_copy_starts_before_any_leaf_is_read():
    log = []
    tree = {"b": [RecordingLeaf("b0", np.ones(3), log),
                  RecordingLeaf("b1", np.zeros(2), log)],
            "a": RecordingLeaf("a", np.arange(4), log), "c": None, "d": 7}
    out = mesh_lib.fetch_global(tree)
    names = ["a", "b0", "b1"]            # tree order: dict keys sorted
    assert log == [("copy", n) for n in names] + [("read", n) for n in names]
    np.testing.assert_array_equal(out["a"], np.arange(4))
    assert out["c"] is None and out["d"] == 7
    assert fetched("overlapped") == 4 * 8 + 3 * 8 + 2 * 8


def test_result_is_the_per_leaf_device_get_bit_for_bit():
    mesh = mesh_lib.create_mesh({"fsdp": -1})
    rs = np.random.RandomState(3)
    w = rs.randn(16, 8).astype(np.float32)
    w[0, :3] = [np.nan, -0.0, np.inf]
    tree = {
        "dense": {"w": jax.device_put(w, NamedSharding(mesh, P("fsdp"))),
                  "b": jnp.asarray(rs.randn(8), jnp.bfloat16)},
        "steps": [jnp.arange(5, dtype=jnp.int32), 3, None, 2.5],
        "host": (np.float32(1.5), np.arange(3, dtype=np.int64)),
        "replicated": jax.device_put(w[:2], mesh_lib.replicated(mesh)),
    }
    want = jax.tree_util.tree_map(jax.device_get, tree)
    got = mesh_lib.fetch_global(tree)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, e in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert type(g) is type(e)
        if isinstance(e, np.ndarray):
            assert (g.dtype, g.shape) == (e.dtype, e.shape)
            assert g.tobytes() == e.tobytes()
        else:
            assert g == e
    assert isinstance(got["dense"]["w"], np.ndarray)
    # the four device arrays, none of the host leaves
    assert fetched("overlapped") == (16 * 8 + 5 + 2 * 8) * 4 + 8 * 2
    assert fetched("allgather") == 0.0


def test_a_leaf_on_other_processes_goes_through_process_allgather(
        monkeypatch):
    from jax.experimental import multihost_utils
    gathered = []

    def allgather(leaf, tiled=False):
        assert tiled
        gathered.append(leaf.name)
        return leaf.value

    monkeypatch.setattr(multihost_utils, "process_allgather", allgather)
    log = []
    tree = {"a": RemoteLeaf("a", np.arange(6, dtype=np.float32)),
            "b": RecordingLeaf("b", np.ones(2, np.float32), log),
            "c": RemoteLeaf("c", np.zeros((2, 2), np.int32))}
    out = mesh_lib.fetch_global(tree, site="train_return")
    assert gathered == ["a", "c"]
    assert log == [("copy", "b"), ("read", "b")]
    np.testing.assert_array_equal(out["a"], np.arange(6))
    np.testing.assert_array_equal(out["c"], np.zeros((2, 2)))
    assert fetched("allgather", "train_return") == 6 * 4 + 4 * 4
    assert fetched("overlapped", "train_return") == 2 * 4
    assert fetched("allgather") == fetched("overlapped") == 0.0


def _trained(monkeypatch, tmp_path=None):
    """A toy ``Estimator.train``, and the trees ``fetch_global`` was
    called with, by ``site``."""
    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.common.triggers import MaxEpoch
    from analytics_zoo_tpu.feature.feature_set import FeatureSet
    from analytics_zoo_tpu.pipeline.api.keras import Sequential
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        BatchNormalization, Dense)
    from analytics_zoo_tpu.pipeline.estimator import Estimator
    calls = []
    fetch = mesh_lib.fetch_global

    def recording(tree, site="other"):
        calls.append((site, tree))
        return fetch(tree, site=site)

    monkeypatch.setattr(mesh_lib, "fetch_global", recording)
    init_zoo_context()
    m = Sequential()
    m.add(Dense(8, input_shape=(8,)))
    m.add(BatchNormalization())
    m.add(Dense(4))
    m.compile("adam", "mse")
    rs = np.random.RandomState(0)
    x = rs.randn(128, 8).astype("float32")
    y = rs.randn(128, 4).astype("float32")
    est = Estimator(m, optim_method=m.optim_method,
                    model_dir=None if tmp_path is None else str(tmp_path))
    est.train(FeatureSet.from_ndarrays(x, y), "mse",
              end_trigger=MaxEpoch(2), batch_size=64)
    return est, calls


def nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def test_train_returns_its_state_in_one_overlapped_fetch(monkeypatch):
    est, calls = _trained(monkeypatch)
    leaves = jax.tree_util.tree_leaves(est.variables)
    assert est.variables["state"]        # BatchNorm's moving statistics
    assert leaves and all(type(a) is np.ndarray for a in leaves)
    # ONE call, with params and state together
    (site, tree), = calls
    assert site == "train_return" and sorted(tree) == ["params", "state"]
    assert all(isinstance(a, jax.Array)
               for a in jax.tree_util.tree_leaves(tree))
    assert fetched("overlapped", "train_return") == nbytes(est.variables)
    assert fetched("allgather", "train_return") == 0.0
    counters = get_registry().snapshot()["counters"]
    assert counters['spans_total{name="train_return"}'] == 1


def test_a_snapshot_fetches_params_state_and_opt_state_together(
        monkeypatch, tmp_path):
    from analytics_zoo_tpu.utils.serialization import Checkpoint
    est, calls = _trained(monkeypatch, tmp_path)
    ckpt = Checkpoint(str(tmp_path))
    payload = ckpt.restore_latest(None)
    assert payload["epoch"] == 2
    snapshot = nbytes([payload[k] for k in ("params", "state", "opt_state")])
    # two epoch-end snapshots, one call each, then the return
    assert [site for site, _ in calls] == [
        "checkpoint_save", "checkpoint_save", "train_return"]
    assert all(len(tree) == 3 for _, tree in calls[:2])
    assert fetched("overlapped", "checkpoint_save") == 2 * snapshot
    assert fetched("overlapped", "train_return") == nbytes(est.variables)

"""chip_smoke.py kept from rotting without a chip.

The script itself refuses to run on the CPU.  Its phases are importable
functions taking sizes, so they run here at toy sizes on the suite's
virtual CPU devices, with the kernels steered to the Pallas branch in
interpret mode BY THIS TEST (the program has no such option): that
keeps the control flow, the counters the checks read and the record
format alive.  What only the chip can show — the real widths, the TPU
compiler, the times — is chip_smoke.py's own job.
"""

import json
import os
import subprocess
import sys

import jax
import pytest
from jax.experimental import pallas as pl

import analytics_zoo_tpu
from analytics_zoo_tpu.common import zoo_context
from analytics_zoo_tpu.common.config import get_config
from analytics_zoo_tpu.ops import fused
from analytics_zoo_tpu.parallel import mesh as mesh_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402  (repo-root script)

TOY_RESNET = dict(depth=18, classes=1000, image=32, batch=8,
                  steps_per_epoch=2, epochs=4)


@pytest.fixture
def one_chip(monkeypatch):
    """The smoke's one-chip process on the suite's 8 virtual devices: a
    one-device context mesh, the suite on its Pallas branch, every
    kernel interpreted."""
    mesh = mesh_lib.create_mesh({"data": 1}, devices=jax.devices()[:1])
    monkeypatch.setattr(zoo_context, "_context",
                        zoo_context.ZooContext(get_config(), mesh))
    monkeypatch.setattr(fused, "pallas_supported", lambda: True)
    monkeypatch.setattr(
        fused, "_use_pallas", lambda: fused._mode() in ("auto", "pallas"))
    compiled_call = pl.pallas_call

    def interpreted(*args, **kwargs):
        kwargs["interpret"] = True
        return compiled_call(*args, **kwargs)
    monkeypatch.setattr(pl, "pallas_call", interpreted)


def _phase(capsys, name, fn, *args, **kwargs):
    """Run a phase through run_phase; return (ok, carry, printed line)."""
    chip_smoke.CLOCK.install()
    ok, carry = chip_smoke.run_phase(name, fn, *args, **kwargs)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == name and line["ok"] == ok
    assert line["wall_s"] > line["compile_s"] > 0
    assert line["run_s"] == line["wall_s"] - line["compile_s"]
    assert line["backend_compiles"] > 0
    return ok, carry, line


def test_train_then_serve(one_chip, capsys, tmp_path):
    ok, model, line = _phase(capsys, "train", chip_smoke.train,
                             workdir=str(tmp_path), **TOY_RESNET)
    assert ok, line
    assert line["dispatch_engine"] == {'{path="epoch_scan"}': 8.0}
    assert line["compiles"] == {'{fn="train_epoch_scan"}': 1.0}
    # the one-pass update on every leaf, a trace; no optimizer kernel
    builds = line["kernel_builds"]
    assert builds['{kernel="fused_sgd",path="lax"}'] > 0
    assert '{kernel="fused_sgd",path="pallas"}' not in builds
    assert line["checks"]["optimizer_one_pass_every_leaf"]
    assert len(line["loss_per_epoch"]) == TOY_RESNET["epochs"]

    ok, _, line = _phase(capsys, "serve", chip_smoke.serve, model,
                         image=TOY_RESNET["image"], n_records=4)
    assert ok, line
    assert line["healthz"] == [200, {"ready": True}]
    assert len(line["top5_first_record"]) == 5


def test_transformer(one_chip, capsys):
    ok, _, line = _phase(capsys, "transformer", chip_smoke.transformer,
                         width=128, heads=2, seq=256, batch=8, blocks=1,
                         vocab=100, fit_steps=2)
    assert ok, line
    assert set(line["flash_vs_dense"]) == {"bfloat16", "float32"}
    # the attention backward's form is printed beside the other builds
    # (the train program is traced twice: the call, the cost analysis)
    one_pass = '{kernel="flash_attention_backward",path="one_pass"}'
    assert line["kernel_builds"][one_pass] == 1.0
    assert line["kernel_builds_fit"][one_pass] == 2.0
    assert line["checks"]["backward_in_one_pass"]
    # the reference run really was the lax forms, and only it (two
    # heads of 64 share a lane tile: the kernels' packed form)
    assert line["kernel_builds_reference"] == {
        '{kernel="flash_attention",path="pallas"}': 1.0,
        '{kernel="flash_attention_packed",path="pallas"}': 1.0,
        '{kernel="flash_attention_backward",path="one_pass"}': 1.0,
        '{kernel="bias_gelu",path="lax"}': 1.0,
        '{kernel="layernorm_act",path="lax"}': 1.0}
    assert get_config().get("ops.fused") == "auto"


def test_hybrid(one_chip, capsys):
    ok, _, line = _phase(capsys, "hybrid", chip_smoke.hybrid, seq=256,
                         hidden=64, channels=1024, states=4, heads=4,
                         kv_heads=2, window=100)
    assert ok, line
    # the pair's backward under the window and under the causal mask
    assert line["kernel_builds"] == {
        '{kernel="selective_scan",path="pallas"}': 1.0,
        '{kernel="flash_attention_backward",path="one_pass"}': 2.0}
    assert line["checks"]["backward_in_one_pass"]
    assert set(line["differential_flash_vs_dense"]) == {"window", "causal"}
    assert set(line["forward_backward_s"]) == {
        "selective_scan", "selective_scan_lax", "flash_window",
        "dense_window", "flash_causal", "dense_causal",
        "recomputed_layer_selective_scan_fwd",
        "recomputed_layer_flash_attention_fwd"}
    # the recomputed layers keep what their kernels wrote: the maps'
    # (1, 256, 4 * 128) bfloat16 output, the scan's float32 y (1, 256, 1024)
    assert line["forward_kernels_in_recomputed_gradient"] == {
        "selective_scan_fwd": 1, "flash_attention_fwd": 1}
    kept = line["train_recompute_kept_bytes"]
    assert kept['{name="flash_attention_out"}'] >= 256 * 512 * 2
    assert kept['{name="selective_scan_y"}'] >= 256 * 1024 * 4


def test_latent(one_chip, capsys):
    ok, _, line = _phase(capsys, "latent", chip_smoke.latent, seq=256,
                         heads=2, hidden=64, experts=16, top_k=3)
    assert ok, line
    assert set(line["latent_flash_vs_dense"]) == {
        "out", "dq_nope", "dq_pe", "dk_nope_dv", "dk_pe"}
    assert set(line["forward_backward_s"]) == {"flash_latent",
                                               "dense_latent"}
    assert line["unread_q_columns_cotangent_max"] == 0.0
    # the backward's form is printed beside the other builds
    assert line["kernel_builds"][
        '{kernel="flash_attention_latent_backward",path="one_pass"}'] == 1.0
    assert line["checks"]["backward_in_one_pass"]
    assert line["router"]["tokens_picking_other_experts"] == 0
    assert line["router"]["tokens_the_bias_moved"] > 0
    assert all(line["checks"].values())


def test_data_parallel_on_four_virtual_devices(capsys):
    ok, _, line = _phase(capsys, "data_parallel", chip_smoke.data_parallel,
                         jax.devices()[:4], **TOY_RESNET)
    checks = line["checks"]
    # the first epoch is the same parameters on both meshes and must
    # agree; after it, toy-batch SGD amplifies bf16 reduction-order
    # noise beyond the chip run's tolerance
    assert {k for k, v in checks.items() if not v} <= {
        "loss_agrees_with_one_device"}
    assert checks["first_epoch_loss_agrees"]
    assert line["mesh"] == {"data": 4}
    assert line["param_device_set_sizes"] == [4]
    assert line["batch_rows_per_device"] == [2]
    assert line["all_reduces"] > 0
    # not a TPU: the row-major mesh, and no claim about mesh_utils
    assert "mesh_from_mesh_utils" not in checks


def test_failed_phase_is_not_a_warning(capsys):
    def broken():
        raise RuntimeError("compiler said no")
    ok, carry = chip_smoke.run_phase("broken", broken)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert ok is False and carry is None and line["ok"] is False
    assert "compiler said no" in line["error"]
    # a phase whose record carries no checks proved nothing
    assert chip_smoke.run_phase("empty", lambda: {"checks": {}})[0] is False


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_command_refuses_the_cpu(argv):
    """As the driver runs it in a sandbox with no accelerator: non-zero
    exit, last line ``ok: false`` with the device JAX did find, and no
    phase ran."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")] + argv,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    assert len(lines) == 1, r.stdout
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"


class TestCompileCachePlacement:
    """ONE persistent compilation cache, placed from outside."""

    @pytest.fixture(autouse=True)
    def _restore(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_set_code_sets_nothing(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        analytics_zoo_tpu._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir is None

    def test_env_unset_fixed_path_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        analytics_zoo_tpu._place_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO_ROOT, ".jax_cache")

    def test_single_guarded_config_update(self):
        hits = subprocess.run(
            ["grep", "-rn", "compilation_cache_dir", "--include=*.py",
             "analytics_zoo_tpu", "bench.py", "chip_smoke.py", "scripts",
             "dev"], cwd=REPO_ROOT, capture_output=True, text=True).stdout
        updates = [h for h in hits.splitlines() if "config.update" in h]
        assert len(updates) == 1 and "analytics_zoo_tpu/__init__.py" \
            in updates[0], hits


def test_pallas_probe_refusal_raises_on_tpu(monkeypatch):
    """On a TPU backend a probe kernel the compiler refuses is an error
    with the compiler's message — never ``False``, which would turn the
    whole suite into its lax forms without a word.  Here the backend's
    NAME is faked; the refusal is the CPU backend's own."""
    monkeypatch.setattr(fused, "_PALLAS_OK", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret mode"):
        fused.pallas_supported()
    assert fused._PALLAS_OK is None          # and nothing was cached
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert fused.pallas_supported() is False

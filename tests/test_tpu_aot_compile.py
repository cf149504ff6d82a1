"""The kernel suite, compiled for a described v5e at the widths the
models run — no chip attached, nothing executed.

The TPU compiler is installed beside the CPU backend and compiles for a
device that is described, not attached (the on-chip-measurement guide,
section 2).  It refuses what interpret mode cannot see: a block that
overflows scoped VMEM, a primitive Mosaic does not lower, a misaligned
slice.  These cases guard every later PR at no chip time; they are
skipped only where the topology cannot be described.  JAX's persistent
compilation cache is off for the whole suite (tests/conftest.py): such
a compile is written to it but cannot be read back without a chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from analytics_zoo_tpu.ops import activations as acts
from analytics_zoo_tpu.ops import fused
from analytics_zoo_tpu.ops.grouped_matmul import (
    buffer_rows, group_layout, grouped_matmul)
from analytics_zoo_tpu.ops.pallas_attention import (
    block_diffusion, flash_attention, flash_attention_token_major,
    sliding_window)
from analytics_zoo_tpu.ops.pallas_latent_attention import (
    latent_flash_attention)
from analytics_zoo_tpu.ops.selective_scan import selective_scan
from analytics_zoo_tpu.ops.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """What one TPU device answers: the Pallas branch.  The CPU backend
    this test runs on would answer lax."""
    monkeypatch.setattr(fused, "_use_pallas", lambda: True)


def _bias_gelu(x, b):
    return fused.bias_gelu(x, b)


def _layernorm_gelu(x, g, b):
    return fused.layernorm_act(x, g, b, activation=acts.gelu)


def _adam(p, g, m, v):
    return fused.adam_leaf_update(
        p, g, m, v, b1=0.9, b2=0.999, eps=1e-8, step_size=-1e-3,
        bias_corr1=0.1, bias_corr2=0.001, clip_scale=0.5)


def _sgd(p, g, t):
    return fused.sgd_leaf_update(
        p, g, t, momentum=0.9, nesterov=False, step_size=-0.1,
        weight_decay=1e-4)


def _flash(q, k, v):
    return flash_attention(q, k, v)


def _flash_block_diffusion(q, k, v):
    return flash_attention(q, k, v, mask=block_diffusion(4096, 4),
                           block_q=512, block_k=512)


def _flash_gpt_cell(qkv):
    return flash_attention_token_major(qkv, n_head=12, causal=True)


def _flash_sdar_cell(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=32, mask=block_diffusion(4096, 4), block_q=512,
        block_k=512)


def _flash_pair_window(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=40, differential=True, mask=sliding_window(512))


def _flash_pair_causal(qkv):
    return flash_attention_token_major(
        qkv, n_head=40, n_kv_head=20, differential=True, causal=True,
        block_q=512, block_k=512)


def _flash_pair_cross(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=40, differential=True, causal=True, block_q=512,
        block_k=512)


def _flash_sdar_long(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=32, causal=True, block_q=512, block_k=512)


def _flash_windowed_cell(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=28, causal=True, block_q=512, block_k=512)


def _flash_windowed_cell_window(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=28, mask=sliding_window(4096), block_q=512,
        block_k=512)


def _scan(x, dt, a, b, c):
    return selective_scan(x, dt, a, b, c)[0]


def _ssd(x, dt, a, b, c):
    """x, B and C in bfloat16 beside float32 dt and A, as the Mamba-2
    layer hands them."""
    return ssd_scan(x.astype(BF16), dt, a, b.astype(BF16),
                    c.astype(BF16))[0]


def _flash_granite(q, k, v):
    return flash_attention_token_major(
        q, k, v, n_head=32, causal=True, scale=1 / 64, block_q=512,
        block_k=512)


def _flash_latent(q, q_pe, kv, k_pe):
    return latent_flash_attention(q, q_pe, kv, k_pe, n_head=32, causal=True,
                                  block_q=512, block_k=512)


# the latent cell's operands at 8,192 positions: the query projection's
# result (32 heads of 128 | 64, the nope heads first), the rotated rotary
# parts, the up-projection's result (k_nope | v) and the ONE rotary key
LATENT = [(1, 8192, 32 * 192), (1, 8192, 32 * 64), (1, 8192, 32 * 256),
          (1, 8192, 64)]
# the Mamba-2 cell's scan operands: x (64 heads of 64), dt, A, B, C
SSD = [(1, 8192, 4096), (1, 8192, 64), (64,), (1, 8192, 128),
       (1, 8192, 128)]
# twice as long: a head pair's whole dq no longer fits the one-pass
# backward's VMEM budget, so the dq and dkv kernels are what compiles
LATENT_LONG = [(1, 16384) + s[2:] for s in LATENT]


def _grad(fn, n_args):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=tuple(range(n_args)))


F32, BF16 = jnp.float32, jnp.bfloat16
# (id, function, argument shapes, dtype, Pallas kernels in the program)
CASES = [
    # the two epilogues at rows 16384 (b32 x T512): the FFN width of
    # BERT-base and its residual width
    ("bias_gelu-16384x3072", _bias_gelu, [(16384, 3072), (3072,)], F32, 1),
    ("bias_gelu-16384x768", _bias_gelu, [(16384, 768), (768,)], F32, 1),
    ("layernorm_act-16384x3072", _layernorm_gelu,
     [(16384, 3072), (3072,), (3072,)], F32, 1),
    ("layernorm_act-16384x768", _layernorm_gelu,
     [(16384, 768), (768,), (768,)], F32, 1),
    # their backward is the lax derivative: it must compile beside the
    # kernel, and the forward kernel is dead code in a grad-only program
    ("bias_gelu-grad-16384x3072", _grad(_bias_gelu, 2),
     [(16384, 3072), (3072,)], F32, 0),
    ("layernorm_act-grad-16384x3072", _grad(_layernorm_gelu, 3),
     [(16384, 3072), (3072,), (3072,)], F32, 0),
    # flash attention forward and its one-pass backward kernel
    ("flash-b2h12t512d64", _flash, [(2, 12, 512, 64)] * 3, BF16, 1),
    ("flash-grad-b2h12t512d64", _grad(_flash, 3),
     [(2, 12, 512, 64)] * 3, BF16, 2),
    ("flash-b4h8t4096d128", _flash, [(4, 8, 4096, 128)] * 3, BF16, 1),
    ("flash-grad-b4h8t4096d128", _grad(_flash, 3),
     [(4, 8, 4096, 128)] * 3, BF16, 2),
    # 8,192 positions (over the old whole-K/V-in-VMEM cap), 32 query
    # heads on 4 K/V heads, the block-diffusion mask's tile tables
    ("flash-blockdiff-grad-h32kv4t8192d128", _grad(_flash_block_diffusion, 3),
     [(1, 32, 8192, 128), (1, 4, 8192, 128), (1, 4, 8192, 128)], BF16, 2),
    # the cells' own operands, where the projections wrote them: GPT's
    # fused float32 qkv at 12 heads of 64, two to a lane tile; the
    # sparse cell's bfloat16 q and k/v at 32 heads on 4 of 128
    ("flash-gpt-cell-b32t512x2304", _flash_gpt_cell, [(32, 512, 2304)],
     F32, 1),
    # the backward in ONE pass wherever a query tile's whole dq and its
    # K/V tile's whole dk and dv stay in VMEM: the three cells' shapes
    # do (24 MiB resident at 8,192 positions; this is where a
    # ``vmem_limit_bytes`` too small for it shows)
    ("flash-gpt-cell-grad-b32t512x2304", _grad(_flash_gpt_cell, 1),
     [(32, 512, 2304)], F32, 2),
    ("flash-sdar-cell-t8192x4096", _flash_sdar_cell,
     [(1, 8192, 4096), (1, 8192, 512), (1, 8192, 512)], BF16, 1),
    ("flash-sdar-cell-grad-t8192x4096", _grad(_flash_sdar_cell, 3),
     [(1, 8192, 4096), (1, 8192, 512), (1, 8192, 512)], BF16, 2),
    # twice as long: past the one-pass backward's VMEM budget, so the
    # form that keeps a query tile's whole dq alone resident and writes
    # dk and dv a query head is what compiles (16 MiB resident)
    ("flash-sdar-long-grad-t16384x4096", _grad(_flash_sdar_long, 3),
     [(1, 16384, 4096), (1, 16384, 512), (1, 16384, 512)], BF16, 2),
    # the windowed cell's: 28 heads on 4 K/V heads of 128 (groups of 7)
    # at 16,384 positions, the global causal layer and the 4,096-key
    # window, in that form
    ("flash-windowed-cell-grad-t16384x3584", _grad(_flash_windowed_cell, 3),
     [(1, 16384, 3584), (1, 16384, 512), (1, 16384, 512)], BF16, 2),
    ("flash-windowed-cell-window-grad-t16384x3584",
     _grad(_flash_windowed_cell_window, 3),
     [(1, 16384, 3584), (1, 16384, 512), (1, 16384, 512)], BF16, 2),
    # the hybrid cell's: 40 heads of 64 in differential pairs on 20 K/V
    # heads (two maps a pair over its 128-wide V), inside a 512-key
    # window with K/V as operands of their own, causal with q, k and v
    # read out of the projection's result, and causal on another
    # layer's K/V (the cross layers); the scan's two kernels over 5,120
    # channels of 16 states (float32)
    ("flash-pair-window-grad-t8192x2560", _grad(_flash_pair_window, 3),
     [(1, 8192, 2560), (1, 8192, 1280), (1, 8192, 1280)], BF16, 2),
    ("flash-pair-causal-grad-t8192x5120", _grad(_flash_pair_causal, 1),
     [(1, 8192, 5120)], BF16, 2),
    ("flash-pair-cross-grad-t8192x2560", _grad(_flash_pair_cross, 3),
     [(1, 8192, 2560), (1, 8192, 1280), (1, 8192, 1280)], BF16, 2),
    ("flash-latent-t8192h32-128-64-128", _flash_latent, LATENT, BF16, 1),
    ("flash-latent-grad-t8192h32-128-64-128", _grad(_flash_latent, 4),
     LATENT, BF16, 2),
    ("flash-latent-grad-t16384h32-128-64-128", _grad(_flash_latent, 4),
     LATENT_LONG, BF16, 3),
    ("selective-scan-t8192c5120n16", _scan,
     [(1, 8192, 5120), (1, 8192, 5120), (5120, 16), (1, 8192, 16),
      (1, 8192, 16)], F32, 1),
    ("selective-scan-grad-t8192c5120n16", _grad(_scan, 5),
     [(1, 8192, 5120), (1, 8192, 5120), (5120, 16), (1, 8192, 16),
      (1, 8192, 16)], F32, 2),
    # the Mamba-2 cell's: the chunked scan over 64 heads of 64 on one
    # group of 128 states (x, B, C in bfloat16, dt and the states in
    # float32), and 32 query heads of 64 on
    # 8 K/V heads, two to a lane tile once K/V are repeated to the group
    ("ssd-scan-t8192h64p64n128", _ssd, SSD, F32, 1),
    ("ssd-scan-grad-t8192h64p64n128", _grad(_ssd, 5), SSD, F32, 2),
    ("flash-granite-grad-t8192x2048", _grad(_flash_granite, 3),
     [(1, 8192, 2048), (1, 8192, 512), (1, 8192, 512)], BF16, 2),
]
# every activation the LayerNorm epilogue claims to run in-kernel
CASES += [
    (f"layernorm_act-{a.__name__}",
     lambda x, g, b, a=a: fused.layernorm_act(x, g, b, activation=a),
     [(64, 768), (768,), (768,)], F32, 1)
    for a in sorted(fused._PALLAS_ACTIVATIONS, key=lambda f: f.__name__)]


@pytest.mark.parametrize("fn,shapes,dtype,kernels",
                         [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_kernel_compiles_for_v5e(v5e, on_tpu, fn, shapes, dtype, kernels):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=v5e) for s in shapes]
    # raises what the chip's compiler would raise
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == kernels


@pytest.mark.parametrize("k,n", [(2048, 768), (768, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_compiles_for_v5e(v5e, on_tpu, k, n):
    """The expert products at the widths and the worst-case buffer of
    the 128-expert cell: 65,536 assignments in 16 block-aligned groups,
    bfloat16 rows against float32 expert matrices; forward, and the two
    backward kernels under their names."""
    rows = buffer_rows(8192 * 8, 16, 256)

    def product(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, group_layout(sizes, rows, 256))

    args = [jax.ShapeDtypeStruct((rows, k), BF16, sharding=v5e),
            jax.ShapeDtypeStruct((16, k, n), F32, sharding=v5e),
            jax.ShapeDtypeStruct((16,), jnp.int32, sharding=v5e)]
    text = jax.jit(product).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "grouped_matmul_fwd" in text
    grad = jax.grad(lambda *a: jnp.sum(product(*a).astype(F32)), (0, 1))
    text = jax.jit(grad).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "grouped_matmul_dlhs" in text and "grouped_matmul_drhs" in text


NAMED = {
    "bias_gelu-16384x768": ["bias_gelu"],
    "layernorm_act-16384x768": ["layernorm_act"],
    "flash-grad-b2h12t512d64": ["flash_attention_fwd", "flash_attention_bwd"],
    "flash-gpt-cell-grad-b32t512x2304": ["flash_attention_fwd",
                                         "flash_attention_bwd"],
    "flash-sdar-cell-grad-t8192x4096": ["flash_attention_fwd",
                                        "flash_attention_bwd"],
    "flash-pair-window-grad-t8192x2560": ["flash_attention_fwd",
                                          "flash_attention_bwd"],
    "flash-pair-causal-grad-t8192x5120": ["flash_attention_fwd",
                                          "flash_attention_bwd"],
    "flash-pair-cross-grad-t8192x2560": ["flash_attention_fwd",
                                         "flash_attention_bwd"],
    "flash-sdar-long-grad-t16384x4096": [
        "flash_attention_fwd", "flash_attention_bwd_heads"],
    "flash-windowed-cell-grad-t16384x3584": [
        "flash_attention_fwd", "flash_attention_bwd_heads"],
    "flash-windowed-cell-window-grad-t16384x3584": [
        "flash_attention_fwd", "flash_attention_bwd_heads"],
    "selective-scan-grad-t8192c5120n16": ["selective_scan_fwd",
                                          "selective_scan_bwd"],
    "ssd-scan-grad-t8192h64p64n128": ["ssd_scan_fwd", "ssd_scan_bwd"],
    "flash-granite-grad-t8192x2048": ["flash_attention_fwd",
                                      "flash_attention_bwd"],
    # the one-pass backward holds a head pair's whole dq in VMEM: this
    # is where a ``vmem_limit_bytes`` too small for it shows
    "flash-latent-grad-t8192h32-128-64-128": [
        "flash_attention_latent_fwd", "flash_attention_latent_bwd"],
    "flash-latent-grad-t16384h32-128-64-128": [
        "flash_attention_latent_fwd", "flash_attention_latent_dq",
        "flash_attention_latent_dkv"],
}


@pytest.mark.parametrize("case", sorted(NAMED))
def test_kernel_name_is_the_compiled_instruction_name(v5e, on_tpu, case):
    """What a chip profile names an ``XLA Ops`` event by: the kernel's
    ``name=`` must be in its custom call's instruction name and
    ``op_name``, whatever transform (jvp, transpose) wraps it."""
    _, fn, shapes, dtype, _ = next(c for c in CASES if c[0] == case)
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=v5e) for s in shapes]
    calls = [line for line in
             jax.jit(fn).lower(*args).compile().as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == len(NAMED[case])
    for name in NAMED[case]:
        hits = [c for c in calls if name in c.split(" = ")[0]]
        assert len(hits) == 1, (name, [c.split(" = ")[0] for c in calls])
        assert f"{name}" in hits[0].split("op_name=")[1]


# Leaf shapes the benchmark's cells hold: one layer's experts, the head
# and the embedding of the sparse cell, GPT's table and FFN, ResNet's
# largest and smallest kernels, its stem and classifier, a bias.
LEAVES = [(16, 2048, 768), (2048, 18992), (18992, 2048), (40990, 768),
          (768, 3072), (3, 3, 512, 512), (1, 1, 64, 256), (7, 7, 3, 64),
          (2048, 1000), (768,)]
# (update, operands p g moments..., which of them are the train state)
UPDATES = {"adam": (_adam, 4, (0, 2, 3)), "sgd": (_sgd, 3, (0, 2))}


@pytest.mark.parametrize("shape", LEAVES,
                         ids=["x".join(map(str, s)) for s in LEAVES])
@pytest.mark.parametrize("update", sorted(UPDATES))
def test_optimizer_update_is_one_fusion_in_the_leafs_own_layout(
        v5e, on_tpu, update, shape):
    """The per-leaf update on one TPU device (where the suite takes a
    Pallas branch wherever it has one), state donated as the train step
    donates it: the compiled entry computation is ONE loop fusion over
    the operands as they lie (whatever tiling the compiler gives that
    shape), parameter and moments written in place.  No kernel, and
    none of what a kernel's ``(rows, 128)`` operands cost: a
    ``reshape`` or ``copy`` of a leaf, a staging ``copy-start`` /
    ``slice-start``."""
    fn, n_args, state = UPDATES[update]
    args = [jax.ShapeDtypeStruct(shape, F32, sharding=v5e)] * n_args
    text = jax.jit(fn, donate_argnums=state).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    for op in ("reshape", "copy", "copy-start", "slice-start"):
        assert f" {op}(" not in entry, op
    assert entry.count(" fusion(") == 1
    alias = text[text.index("input_output_alias={"):]
    alias = alias[:alias.index("entry_computation_layout")]
    pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", alias)
    assert [(int(o), int(i)) for o, i in pairs] == list(enumerate(state))


def _relayouts(text, t, least):
    """The ``copy`` and ``transpose`` instructions of a compiled module
    (fused computations included) over arrays of ``t`` positions and at
    least ``least`` elements: activations, not weights."""
    found = []
    for line in text.splitlines():
        m = re.search(r" = (\w+\[([\d,]+)\]\S*) (copy|transpose)\(", line)
        if not m:
            continue
        dims = [int(n) for n in m.group(2).split(",")]
        size = 1
        for n in dims:
            size *= n
        if t in dims and size >= least:
            found.append(f"{m.group(3)} {m.group(1)}")
    return found


def _layer_grad_text(layer, input_shapes, args):
    params = jax.eval_shape(
        lambda: layer.build(jax.random.PRNGKey(0), input_shapes))

    def loss(params, *inputs):
        x = list(inputs) if len(inputs) > 1 else inputs[0]
        return jnp.sum(jnp.square(layer.call(params, x).astype(F32)))

    sharding = args[0].sharding
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        params)
    return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, *args).compile().as_text()


def test_attention_layers_hand_the_kernels_what_the_projections_wrote(
        v5e, one_chip_routing):
    """The two attention layers at their cells' shapes, forward and
    backward, compiled for the v5e: between the projections and the
    two flash kernels (the forward and the one-pass backward) no head
    array is copied or transposed.

    GPT's block (12 heads of 64, float32): NOTHING is — q, k and v are
    read out of the fused projection's result, ctx goes to the output
    projection as written, and the three gradients are read side by
    side by the projection's gradient products (no concatenate built).
    The sparse cell's block (32 heads on 4 of 128, bfloat16, per-head
    RMS norm and rotary): v, ctx and its cotangent are not; q and k,
    dq and dk still are, once each, because XLA works the rotary
    embedding's half-head slices with the positions in the lanes
    (ROADMAP S9) — four, where the head-major entry had five."""
    from analytics_zoo_tpu.pipeline.api.keras.layers.attention import (
        GroupedQueryAttention, MultiHeadSelfAttention)
    gpt = MultiHeadSelfAttention(768, 12, causal=True)
    text = _layer_grad_text(
        gpt, (None, 512, 768),
        [jax.ShapeDtypeStruct((32, 512, 768), F32, sharding=v5e)])
    assert text.count("tpu_custom_call") == 2
    assert _relayouts(text, 512, 32 * 512 * 768) == []
    assert "dynamic-update-slice" not in text and " concatenate(" not in text

    sdar = GroupedQueryAttention(32, 4, 128, rope_theta=1e6,
                                 mask=block_diffusion(4096, 4))
    text = _layer_grad_text(
        sdar, [(None, 8192, 2048), (None, 8192)],
        [jax.ShapeDtypeStruct((1, 8192, 2048), F32, sharding=v5e),
         jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=v5e)])
    assert text.count("tpu_custom_call") == 2
    moved = _relayouts(text, 8192, 8192 * 4 * 128)
    assert len(moved) <= 4, moved
    assert not [m for m in moved if m.startswith("transpose")], moved


# (mixer, what the layer reads besides the stream, kernels in its
# gradient: each forward kernel ONCE though the layer is recomputed,
# because the layer's policy keeps the kernels' results by name)
def _hybrid_blocks():
    from analytics_zoo_tpu.pipeline.api.keras.layers import ssm
    attention = dict(n_head=40, n_kv_head=20, head_dim=64)
    kv, memory = (1, 8192, 1280), (1, 8192, 5120)
    return {
        "mamba": (ssm.Mamba(5120, 16, 4, 160, emit_memory=True), [], 2),
        "window_attention": (ssm.DifferentialAttention(
            layer_index=1, mask=sliding_window(512), **attention), [], 2),
        "full_attention": (ssm.DifferentialAttention(
            layer_index=17, emit_kv=True, **attention), [], 2),
        "memory_unit": (ssm.GatedMemoryUnit(), [memory], 0),
        "cross_attention": (ssm.DifferentialAttention(
            layer_index=19, cross=True, **attention), [kv, kv], 2),
    }


@pytest.mark.parametrize("kind", ["mamba", "window_attention",
                                  "full_attention", "memory_unit",
                                  "cross_attention"])
def test_hybrid_decoder_layers_compile_for_v5e(v5e, one_chip_routing, on_tpu,
                                               kind):
    """One recomputed decoder layer of each of the hybrid cell's five
    kinds at its published widths and 8,192 positions, forward and
    backward: Mosaic takes the scan's kernels and the differential pair
    under the window and the causal mask, K/V its own or another
    layer's, and the layers reach them (no lax form in the program)."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import ssm
    mixer, reads, kernels = _hybrid_blocks()[kind]
    layer = ssm.HybridDecoderLayer(mixer, ssm.GatedFeedForward(10240),
                                   recompute=True)
    stream = (1, 8192, 2560)
    shapes = [stream, *reads] if reads else stream
    params = jax.eval_shape(
        lambda: layer.build(jax.random.PRNGKey(0), shapes))

    def loss(params, h, *extra):
        out = layer.call(params, [h, *extra] if extra else h)
        outs = out if isinstance(out, list) else [out]
        return sum(jnp.sum(jnp.square(o.astype(F32))) for o in outs)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    # the gradients of what the layer reads too: the writer's share
    text = jax.jit(jax.grad(loss, argnums=tuple(range(2 + len(reads))))
                   ).lower(
        jax.tree.map(lambda a: shaped(a.shape, a.dtype), params),
        shaped(stream, F32), *(shaped(r, BF16) for r in reads)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == kernels


def test_latent_decoder_layer_compiles_for_v5e(v5e, one_chip_routing, on_tpu):
    """One recomputed SPARSE decoder layer of the latent cell at its
    published widths and 8,192 positions, forward and backward, state
    and all: Mosaic takes the latent forward and one-pass backward
    kernels and the layer reaches them, each ONCE though the layer is
    recomputed (its policy keeps the forward kernel's results); the
    grouped products' forward stands twice, the expert layer's own
    recomputation.  No 192-wide key exists (the logit is formed as a
    sum in the kernels), and the shared rotary key is never broadcast
    to the heads: besides its (T, 64) self only the (T, 128) tile
    holding it twice."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import latent, moe
    layer = latent.LatentDecoderLayer(
        latent.LatentAttention(32, 512, 128, 64, 128, rope_theta=1e6),
        moe.DroplessMoE(128, 768, top_k=6, experts_held=(0, 16),
                        init="normal", scoring="sigmoid",
                        routed_scaling_factor=2.448,
                        shared_hidden=1536),
        recompute=True)
    stream = (1, 8192, 2048)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = shaped(jax.eval_shape(
        lambda: layer.build(jax.random.PRNGKey(0), stream)))
    state = shaped(jax.eval_shape(lambda: layer.init_state(stream)))

    def loss(params, h, state):
        out, new = layer.apply(params, h, state=state)
        return jnp.sum(jnp.square(out)), new

    text = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, jax.ShapeDtypeStruct(stream, F32, sharding=v5e), state
    ).compile().as_text()
    calls = [line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == sorted(
        ["flash_attention_latent_fwd", "flash_attention_latent_bwd"]
        + 2 * 3 * ["grouped_matmul_fwd"]
        + 3 * ["grouped_matmul_dlhs", "grouped_matmul_drhs"])
    # no (T, 32, 192) array: 192 is no dimension of anything
    assert not re.search(r"\[[\d,]*\b192\b[\d,]*\]", text)
    for line in text.splitlines():
        m = re.search(r" = \w+\[([\d,]+)\]\S* broadcast\(", line)
        if m and {"8192", "32", "64"} <= set(m.group(1).split(",")):
            raise AssertionError(f"k_pe broadcast to the heads: {line}")


@pytest.mark.parametrize("kind", ["global", "window"])
def test_prerouted_decoder_layer_compiles_for_v5e(v5e, one_chip_routing,
                                                  on_tpu, kind):
    """One recomputed decoder layer of the windowed cell at its published
    widths and 16,384 positions, forward and backward, state and all:
    28 query heads on 4 K/V heads (groups of 7), no positions and the
    causal mask or rotary under the 4,096-key window.  Past the one-pass
    budget the backward keeps a query tile's whole dq alone resident and
    writes dk and dv a query head (``_bwd_heads``); the forward kernel
    stands once though the layer is recomputed; the ReGLU experts'
    grouped products as in the latent layer."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import (
        attention, moe, prerouted)
    windowed = kind == "window"
    layer = prerouted.PreRoutedDecoderLayer(
        attention.GroupedQueryAttention(
            28, 4, 128, rope_theta=1.5e6 if windowed else None,
            qk_norm=False,
            mask=sliding_window(4096) if windowed else "causal"),
        moe.DroplessMoE(64, 768, top_k=6, experts_held=(0, 16),
                        init="normal", activation="relu"),
        recompute=True)
    stream = (1, 16384, 2560)

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = shaped(jax.eval_shape(
        lambda: layer.build(jax.random.PRNGKey(0), stream)))
    state = shaped(jax.eval_shape(lambda: layer.init_state(stream)))

    def loss(params, h, state):
        out, new = layer.apply(params, h, state=state)
        return jnp.sum(jnp.square(out)), new

    text = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        params, jax.ShapeDtypeStruct(stream, F32, sharding=v5e), state
    ).compile().as_text()
    calls = [line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sorted(calls) == sorted(
        ["flash_attention_fwd", "flash_attention_bwd_heads"]
        + 2 * 3 * ["grouped_matmul_fwd"]
        + 3 * ["grouped_matmul_dlhs", "grouped_matmul_drhs"])


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_mamba2_decoder_layer_compiles_for_v5e(v5e, one_chip_routing, on_tpu,
                                               kind):
    """One recomputed decoder layer of the Mamba-2 cell at its published
    widths and 8,192 positions, forward and backward: the chunked scan's
    two kernels (64 heads of 64 on one group of 128 states) or flash
    attention on 32 query heads of 64 over 8 K/V heads repeated to the
    group (the one-pass backward), each forward kernel ONCE though the
    layer is recomputed, and no lax form in the program."""
    from analytics_zoo_tpu.pipeline.api.keras.layers import attention, ssm
    mixer = ssm.Mamba2(64, 64, 128) if kind == "mamba" else \
        attention.GroupedQueryAttention(32, 8, 64, rope_theta=None,
                                        qk_norm=False, mask="causal",
                                        scale=1 / 64)
    layer = ssm.PreNormDecoderLayer(mixer, ssm.GatedFeedForward(8192), 1e-5,
                                    recompute=True, residual_multiplier=0.22)
    text = _layer_grad_text(
        layer, (1, 8192, 2048),
        [jax.ShapeDtypeStruct((1, 8192, 2048), F32, sharding=v5e)])
    calls = [line.split(" = ")[0].strip().lstrip("%").rsplit(".", 1)[0]
             for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    prefix = "ssd_scan" if kind == "mamba" else "flash_attention"
    assert sorted(calls) == [prefix + "_bwd", prefix + "_fwd"]


def test_capability_probe_compiles_for_v5e(v5e):
    """On a TPU a probe the compiler refuses is an error, so the probe
    itself must be a kernel the v5e accepts."""
    fn, shapes = fused._probe()
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=v5e)
            for s in shapes]
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("activation", [acts.gelu_erf, acts.elu, acts.selu],
                         ids=lambda a: a.__name__)
def test_activation_mosaic_cannot_lower_takes_lax(v5e, on_tpu, activation):
    """erf and expm1 have no Mosaic lowering in this jaxlib: those
    activations must take the lax form, or the program would be refused
    on the chip and nowhere else."""
    args = [jax.ShapeDtypeStruct(s, F32, sharding=v5e)
            for s in [(64, 768), (768,), (768,)]]
    text = jax.jit(lambda x, g, b: fused.layernorm_act(
        x, g, b, activation=activation)).lower(*args).compile().as_text()
    assert "tpu_custom_call" not in text

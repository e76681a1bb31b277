"""Answers the TPU compiler gives without a chip, kept as tests.

The compiler that ships with the installed jaxlib/libtpu compiles for a TPU
v5e that is described, not attached (on-chip-measurement guide, section 2.3).
Held here: the fused Pallas LN-GRU cell at the Dreamer sizes, the fused
latent-attention and differential-attention kernels and the selective-scan
kernels at the token policies' shapes — "eligible" must imply "compiles". Nothing here runs on a device, and nothing here is a chip
measurement. (The bound, the warning and the cache placement are in
tests/test_core/test_tpu_aot.py.)
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from sheeprl_tpu.models import pallas_diff_attention, pallas_gru, pallas_mla_attention, pallas_selective_scan  # noqa: E402


@pytest.fixture(scope="module")
def one_described_chip():
    try:
        topo = chip_smoke.described_v5e()
    except Exception as err:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"the TPU topology cannot be described here: {err}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one (the next compile would warn): keep the
    # cache out of these tests.
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("size", sorted(chip_smoke.GRU_SHAPES))
def test_ln_gru_eligible_implies_compiles(size, dtype, one_described_chip):
    batch, hidden, d = chip_smoke.GRU_SHAPES[size]
    reason = pallas_gru.ineligible_reason(batch, d, hidden, jnp.dtype(dtype).itemsize)
    if size == "XL_imagination":
        # B=1024 x 3H=12288 keeps 50-64 MiB of blocks per grid step; the
        # compiler's scoped-VMEM limit is 16 MiB. Declared ineligible, and
        # the compiler agrees.
        assert reason is not None and "VMEM" in reason
        with pytest.raises(Exception, match="(?i)vmem"):
            chip_smoke.compile_ln_gru(batch, hidden, d, dtype, one_described_chip)
        return
    assert reason is None
    compiled = chip_smoke.compile_ln_gru(batch, hidden, d, dtype, one_described_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", sorted(chip_smoke.MLA_SHAPES))
def test_mla_attention_eligible_implies_compiles(shape, dtype, one_described_chip):
    batch, seq, grad = chip_smoke.MLA_SHAPES[shape]
    lanes = pallas_mla_attention.LANES
    assert pallas_mla_attention.shape_ineligible_reason(seq, lanes, chip_smoke.MLA_ROPE, lanes, dtype) is None
    compiled = chip_smoke.compile_mla_attention(batch, seq, grad, dtype, one_described_chip)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)  # forward, and one backward kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_mla_attention_longest_eligible_compiles(dtype, one_described_chip):
    """The longest sequences the rule admits (its VMEM bound) are what the compiler admits too."""
    lanes, block = pallas_mla_attention.LANES, pallas_mla_attention.BLOCK
    eligible = lambda seq: pallas_mla_attention.shape_ineligible_reason(seq, lanes, chip_smoke.MLA_ROPE, lanes, dtype) is None  # noqa: E731
    seq = max(n * block for n in range(1, 64) if eligible(n * block))
    assert 4096 <= seq < 63 * block and "VMEM" in pallas_mla_attention.shape_ineligible_reason(seq + block, lanes, chip_smoke.MLA_ROPE, lanes, dtype)
    chip_smoke.compile_mla_attention(1, seq, True, dtype, one_described_chip)


def test_mla_gradient_step_holds_the_kernels(one_described_chip, monkeypatch):
    """The gradient of one attention layer at the cell's widths and its
    `[4, 2080]` minibatch, compiled for the chip: the layer takes the kernels
    (the rule is asked about the shape alone here: this process's backend is
    the CPU) and the compiled step holds them, forward and backward."""
    from sheeprl_tpu.models import transformer as T

    monkeypatch.setattr(pallas_mla_attention, "ineligible_reason", pallas_mla_attention.shape_ineligible_reason)
    cfg = T.TransformerConfig(vocab_size=16032, hidden_size=2048, num_hidden_layers=5, num_attention_heads=32, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512, intermediate_size=6144,
                              moe_intermediate_size=768, n_routed_experts=128, n_shared_experts=2, num_experts_per_tok=6)
    layer = T.MLA(cfg, jnp.bfloat16, jnp.float32)
    batch, seq, _ = chip_smoke.MLA_SHAPES["update"]

    def spec(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_described_chip)

    x, positions, start = spec(batch, seq, cfg.hidden_size), spec(batch, seq, dt=jnp.int32), spec(batch, dt=jnp.int32)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size), jnp.bfloat16),
                                               jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32)))
    params = jax.tree_util.tree_map(lambda p: spec(*p.shape, dt=p.dtype), params)

    def step(params, x, positions, start):
        return jax.grad(lambda p: layer.apply(p, x, positions, start)[0].astype(jnp.float32).sum())(params)

    text = jax.jit(step).lower(params, x, positions, start).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    assert "mla_attention_fwd" in text and "mla_attention_bwd" in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", sorted(chip_smoke.DIFF_SHAPES))
def test_diff_attention_eligible_implies_compiles(shape, dtype, one_described_chip):
    batch, seq, window, grad = chip_smoke.DIFF_SHAPES[shape]
    group = chip_smoke.DIFF_HEADS // chip_smoke.DIFF_KV_HEADS
    assert pallas_diff_attention.shape_ineligible_reason(seq, chip_smoke.DIFF_HEAD_DIM, window, dtype, group) is None
    compiled = chip_smoke.compile_diff_attention(batch, seq, window, grad, dtype, one_described_chip)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)  # forward, and one backward kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_diff_attention_longest_eligible_compiles(dtype, one_described_chip):
    """The longest sequences the rule admits (its VMEM bound) are what the compiler admits too."""
    block, group = pallas_diff_attention.BLOCK, chip_smoke.DIFF_HEADS // chip_smoke.DIFF_KV_HEADS
    reason = lambda seq: pallas_diff_attention.shape_ineligible_reason(seq, chip_smoke.DIFF_HEAD_DIM, None, dtype, group)  # noqa: E731
    seq = max(n * block for n in range(1, 64) if reason(n * block) is None)
    assert 4608 <= seq < 63 * block and "VMEM" in reason(seq + block)
    chip_smoke.compile_diff_attention(1, seq, None, True, dtype, one_described_chip)


def test_diff_attention_gradient_step_holds_the_kernels(one_described_chip, monkeypatch):
    """The gradient of a `full` and a `cross` layer at the cell's widths and its
    `[2, 4128]` minibatch, compiled for the chip: the layers take the kernels
    (the rule is asked about the shape alone here: this process's backend is
    the CPU) and the compiled step holds them, forward and backward, and no
    block of float32 scores beside them."""
    from sheeprl_tpu.models import hybrid_decoder as H

    monkeypatch.setattr(pallas_diff_attention, "ineligible_reason", pallas_diff_attention.shape_ineligible_reason)
    cfg = H.HybridConfig(vocab_size=25008, hidden_size=2560, num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
                         intermediate_size=10240, sliding_window=512, layers_held=(14, 6))
    full, cross = (H.DiffAttention(cfg, index, jnp.bfloat16, jnp.float32) for index in (17, 19))
    batch, seq, _, _ = chip_smoke.DIFF_SHAPES["update_full"]

    def spec(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_described_chip)

    small, none = jnp.zeros((1, 8, cfg.hidden_size), jnp.bfloat16), jnp.zeros((1,), jnp.int32)
    shared = (jnp.zeros((1, 8, 20, 64), jnp.bfloat16),) * 2
    params = jax.eval_shape(lambda: (full.init(jax.random.PRNGKey(0), small, none), cross.init(jax.random.PRNGKey(1), small, none, shared)))
    params = jax.tree_util.tree_map(lambda p: spec(*p.shape, dt=p.dtype), params)

    def step(params, x, start):
        def loss(params):
            out, kept = full.apply(params[0], x, start)
            return (out + cross.apply(params[1], x, start, kept)[0]).astype(jnp.float32).sum()

        return jax.grad(loss)(params)

    text = jax.jit(step).lower(params, spec(batch, seq, cfg.hidden_size), spec(batch, dt=jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 4
    assert "diff_attention_fwd" in text and "diff_attention_bwd" in text
    assert "f32[2,40," not in text and "f32[2,10,2,2," not in text  # the plain path's scores, as `_differential` shapes them


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape", sorted(chip_smoke.SCAN_SHAPES))
def test_selective_scan_eligible_implies_compiles(shape, dtype, one_described_chip):
    batch, seq, grad = chip_smoke.SCAN_SHAPES[shape]
    assert pallas_selective_scan.shape_ineligible_reason(batch, seq, chip_smoke.SCAN_WIDTH, chip_smoke.SCAN_STATE, dtype) is None
    compiled = chip_smoke.compile_selective_scan(batch, seq, grad, dtype, one_described_chip)
    assert compiled.as_text().count("tpu_custom_call") == (2 if grad else 1)  # forward, and one backward kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_selective_scan_most_states_eligible_compiles(dtype, one_described_chip):
    """The most states a lane the rule admits (its VMEM bound) are what the compiler admits too."""
    reason = lambda state: pallas_selective_scan.shape_ineligible_reason(1, 1024, chip_smoke.SCAN_WIDTH, state, dtype)  # noqa: E731
    state = max(n for n in range(8, 1024, 8) if reason(n) is None)
    assert chip_smoke.SCAN_STATE < state < 1016 and "VMEM" in reason(state + 8)
    chip_smoke.compile_selective_scan(1, 1024, True, dtype, one_described_chip, state=state)


def test_mamba_gradient_step_holds_the_scan_kernels(one_described_chip, monkeypatch):
    """The gradient of a Mamba layer at the cell's widths and its `[2, 4128]`
    minibatch, rematerialised as the decoder does it, compiled for the chip:
    the layer takes the kernels (the rule is asked about the shape alone here:
    this process's backend is the CPU), the compiled step holds them under the
    layer's scope, forward and backward, and no loop over positions beside them."""
    from sheeprl_tpu.models import hybrid_decoder as H
    from sheeprl_tpu.telemetry import scopes

    monkeypatch.setattr(pallas_selective_scan, "ineligible_reason", pallas_selective_scan.shape_ineligible_reason)
    cfg = H.HybridConfig(vocab_size=25008, hidden_size=2560, num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
                         intermediate_size=10240, sliding_window=512, layers_held=(14, 6))
    assert (cfg.d_inner, cfg.d_state) == (chip_smoke.SCAN_WIDTH, chip_smoke.SCAN_STATE)
    layer = H.Mamba(cfg, jnp.bfloat16, jnp.float32)
    batch, seq, _ = chip_smoke.SCAN_SHAPES["update"]

    def spec(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_described_chip)

    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size), jnp.bfloat16), jnp.zeros((1,), jnp.int32)))
    params = jax.tree_util.tree_map(lambda p: spec(*p.shape, dt=p.dtype), params)

    def forward(params, x, start):
        with scopes.scope(scopes.LM_SSM):
            return layer.apply(params, x, start)[0]

    def step(params, x, start):
        return jax.value_and_grad(lambda p: jax.checkpoint(forward)(p, x, start).astype(jnp.float32).sum())(params)

    text = jax.jit(step).lower(params, spec(batch, seq, cfg.hidden_size), spec(batch, dt=jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 3  # the forward, the rematerialised forward and the backward
    assert sum("selective_scan_fwd" in line for line in calls) == 2 and sum("selective_scan_bwd" in line for line in calls) == 1
    assert all(scopes.LM_SSM in line for line in calls)
    assert any("transpose(jvp(" in line and "selective_scan_bwd" in line for line in calls)
    assert " while(" not in text  # the plain path's loops over chunks and positions


@pytest.mark.parametrize("shape", sorted(chip_smoke.MLA_SHAPES))
def test_expert_layer_compiles_with_its_bounded_chunks(shape, one_described_chip):
    """One expert layer at the token cell's widths (16 of 128 experts held, top
    6) through the chip's compiler: the gradient with rematerialisation at the
    update's `[4, 2080]`, the forward at the prefill's `[16, 2048]`. The grouped
    products are built over a chunk of the sorted slots (about a quarter of
    them) and nowhere over every slot, inside a loop the held slots bound."""
    from sheeprl_tpu.models import transformer as T

    batch, seq, grad = chip_smoke.MLA_SHAPES[shape]
    cfg = T.TransformerConfig(vocab_size=16032, hidden_size=2048, num_hidden_layers=5, num_attention_heads=32, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512, intermediate_size=6144,
                              moe_intermediate_size=768, n_routed_experts=128, n_shared_experts=2, num_experts_per_tok=6,
                              routed_scaling_factor=2.448, experts_held=(0, 16))
    layer = T.MoE(cfg, jnp.bfloat16, jnp.float32)
    slots = batch * seq * cfg.num_experts_per_tok
    rows = T.expert_chunk_rows(slots, 16, 128)
    assert 4 * rows >= slots > 3 * rows and rows % T.EXPERT_ROW_TILE == 0

    def spec(*dims, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dt, sharding=one_described_chip)

    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size), jnp.bfloat16)))
    params = jax.tree_util.tree_map(lambda p: spec(*p.shape, dt=p.dtype), params)

    def forward(params, x, real):
        return layer.apply(params, x, real)[0]

    def step(params, x, real):
        return jax.grad(lambda p, x: jax.checkpoint(forward)(p, x, real).astype(jnp.float32).sum(), argnums=(0, 1))(params, x)

    text = jax.jit(step if grad else forward).lower(params, spec(batch, seq, cfg.hidden_size), spec(batch, seq, dt=jnp.bool_)).compile().as_text()
    assert f"bf16[{rows},768]" in text and f"bf16[{slots},768]" not in text and f"bf16[{slots},2048]" not in text
    assert " while(" in text and "ragged-dot" in text

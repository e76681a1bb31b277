"""The selective-scan kernels (Pallas interpreter on the CPU) against the plain
`hybrid_decoder.selective_scan` they replace on the TPU: ``y``, the last state
and the cotangents of all five inputs, and the rule that chooses between the
two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import hybrid_decoder as H
from sheeprl_tpu.models import pallas_selective_scan as kernel

SHIPPED = (kernel.LANE_BLOCK, kernel.CHUNK)
#: name -> (rows, positions, inner width, states, where each row's context begins). Tiles of 128 lanes and chunks
#: of 16 positions keep the interpreter quick: 45 positions are 2.8 chunks, as 4128 are 32.25 of 128.
CASES = {
    "one_row_one_lane_block": (1, 40, 128, 8, None),
    "two_rows_two_lane_blocks_ragged_length": (2, 45, 256, 16, None),
    "eight_rows": (8, 32, 128, 8, None),
    "left_padded_rows": (2, 50, 256, 16, [0, 21]),
    "shorter_than_a_chunk": (2, 11, 128, 8, [0, 4]),
}
NAMES = ("y", "last state", "x", "delta", "a", "b", "c")


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(kernel, "LANE_BLOCK", 128)
    monkeypatch.setattr(kernel, "CHUNK", 16)


def operands(batch, seq, width, state, start, dtype, seed=0):
    """Inputs as `Mamba.__call__` makes them: ``x`` and ``delta`` zero before a row's ``start`` (a left pad feeds
    neither the state nor its decay), ``a`` negative; and cotangents for ``y`` and the last state."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    real = jnp.ones((batch, seq, 1), bool) if start is None else (jnp.arange(seq)[None, :] >= jnp.asarray(start)[:, None])[..., None]
    x = jnp.where(real, jax.random.normal(keys[0], (batch, seq, width)), 0).astype(dtype)
    delta = jnp.where(real, jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, width)) - 1.0), 0.0)
    a = -jnp.exp(0.5 * jax.random.normal(keys[2], (state, width)))
    b, c = (jax.random.normal(k, (batch, seq, state)).astype(dtype) for k in keys[3:5])
    d_y = jax.random.normal(keys[5], (batch, seq, width))
    d_last = jax.random.normal(keys[6], (batch, state, width))
    return (x, delta, a, b, c), (d_y, d_last)


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape and np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), what


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_plain_scan(case, dtype, tol):
    """``y``, the last state and the five cotangents. In bfloat16 both compute in float32 from the same inputs and
    round their cotangents to bfloat16 at the end: a sum taken in another order may round one unit the other way."""
    args, cotangents = operands(*CASES[case], dtype)
    got, vjp = jax.vjp(lambda *z: kernel.selective_scan(*z, interpret=True), *args)
    want, want_vjp = jax.vjp(H.selective_scan, *args)
    for name, g, w in zip(NAMES, (*got, *vjp(cotangents)), (*want, *want_vjp(cotangents))):
        assert g.dtype == w.dtype, name
        close(g, w, tol, name)


def test_a_cotangent_for_y_alone_is_the_training_step():
    """What the gradient step asks (the last state feeds no loss): its cotangent arrives as zeros."""
    args, (d_y, _) = operands(2, 45, 256, 16, [3, 0], jnp.float32, seed=4)
    loss = lambda scan: lambda *z: jnp.sum(scan(*z)[0] * d_y)  # noqa: E731
    got = jax.grad(loss(lambda *z: kernel.selective_scan(*z, interpret=True)), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(H.selective_scan), argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, w in zip(NAMES[2:], got, want):
        close(g, w, 1e-5, name)


def test_the_rule_takes_the_plain_path_off_the_tpu_and_says_why(monkeypatch):
    reason = kernel.ineligible_reason(2, 4128, 5120, 16, jnp.bfloat16)
    assert reason is not None and jax.default_backend() in reason and "TPU" in reason
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        patch.setattr(kernel, "LANE_BLOCK", SHIPPED[0])
        patch.setattr(kernel, "CHUNK", SHIPPED[1])
        assert kernel.ineligible_reason(2, 4128, 5120, 16, jnp.bfloat16) is None
        # a player that acts from the host traces under `jax.default_device(<the CPU>)`
        with jax.default_device(jax.devices("cpu")[0]):
            assert "cpu" in kernel.ineligible_reason(8, 4096, 5120, 16, jnp.bfloat16)
    # and the layer follows it: no kernel in what it traces here
    layer, params, u, start = mamba_layer()
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p: layer.apply(p, u, start))(params))


@pytest.mark.parametrize("shape,word", [
    ((2, 4128, 5120, 16, jnp.bfloat16), None),
    ((8, 4096, 5120, 16, jnp.bfloat16), None),
    ((2, 4128, 5120, 16, jnp.float32), None),
    ((1, 1, 512, 8, jnp.float32), None),
    ((2, 4128, 5000, 16, jnp.bfloat16), "lane block"),
    ((2, 4128, 5120, 12, jnp.bfloat16), "sublanes"),
    ((2, 4128, 5120, 256, jnp.bfloat16), "VMEM"),
    ((2, 4128, 5120, 16, jnp.float16), "float16"),
    ((2, 0, 5120, 16, jnp.bfloat16), "no position"),
], ids=["cell_update", "cell_prefill", "cell_update_f32", "one_position", "width_off_the_lane_block", "states_off_the_sublanes",
        "too_many_states", "float16", "empty"])
def test_the_rule_on_shapes(shape, word, monkeypatch):
    monkeypatch.setattr(kernel, "LANE_BLOCK", SHIPPED[0])
    monkeypatch.setattr(kernel, "CHUNK", SHIPPED[1])
    reason = kernel.shape_ineligible_reason(*shape)
    assert (reason is None) if word is None else (reason is not None and word in reason)


CELL = dict(vocab_size=25008, hidden_size=2560, num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
            intermediate_size=10240, sliding_window=512, layers_held=(14, 6))


def test_the_backbone_counts_its_fused_scans_and_their_chunks(monkeypatch):
    """`ssm/scan_fused`: the held Mamba layers the rule lets onto the kernels; `ssm/scan_chunks` follows the chunk of
    the path that runs (the plain path's 64, the kernels' own on the chip)."""
    monkeypatch.setattr(kernel, "LANE_BLOCK", SHIPPED[0])
    monkeypatch.setattr(kernel, "CHUNK", SHIPPED[1])
    backbone = H.HybridConfig(**CELL).backbone(jnp.bfloat16, jnp.float32)
    assert backbone.fused_scan_layers(4128) == 0 and backbone.scan_chunks(4128) == 2 * 65  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backbone.fused_scan_layers(4128) == 2  # layers 14 and 16
    assert backbone.scan_chunks(4128) == 2 * -(-4128 // kernel.CHUNK)
    narrow = H.HybridConfig(**{**CELL, "hidden_size": 2400})
    assert narrow.backbone(jnp.bfloat16, jnp.float32).fused_scan_layers(4128) == 0  # an inner width of 4800: 9.375 lane blocks
    assert narrow.backbone(jnp.bfloat16, jnp.float32).scan_chunks(4128) == 2 * 65


MICRO = dict(vocab_size=16, hidden_size=128, num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32, sliding_window=16, d_state=8, initializer_range=0.2)


def mamba_layer(seq=45):
    """A Mamba layer whose inner width is two of the tests' lane blocks, over left-padded rows."""
    cfg = H.HybridConfig(**MICRO)
    layer = H.Mamba(cfg)
    start = jnp.asarray([0, 13], jnp.int32)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.hidden_size))
    return layer, layer.init(jax.random.PRNGKey(0), u, start), u, start


def test_the_layer_with_the_kernels_is_the_layer_without(monkeypatch):
    """Both forms share the projections, the convolution and the gate: the layer's output, the memory, the last
    state and the parameters' gradients agree between them."""
    layer, params, u, start = mamba_layer()

    def loss(p):
        out, memory, (_, state) = layer.apply(p, u, start)
        return jnp.sum(out ** 2) + jnp.sum(memory * jnp.cos(memory)) + jnp.sum(state ** 2)

    plain, plain_grads = jax.value_and_grad(loss)(params)
    plain_out = layer.apply(params, u, start)
    monkeypatch.setattr(kernel, "ineligible_reason", lambda *a: None)
    monkeypatch.setattr(kernel, "selective_scan", lambda *a, run=kernel.selective_scan: run(*a, interpret=True))
    assert "pallas_call" in str(jax.make_jaxpr(loss)(params))
    fused, fused_grads = jax.value_and_grad(loss)(params)
    close(fused, plain, 1e-5, "the loss")
    for name, got, want in zip(("output", "memory", "conv tail", "last state"), jax.tree_util.tree_leaves(layer.apply(params, u, start)),
                               jax.tree_util.tree_leaves(plain_out)):
        close(got, want, 1e-5, name)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(fused_grads), jax.tree_util.tree_leaves(plain_grads)):
        close(got, want, 1e-4, jax.tree_util.keystr(path))

"""The fused latent-attention kernels (Pallas interpreter on the CPU) against
the blocked plain-JAX path they replace on the TPU: outputs and the gradients
to all five operands, and the rule that chooses between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import pallas_mla_attention as kernel
from sheeprl_tpu.models import transformer as T

ROPE = 64
SCALE = (kernel.LANES + ROPE) ** -0.5
SHIPPED_BLOCK = kernel.BLOCK
#: name -> (sequence length, start of each row, tile, heads). Tiles of 128 keep
#: the interpreter quick: 300 positions are 2.3 of them, as 2080 are 4.06 of
#: 512. Two cases run the tile as shipped: three of them on the diagonal, one
#: row's keys beginning inside the second (its first tile is all padding); and
#: the cell's 2080 positions with the first two of five tiles all padding. The
#: left padding covers whole tiles (1, 2 and 3 of them, the walks start past
#: them), ends one position short of a tile's edge, or is the whole row.
CASES = {
    "batch1_start0": (300, [0], 128, 2),
    "batch4_start_0_midtile_edge_allpadding": (300, [0, 70, 128, 300], 128, 2),
    "whole_tiles_start_in_the_last": (256, [5, 200], 128, 2),
    "shorter_than_a_tile": (72, [0, 9], 128, 2),
    "shipped_tile_start_0_and_midtile": (1100, [0, 600], SHIPPED_BLOCK, 1),
    "padding_covers_1_2_3_whole_tiles": (500, [128, 256, 384, 0], 128, 2),
    "padding_one_short_of_a_tile_edge": (500, [127, 255, 383], 128, 2),
    "padding_throughout_the_row": (512, [512, 0], 128, 2),
    "shipped_tile_start_1024_of_2080": (2080, [1024, 1535], SHIPPED_BLOCK, 1),
}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(kernel, "BLOCK", 128)


def operands(seq, start, dtype, heads=2, seed=0):
    batch = len(start)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    make = lambda k, *shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)  # noqa: E731
    args = (make(keys[0], batch, seq, heads, kernel.LANES), make(keys[1], batch, seq, heads, ROPE),
            make(keys[2], batch, seq, heads, kernel.LANES), make(keys[3], batch, seq, ROPE),
            make(keys[4], batch, seq, heads, kernel.LANES))
    return args, jnp.asarray(start, jnp.int32), make(keys[5], batch, seq, heads, kernel.LANES)


def attend(q_nope, q_rope, k_nope, k_rope, v, start):
    """The kernels on explicit keys and values: they go in as the latent, a
    head's keys beside its values, with the identity as its expansion (exact
    in either dtype), so that a gradient to ``k_nope`` or ``v`` is the latent's."""
    batch, seq = k_nope.shape[:2]
    latent = jnp.concatenate([k_nope, v], axis=-1).reshape(batch, seq, -1)
    eye = jnp.eye(latent.shape[-1], dtype=latent.dtype)
    return kernel.mla_attention(q_nope, q_rope, latent, eye, k_rope, start, SCALE, interpret=True)


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), what


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_blocked_path(case, dtype, tol, monkeypatch):
    seq, start, tile, heads = CASES[case]
    monkeypatch.setattr(kernel, "BLOCK", tile)
    args, start, d_out = operands(seq, start, dtype, heads)
    real = (jnp.arange(seq)[None, :] >= start[:, None])[:, :, None, None]  # rows that see a key

    out, vjp = jax.vjp(lambda *a: attend(*a, start), *args)
    want, want_vjp = jax.vjp(lambda *a: T.blocked_attention(*a, start, SCALE), *args)
    assert out.shape == want.shape and out.dtype == want.dtype
    close(jnp.where(real, out, 0), jnp.where(real, want, 0), tol, "output at the rows that see a key")
    # a row inside the padding: finite (zero), and nothing flows from it, whatever its cotangent
    assert not np.asarray(jnp.where(real, 0, out), np.float32).any()
    grads = vjp(d_out)
    want_grads = want_vjp(jnp.where(real, d_out, 0).astype(dtype))
    for name, got, want_grad in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"), grads, want_grads):
        assert got.shape == want_grad.shape and got.dtype == want_grad.dtype, name
        close(got, want_grad, tol, f"gradient to {name}")
    # the queries inside the padding get none either, nor do the keys and values there
    for name, got in zip(("q_nope", "q_rope", "k_nope", "k_rope", "v"), grads):
        assert not np.asarray(jnp.where(real.reshape(real.shape[:2] + (1,) * (got.ndim - 2)), 0, got), np.float32).any(), name


@pytest.mark.parametrize("seq,visits", [(2560, [15, 15, 10, 6, 3]), (2048, [10, 10, 6, 3, 1]), (2080, [15, 15, 10, 6, 3])],
                         ids=["update_padded", "prefill", "update"])
def test_tile_visits_by_hand(seq, visits, monkeypatch):
    """Visits a (row, head) for rows whose keys begin at 0, 511, 512, 1024 and
    1536 of the shipped tile; with those skipped they make every pair on or
    below the diagonal."""
    monkeypatch.setattr(kernel, "BLOCK", SHIPPED_BLOCK)
    got, skipped = kernel.tile_visits(jnp.asarray([0, 511, 512, 1024, 1536], jnp.int32), seq)
    tiles = -(-seq // SHIPPED_BLOCK)
    assert np.asarray(got).tolist() == visits
    assert (np.asarray(got) + np.asarray(skipped)).tolist() == [tiles * (tiles + 1) // 2] * 5
    # a row that is padding throughout is skipped whole
    assert np.asarray(kernel.tile_visits(jnp.asarray([seq], jnp.int32), seq)[0]).tolist() == [0 if seq % SHIPPED_BLOCK == 0 else 1]


def test_the_rule_takes_the_plain_path_off_the_tpu_and_says_why(monkeypatch):
    reason = kernel.ineligible_reason(2080, 128, 64, 128, jnp.bfloat16)
    assert reason is not None and jax.default_backend() in reason and "TPU" in reason
    # a player that acts from the host traces under `jax.default_device(<the CPU>)` while the default backend is the chip
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert kernel.ineligible_reason(2080, 128, 64, 128, jnp.bfloat16) is None
        with jax.default_device(jax.devices("cpu")[0]):
            assert "cpu" in kernel.ineligible_reason(2080, 128, 64, 128, jnp.bfloat16)
    # and the layer follows it: no kernel in what it traces here
    cfg = T.TransformerConfig(vocab_size=16, hidden_size=32, num_hidden_layers=1, num_attention_heads=2, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=16, intermediate_size=32,
                              moe_intermediate_size=16, n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2)
    layer = T.MLA(cfg)
    x, positions, start = jnp.zeros((1, 130, 32)), jnp.arange(130)[None], jnp.zeros((1,), jnp.int32)
    params = layer.init(jax.random.PRNGKey(0), x, positions, start)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda p: layer.apply(p, x, positions, start))(params))


@pytest.mark.parametrize("shape,word", [
    ((2080, 128, 64, 128, jnp.bfloat16), None),
    ((2048, 128, 64, 128, jnp.float32), None),
    ((2080, 64, 64, 64, jnp.bfloat16), "128-lane"),
    ((2080, 128, 192, 128, jnp.bfloat16), "rotated"),
    ((2080, 128, 64, 128, jnp.float16), "float16"),
    ((64, 128, 64, 128, jnp.bfloat16), "fewer than one tile"),
    ((65536, 128, 64, 128, jnp.bfloat16), "VMEM"),
], ids=["cell_update", "cell_prefill_f32", "narrow_heads", "wide_rope", "float16", "short", "too_long"])
def test_the_rule_on_shapes(shape, word):
    reason = kernel.shape_ineligible_reason(*shape)
    assert (reason is None) if word is None else (reason is not None and word in reason)


def test_the_layer_with_the_kernel_is_the_layer_without(monkeypatch):
    """Both forms share the projections, RoPE and the output product: the
    layer's output and its parameters' gradients agree between them."""
    cfg = T.TransformerConfig(vocab_size=16, hidden_size=32, num_hidden_layers=1, num_attention_heads=2, qk_nope_head_dim=128,
                              qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=16, intermediate_size=32,
                              moe_intermediate_size=16, n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
                              initializer_range=0.2)
    layer = T.MLA(cfg)
    start = jnp.asarray([0, 37], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 150, 32))
    positions = jnp.maximum(jnp.arange(150)[None, :] - start[:, None], 0)
    params = layer.init(jax.random.PRNGKey(0), x, positions, start)
    real = (jnp.arange(150)[None, :] >= start[:, None])[..., None]

    def loss(p):
        out, _ = layer.apply(p, x, positions, start)
        return jnp.sum(jnp.where(real, out, 0) ** 2)

    plain, plain_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(kernel, "ineligible_reason", lambda *a: None)
    monkeypatch.setattr(kernel, "mla_attention", lambda *a, run=kernel.mla_attention: run(*a, interpret=True))
    assert "pallas_call" in str(jax.make_jaxpr(loss)(params))
    fused, fused_grads = jax.value_and_grad(loss)(params)
    close(fused, plain, 1e-5, "the layer's output")
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(fused_grads), jax.tree_util.tree_leaves(plain_grads)):
        close(got, want, 1e-4, jax.tree_util.keystr(path))

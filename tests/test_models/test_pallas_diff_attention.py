"""The fused differential-attention kernels (Pallas interpreter on the CPU)
against the blocked plain-JAX path they replace on the TPU: outputs and the
gradients to q, k, v and lambda, with and without a window, and the rule that
chooses between the two."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.models import hybrid_decoder as H
from sheeprl_tpu.models import pallas_diff_attention as kernel

D = kernel.LANES // 2
LAM = 0.37
SHIPPED_BLOCK = kernel.BLOCK
#: name -> (sequence length, start of each row, tile, window, query heads, key/value heads). Tiles of 128 keep the
#: interpreter quick: 300 positions are 2.3 of them, as 4128 are 8.06 of 512; the windows reach one tile back (a
#: whole tile, as 512 of 512), two (not a multiple of the tile) and none (shorter than a tile). One case a kind
#: runs the tile as shipped, a row's keys beginning inside the second tile (its first is all padding).
CASES = {
    "full_batch1_start0": (300, [0], 128, None, 4, 2),
    "full_start_0_midtile_edge_allpadding": (300, [0, 70, 128, 300], 128, None, 4, 2),
    "full_two_groups": (260, [3, 131], 128, None, 8, 4),
    "full_whole_tiles_start_in_the_last": (256, [5, 200], 128, None, 4, 2),
    "full_shorter_than_a_tile": (72, [0, 9], 128, None, 4, 2),
    "full_shipped_tile": (SHIPPED_BLOCK * 2 + 76, [0, SHIPPED_BLOCK + 88], SHIPPED_BLOCK, None, 4, 2),
    "window_of_one_tile": (300, [0, 70, 128, 300], 128, 128, 4, 2),
    "window_not_a_multiple": (420, [0, 150], 128, 200, 4, 2),
    "window_inside_a_tile": (300, [0, 70], 128, 50, 4, 2),
    "window_longer_than_the_sequence": (200, [0, 31], 128, 512, 4, 2),
    "window_two_groups": (260, [3, 131], 128, 128, 8, 4),
    "window_shipped_tile": (SHIPPED_BLOCK * 2 + 76, [0, SHIPPED_BLOCK + 88], SHIPPED_BLOCK, SHIPPED_BLOCK, 4, 2),
}


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(kernel, "BLOCK", 128)


def operands(seq, start, dtype, heads, kv_heads, seed=0):
    batch = len(start)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    make = lambda k, *shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)  # noqa: E731
    args = (make(keys[0], batch, seq, heads, D), make(keys[1], batch, seq, kv_heads, D), make(keys[2], batch, seq, kv_heads, D))
    d_out = jax.random.normal(keys[3], (batch, seq, heads // 2, 2 * D), jnp.float32)
    return args, jnp.asarray(start, jnp.int32), d_out


def close(got, want, tol, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all(), what
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0), what


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_blocked_path(case, dtype, tol, monkeypatch):
    seq, start, tile, window, heads, kv_heads = CASES[case]
    monkeypatch.setattr(kernel, "BLOCK", tile)
    args, start, d_out = operands(seq, start, dtype, heads, kv_heads)
    lam = jnp.float32(LAM)
    real = (jnp.arange(seq)[None, :] >= start[:, None])[:, :, None, None]  # rows that see a key

    out, vjp = jax.vjp(lambda q, k, v, lam: kernel.diff_attention(q, k, v, start, lam, window, interpret=True), *args, lam)
    want, want_vjp = jax.vjp(lambda q, k, v, lam: H.blocked_differential(q, k, v, start, lam, window), *args, lam)
    assert out.shape == want.shape and out.dtype == want.dtype == jnp.float32
    close(jnp.where(real, out, 0), jnp.where(real, want, 0), tol, "output at the rows that see a key")
    # a row inside the padding: finite (zero), and nothing flows from it, whatever its cotangent
    assert not np.asarray(jnp.where(real, 0, out)).any()
    grads = vjp(d_out)
    want_grads = want_vjp(jnp.where(real, d_out, 0))
    for name, got, want_grad in zip(("q", "k", "v", "lambda"), grads, want_grads):
        assert got.shape == want_grad.shape and got.dtype == want_grad.dtype, name
        # lambda's gradient is one sum over every position and pair: held against the size of what it sums
        scale = float(np.sqrt(out.size)) if name == "lambda" else 1.0
        close(got / scale, want_grad / scale, tol, f"gradient to {name}")
    # the queries inside the padding get none either
    assert not np.asarray(jnp.where(real, 0, grads[0]), np.float32).any()


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_bfloat16_kernel_stands_where_the_plain_path_stands_from_float32(window):
    """In bfloat16 the kernels are held to the plain path's own distance from
    float32: output and gradients no further from the float32 answer than
    `blocked_differential` in bfloat16 is (with a quarter of room)."""
    seq, start = 300, [0, 70]
    (q, k, v), start, d_out = operands(seq, start, jnp.float32, 4, 2, seed=3)
    lam = jnp.float32(LAM)
    real = (jnp.arange(seq)[None, :] >= start[:, None])[:, :, None, None]
    d_out = jnp.where(real, d_out, 0)
    plain = lambda q, k, v, lam: H.blocked_differential(q, k, v, start, lam, window)  # noqa: E731
    fused = lambda q, k, v, lam: kernel.diff_attention(q, k, v, start, lam, window, interpret=True)  # noqa: E731

    def answers(fn, dtype):
        out, vjp = jax.vjp(fn, q.astype(dtype), k.astype(dtype), v.astype(dtype), lam)
        return [jnp.where(real, out, 0), *vjp(d_out)]

    exact = answers(plain, jnp.float32)
    # lambda's gradient is one sum of cancelling terms, -sum(d_out * o_2): held against the size of what it sums
    terms = d_out * (plain(q, k, v, jnp.float32(0.0)) - plain(q, k, v, jnp.float32(1.0)))
    sizes = [jnp.linalg.norm(e.ravel()) for e in exact[:4]] + [jnp.linalg.norm(terms.ravel())]
    distance = lambda got: [float(jnp.linalg.norm((g.astype(jnp.float32) - e).ravel()) / size)  # noqa: E731
                            for g, e, size in zip(got, exact, sizes)]
    for name, ours, theirs in zip(("output", "q", "k", "v", "lambda"), distance(answers(fused, jnp.bfloat16)),
                                  distance(answers(plain, jnp.bfloat16))):
        assert ours <= 1.25 * theirs + (5e-3 if name == "lambda" else 1e-4), (name, ours, theirs)


def test_shared_keys_and_values_get_the_sum_of_both_uses():
    """A `cross` layer reads the `full` layer's keys and values: their gradient is the sum of the two kernels'."""
    seq, start = 200, [0, 45]
    (q, k, v), start, d_out = operands(seq, start, jnp.float32, 4, 2, seed=5)
    q2 = jnp.flip(q, axis=2)

    def both(fn):
        def loss(q, q2, k, v):
            return jnp.sum((fn(q, k, v, start, jnp.float32(LAM), None) + fn(q2, k, v, start, jnp.float32(0.61), None)) * d_out)

        return jax.grad(loss, argnums=(0, 1, 2, 3))(q, q2, k, v)

    real = (jnp.arange(seq)[None, :] >= start[:, None])[:, :, None, None]
    d_out = jnp.where(real, d_out, 0)
    fused = lambda *a: kernel.diff_attention(*a, interpret=True)  # noqa: E731
    for name, got, want in zip(("q", "q2", "k", "v"), both(fused), both(H.blocked_differential)):
        close(got, want, 2e-5, f"gradient to {name}")


def test_the_rule_takes_the_plain_path_off_the_tpu_and_says_why(monkeypatch):
    reason = kernel.ineligible_reason(4128, 64, None, jnp.bfloat16)
    assert reason is not None and jax.default_backend() in reason and "TPU" in reason
    # a player that acts from the host traces under `jax.default_device(<the CPU>)` while the default backend is the chip
    with monkeypatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        assert kernel.ineligible_reason(4128, 64, 512, jnp.bfloat16) is None
        with jax.default_device(jax.devices("cpu")[0]):
            assert "cpu" in kernel.ineligible_reason(4128, 64, 512, jnp.bfloat16)
    # and the layer follows it: no kernel in what it traces here
    for index in (1, 5, 7):
        layer, params, x, start = attention_layer(index, seq=130)
        assert "pallas_call" not in str(jax.make_jaxpr(lambda p: layer.apply(p, x, start, shared_for(layer, x)))(params))


@pytest.mark.parametrize("shape,word", [
    ((4128, 64, None, jnp.bfloat16), None),
    ((4128, 64, 512, jnp.bfloat16), None),
    ((4096, 64, None, jnp.float32), None),
    ((4096, 64, 512, jnp.float32), None),
    ((4128, 128, None, jnp.bfloat16), "128-lane"),
    ((4128, 32, 512, jnp.bfloat16), "128-lane"),
    ((4128, 64, None, jnp.float16), "float16"),
    ((4128, 64, 0, jnp.bfloat16), "holds no key"),
    ((64, 64, None, jnp.bfloat16), "fewer than one tile"),
    ((65536, 64, None, jnp.bfloat16), "VMEM"),
], ids=["cell_update_full", "cell_update_window", "cell_prefill_f32_full", "cell_prefill_f32_window", "wide_heads",
        "narrow_heads", "float16", "empty_window", "short", "too_long"])
def test_the_rule_on_shapes(shape, word, monkeypatch):
    monkeypatch.setattr(kernel, "BLOCK", SHIPPED_BLOCK)
    reason = kernel.shape_ineligible_reason(*shape)
    assert (reason is None) if word is None else (reason is not None and word in reason)


def test_the_backbone_counts_its_fused_layers(monkeypatch):
    """`lm/attention_fused`: the held attention layers the rule lets onto the kernels, at the cell's widths and the micro ones."""
    cell = H.HybridConfig(vocab_size=25008, hidden_size=2560, num_hidden_layers=32, num_attention_heads=40, num_key_value_heads=20,
                          intermediate_size=10240, sliding_window=512, layers_held=(14, 6))
    monkeypatch.setattr(kernel, "BLOCK", SHIPPED_BLOCK)
    assert cell.backbone(jnp.bfloat16, jnp.float32).fused_attention_layers(4128) == 0  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cell.backbone(jnp.bfloat16, jnp.float32).fused_attention_layers(4128) == 3  # window, full, cross
    assert cell.backbone(jnp.bfloat16, jnp.float32).fused_attention_layers(130) == 0  # fewer positions than a tile
    assert H.HybridConfig(**{**MICRO, "hidden_size": 32}).backbone(jnp.float32, jnp.float32).fused_attention_layers(4128) == 0  # heads of 8


MICRO = dict(vocab_size=16, hidden_size=4 * D, num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=32, sliding_window=128, initializer_range=0.2)


def attention_layer(index, seq):
    """Published layer ``index`` of eight (1 = swa, 5 = full, 7 = cross) at heads of 64."""
    cfg = H.HybridConfig(**MICRO)
    layer = H.DiffAttention(cfg, index)
    start = jnp.asarray([0, 37], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.hidden_size))
    params = layer.init(jax.random.PRNGKey(0), x, start, shared_for(layer, x))
    return layer, params, x, start


def shared_for(layer, x):
    """The `full` layer's keys and values a `cross` layer reads (any will do), else None."""
    cfg = layer.cfg
    if cfg.kind(layer.index) != "cross":
        return None
    make = lambda seed: jax.random.normal(jax.random.PRNGKey(seed), (*x.shape[:2], cfg.num_key_value_heads, cfg.head_dim))  # noqa: E731
    return make(7), make(8)


@pytest.mark.parametrize("index", [1, 5, 7], ids=["swa", "full", "cross"])
def test_the_layer_with_the_kernel_is_the_layer_without(index, monkeypatch):
    """Both forms share the projections, lambda, the sub-layer norm and the
    output product: the layer's output and its parameters' gradients (the
    lambda vectors' among them) agree between them."""
    layer, params, x, start = attention_layer(index, seq=300)
    shared = shared_for(layer, x)
    real = (jnp.arange(x.shape[1])[None, :] >= start[:, None])[..., None]

    def loss(p):
        out, _ = layer.apply(p, x, start, shared)
        return jnp.sum(jnp.where(real, out, 0) ** 2)

    plain, plain_grads = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(kernel, "ineligible_reason", lambda *a: None)
    monkeypatch.setattr(kernel, "diff_attention", lambda *a, run=kernel.diff_attention: run(*a, interpret=True))
    assert "pallas_call" in str(jax.make_jaxpr(loss)(params))
    fused, fused_grads = jax.value_and_grad(loss)(params)
    close(fused, plain, 1e-5, "the layer's output")
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(fused_grads), jax.tree_util.tree_leaves(plain_grads)):
        close(got, want, 1e-4, jax.tree_util.keystr(path))

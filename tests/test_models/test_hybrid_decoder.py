"""The phi4flash decoder blocks (models/hybrid_decoder.py) at a micro size on
the CPU, float32: every kind of layer against the benchmark's plain reference
(logits, loss, gradients), the one-token forms over the three kinds of player
state against the whole-sequence form, left padding, the chunked scan, and
the held layers of a pipeline stage."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sheeprl_tpu.algos.ppo_lm.agent import LMPolicy, PPOLMAgent  # noqa: E402
from sheeprl_tpu.models import hybrid_decoder as H  # noqa: E402

# the published ratios of widths (inner = 2 x hidden, heads 2 x kv heads, MLP 4 x hidden), all six kinds in 8 layers
MICRO = dict(vocab_size=48, hidden_size=32, num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
             intermediate_size=128, sliding_window=4, d_state=4, d_conv=4, expand=2, dt_rank=2)
P, R = 10, 6  # prompts longer than the window: the ring wraps during prefill and again while decoding


def reference_model(layers_held=(0, 8), depth=8, **over):
    return dict(MICRO, layer_norm_eps=1e-5, layers_held=list(layers_held), published={"num_hidden_layers": depth},
                prompt_len=P, rollout_steps=R, clip_coef=0.2, vf_coef=0.1, ent_coef=0.01,
                optim={"lr": 1e-3, "eps": 1e-8, "clip": 1.0}, **over)


def make(cfg, seed=7):
    from benchmarks.harness import weights

    agent = PPOLMAgent(cfg, P, R, jnp.float32, jnp.float32)
    return agent, weights.make_weights(jax.eval_shape(agent.init_params, jax.random.PRNGKey(0)), seed)


@pytest.fixture(scope="module")
def agent_and_params():
    return make(H.HybridConfig(**MICRO))


def left_padded(rng, length, width=P):
    prompt = np.zeros((width,), np.int32)
    prompt[width - length:] = rng.integers(0, MICRO["vocab_size"], length)
    return prompt


def close(got, want, tol=1e-4):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


def test_the_kinds_follow_the_published_index():
    cfg = H.HybridConfig(**dict(MICRO, num_hidden_layers=32))
    kinds = [cfg.kind(i) for i in range(32)]
    assert [kinds.count(k) for k in ("ssm", "swa", "full", "cross", "gmu")] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == ["ssm", "swa", "ssm", "full", "gmu", "cross"] and cfg.memory_layer == 16 and cfg.kv_layer == 17
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    assert H.HybridConfig(**dict(MICRO, hidden_size=2560, dt_rank=None)).dt_rank == 160
    assert [H.HybridConfig(**MICRO).kind(i) for i in range(8)] == ["ssm", "swa", "ssm", "swa", "ssm", "full", "gmu", "cross"]


@pytest.mark.parametrize("held, missing", [((18, 2), "keys and values"), ((18, 1), "memory"), ((17, 3), "memory"), ((16, 1), None),
                                           ((14, 6), None), ((0, 16), None)])
def test_a_held_range_needs_the_sources_of_its_layers(held, missing):
    config = dict(MICRO, num_hidden_layers=32, layers_held=held)
    if missing is None:
        assert H.HybridConfig(**config).layers == range(held[0], held[0] + held[1])
    else:
        with pytest.raises(ValueError, match=missing):
            H.HybridConfig(**config)


def test_decode_through_the_three_kinds_of_state_is_the_whole_sequence_form_and_the_reference(agent_and_params):
    """Two envs side by side, the second reset (a new prompt, prefill) while
    the first keeps decoding; prompts of 9 and 6 tokens against a window of 4,
    so the ring has wrapped at prefill and wraps again while decoding. Every
    logit the player produced through prefill, ring, shared keys and values and
    recurrent state is the whole-sequence form's and the reference's."""
    from benchmarks.harness.adapters import ppo_lm_hybrid as adapter
    from benchmarks.reference.phi4flash_ppo import Reference

    agent, params = agent_and_params
    rng = np.random.default_rng(3)
    lens = {"a": 9, "b": 6, "c": 10}
    prompts = {k: left_padded(rng, n) for k, n in lens.items()}
    fed = {k: rng.integers(0, MICRO["vocab_size"], R).astype(np.int32) for k in lens}
    prefill, decode = jax.jit(agent.prefill), jax.jit(agent.decode)
    key = jax.random.PRNGKey(0)
    got = {k: [] for k in lens}

    def keep(state, names):
        for row, name in enumerate(names):
            if name:
                got[name].append(np.asarray(state["logits"][row]))

    state = agent.init_state(2)
    assert {k: len(v) for k, v in state.items() if isinstance(v, tuple)} == dict(win_k=2, win_v=2, full_k=1, full_v=1, conv=3, ssm=3)
    assert state["win_k"][0].shape == (2, 4, 2, 8) and state["full_k"][0].shape == (2, P + R, 2, 8)
    assert state["conv"][0].shape == (2, 3, 64) and state["ssm"][0].shape == (2, 4, 64) and state["ssm"][0].dtype == jnp.float32
    _, state, key = prefill(params, state, np.stack([prompts["a"], prompts["b"]]), np.array([9, 6]), np.array([True, True]), key)
    keep(state, "ab")
    for t in range(2):
        _, state, key = decode(params, state, np.array([fed["a"][t], fed["b"][t]]), key)
        keep(state, "ab")
    before = np.asarray(state["logits"][0])
    _, state, key = prefill(params, state, np.stack([prompts["a"], prompts["c"]]), np.array([9, 10]), np.array([False, True]), key)
    assert np.array_equal(np.asarray(state["logits"][0]), before) and int(state["pos"][0]) == P + 2 and int(state["pos"][1]) == P
    keep(state, [None, "c"])
    for t in range(2, R - 1):
        _, state, key = decode(params, state, np.array([fed["a"][t], fed["c"][t - 2]]), key)
        keep(state, ["a", "c"])
    ref = Reference(reference_model())
    flat = {k: jnp.asarray(v) for k, v in adapter.to_reference(params).items()}
    for name, steps in got.items():
        whole = np.concatenate([prompts[name], fed[name]]).astype(np.int32)
        start = P - lens[name]
        logits, _, _ = agent.evaluate(params, whole[None], np.array([start]))
        plain = ref.logits(flat, whole, start)
        assert len(steps) >= 3
        for t, step in enumerate(steps):
            assert close(step, logits[0, t]), (name, t)
            assert close(step, plain[t]), (name, t)


def test_a_left_padded_row_is_its_unpadded_run(agent_and_params):
    """Convolution, state, window and cross-attention: pads feed none of them."""
    agent, params = agent_and_params
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, MICRO["vocab_size"], 7).astype(np.int32)
    module = agent.module

    def hidden(tokens, start):
        return module.apply(params, tokens[None], np.array([start]), method=lambda m, t, s: m.backbone(t, s)[0])[0]

    short = hidden(tokens, 0)
    padded = hidden(np.concatenate([np.full(9, 5, np.int32), tokens]), 9)  # the pads hold a real id: they still count for nothing
    assert close(padded[9:], short)


@pytest.mark.parametrize("chunk", [1, 3, 4, 5, 16, 64])
def test_the_chunked_scan_is_the_sequential_scan(chunk):
    """Forward and gradient, at chunk sizes that do and do not divide the 13 positions."""
    rng = np.random.default_rng(chunk)
    B, S, D, N = 2, 13, 8, 4
    x, b, c = (jnp.asarray(rng.normal(size=shape), jnp.float32) for shape in ((B, S, D), (B, S, N), (B, S, N)))
    delta = jnp.asarray(rng.uniform(0.1, 1.0, (B, S, D)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (N, D)), jnp.float32)

    def sequential(x, delta, a, b, c):
        state, ys = jnp.zeros((B, N, D)), []
        for t in range(S):
            state = jnp.exp(delta[:, t, None, :] * a) * state + (delta[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
            ys.append(jnp.einsum("bnd,bn->bd", state, c[:, t]))
        return jnp.stack(ys, axis=1), state

    loss = lambda fn: lambda *args: jnp.sum(jnp.sin(fn(*args)[0])) + jnp.sum(fn(*args)[1])  # noqa: E731
    want, got = sequential(x, delta, a, b, c), H.selective_scan(x, delta, a, b, c, chunk=chunk)
    assert close(got[0], want[0], 1e-5) and close(got[1], want[1], 1e-5)
    want_grads = jax.grad(loss(sequential), argnums=(0, 1, 2, 3, 4))(x, delta, a, b, c)
    got_grads = jax.grad(loss(lambda *args: H.selective_scan(*args, chunk=chunk)), argnums=(0, 1, 2, 3, 4))(x, delta, a, b, c)
    assert all(close(g, w, 1e-4) for g, w in zip(got_grads, want_grads))
    assert H.HybridConfig(**MICRO).scan_chunks(130) == 3 * 3 and H.HybridConfig(**MICRO).scan_chunks(13) == 3


def test_the_window_reads_its_band_only():
    """A key further back than the window moves nothing; the nearest one outside it is the edge."""
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 12, heads, 8)), jnp.float32) for heads in (4, 2, 2))
    start, lam = jnp.zeros((1,), jnp.int32), jnp.float32(0.3)
    out = H.blocked_differential(q, k, v, start, lam, 4)
    moved = H.blocked_differential(q, k.at[:, 3].add(1.0), v.at[:, 3].add(1.0), start, lam, 4)
    changed = np.abs(np.asarray(moved - out)).reshape(12, -1).max(-1) > 0
    assert changed.tolist() == [False] * 3 + [True] * 4 + [False] * 5  # positions 3..6 see key 3
    full = H.blocked_differential(q, k, v, start, lam, None)
    assert close(full[:, :4], out[:, :4]) and not close(full[:, 6:], out[:, 6:])


def test_the_held_layers_are_the_whole_models_layers():
    """`layers_held = (14, 6)` of a 32-layer toy, given a stream entering layer 14: the same six layers' output as
    the whole model's layers 14-19 on the same weights, and the reference's with the same share. (The benchmark's
    weights are drawn leaf by leaf from the leaf's path, so the whole model's layers 14-19 are drawn alone.)"""
    from benchmarks.harness import weights
    from benchmarks.harness.adapters import ppo_lm_hybrid as adapter
    from benchmarks.reference.phi4flash_ppo import Net

    deep = dict(MICRO, num_hidden_layers=32)
    whole = PPOLMAgent(H.HybridConfig(**deep), P, R, jnp.float32, jnp.float32)
    shapes = jax.eval_shape(whole.init_params, jax.random.PRNGKey(0))["params"]
    assert sorted(k for k in shapes["backbone"] if k.startswith("layer_")) == sorted(f"layer_{i}" for i in range(32))
    others = [f"layer_{i}" for i in range(32) if not 14 <= i < 20]
    pruned = dict(shapes, backbone={k: v for k, v in shapes["backbone"].items() if k not in others})
    whole_params = weights.make_weights({"params": pruned}, 7)
    stage, stage_params = make(H.HybridConfig(**deep, layers_held=(14, 6)))
    assert jax.tree_util.tree_structure(stage_params) == jax.tree_util.tree_structure(whole_params)
    rng = np.random.default_rng(9)
    start = np.array([0, 5])
    entering = jnp.asarray(rng.normal(size=(2, 16, MICRO["hidden_size"])), jnp.float32)

    def through(layers):
        def run(module, entering):
            x, carried = entering, (None, None)
            for layer in layers(module.backbone.layers):
                x, _, carried = layer(x, start, carried)
            return x

        return run

    want = jax.jit(lambda params: whole.module.apply(params, entering, method=through(lambda held: held[14:20])))(whole_params)
    got = jax.jit(lambda params: stage.module.apply(params, entering, method=through(lambda held: held)))(stage_params)
    assert [layer_index for layer_index in stage.model.layers] == list(range(14, 20)) and close(got, want, 1e-5)
    net = Net(reference_model((14, 6), depth=32))
    flat = {k: jnp.asarray(v) for k, v in adapter.to_reference(stage_params).items()}

    def plain(p, x, begins):
        memory = shared = None
        for index in net.held:
            x, memory, shared = net.layer(index, p, x, begins, memory, shared)
        return x

    for row in range(2):  # a pad position's stream is nobody's: the real positions are compared
        assert close(jax.jit(plain)(flat, entering[row], start[row])[start[row]:], want[row, start[row]:])


def test_loss_and_gradients_are_the_references(agent_and_params):
    """One PPO step's three losses and its first gradient, leaf by leaf, program against plain reference."""
    import optax

    from benchmarks.harness.adapters import ppo_lm_hybrid as adapter
    from benchmarks.reference.phi4flash_ppo import Reference
    from sheeprl_tpu.algos.ppo_lm.ppo_lm import make_train_step
    from sheeprl_tpu.utils.utils import dotdict

    agent, params = agent_and_params
    rng = np.random.default_rng(13)
    lengths = [10, 4, 7]
    batch = {
        "tokens": np.stack([np.concatenate([left_padded(rng, n), rng.integers(0, 48, R)]) for n in lengths]).astype(np.int32),
        "start": np.array([P - n for n in lengths], np.int32),
        "logprobs": rng.normal(-3.5, 0.3, (3, R)).astype(np.float32),
        "values": rng.normal(size=(3, R)).astype(np.float32),
        "advantages": rng.normal(size=(3, R)).astype(np.float32),
        "returns": rng.normal(size=(3, R)).astype(np.float32),
        "mask": (np.arange(R)[None, :] < np.array([6, 3, 5])[:, None]).astype(np.float32),
    }
    ref = Reference(reference_model())
    flat = {k: jnp.asarray(v) for k, v in adapter.to_reference(params).items()}
    want_grads, want_losses = ref.gradient(flat, batch)
    tx = optax.sgd(1.0)  # the update is the gradient: params before - params after
    step = make_train_step(agent, tx, dotdict({"algo": {"vf_coef": 0.1}}))
    copy = jax.tree_util.tree_map(jnp.array, params)
    new, _, metrics, _ = step(copy, tx.init(params), batch, np.float32(0.2), np.float32(0.01))
    assert float(metrics["policy_loss"]) == pytest.approx(float(want_losses["policy"]), rel=1e-4)
    assert float(metrics["value_loss"]) == pytest.approx(float(want_losses["value"]), rel=1e-4)
    assert float(metrics["entropy_loss"]) == pytest.approx(float(want_losses["entropy"]), rel=1e-4)
    assert float(metrics["ssm/scan_chunks"]) == 3.0  # 16 positions in one chunk, three Mamba layers
    got = adapter.to_reference(jax.tree_util.tree_map(lambda a, b: a - b, params, new))
    assert sorted(got) == sorted(want_grads)
    scale = max(float(np.abs(np.asarray(g)).max()) for g in want_grads.values())
    for name, want in want_grads.items():
        assert np.abs(np.asarray(got[name]) - np.asarray(want)).max() <= 2e-4 * max(np.abs(np.asarray(want)).max(), 1e-3 * scale), name
    assert {name.split("/")[0] for name in want_grads} == set(adapter.GROUPS)


def test_the_acting_copy_keeps_the_float32_parts(agent_and_params):
    agent, params = agent_and_params
    acting = PPOLMAgent(agent.model, P, R, jnp.bfloat16, jnp.float32).acting_params(params)
    leaves = {"/".join(str(getattr(p, "key", p)) for p in path): leaf.dtype for path, leaf in jax.tree_util.tree_flatten_with_path(acting)[0]}
    assert leaves["params/backbone/layer_0/ssm/A_log"] == jnp.float32 and leaves["params/backbone/layer_0/ssm/b_dt"] == jnp.float32
    assert leaves["params/backbone/layer_0/ssm/w_in"] == jnp.bfloat16 and leaves["params/backbone/embedding"] == jnp.bfloat16
    assert leaves["params/value_head"] == jnp.float32 and leaves["params/backbone/layer_5/full/lq1"] == jnp.float32
    assert "params/head" not in leaves  # the head is the embedding
    assert isinstance(agent.module, LMPolicy) and agent.cache_bytes(2) == {
        "window": 2 * 2 * 2 * 4 * 2 * 8 * 4, "full": 2 * 2 * (P + R) * 2 * 8 * 4, "state": 3 * 2 * (3 * 64 + 4 * 64) * 4}


def test_the_bf16_mixed_player_is_near_the_float32_one(agent_and_params):
    """The acting copy in bfloat16 through prefill and two decode steps: same tokens fed, logits within bfloat16's reach."""
    agent, params = agent_and_params
    mixed = PPOLMAgent(agent.model, P, R, jnp.bfloat16, jnp.float32)
    rng = np.random.default_rng(4)
    prompts, lengths = np.stack([left_padded(rng, 9), left_padded(rng, 5)]), np.array([9, 5])
    fed = rng.integers(0, MICRO["vocab_size"], (2, 2)).astype(np.int32)
    got = []
    for player, weights in ((agent, params), (mixed, mixed.acting_params(params))):
        state, key, logits = player.init_state(2), jax.random.PRNGKey(0), []
        _, state, key = jax.jit(player.prefill)(weights, state, prompts, lengths, np.ones(2, bool), key)
        logits.append(np.asarray(state["logits"]))
        for t in range(2):
            _, state, key = jax.jit(player.decode)(weights, state, fed[t], key)
            logits.append(np.asarray(state["logits"]))
        got.append(np.stack(logits))
        assert state["ssm"][0].dtype == jnp.float32 and state["win_k"][0].dtype == player.dtype
    assert 1e-4 < np.abs(got[1] - got[0]).max() < 0.1 * np.abs(got[0]).max()

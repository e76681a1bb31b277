"""The deepseek_v3 decoder blocks (models/transformer.py) at a micro size on
the CPU, float32: the two forms of latent attention against each other and
against the benchmark's plain reference, the expert layer's share of an
expert-parallel deployment, and the router's rules."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from sheeprl_tpu.algos.ppo_lm.agent import PPOLMAgent  # noqa: E402
from sheeprl_tpu.models import transformer as T  # noqa: E402

MICRO = dict(vocab_size=48, hidden_size=32, num_hidden_layers=3, num_attention_heads=2, qk_nope_head_dim=8,
             qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16, intermediate_size=64, moe_intermediate_size=16,
             n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=6, routed_scaling_factor=2.448,
             rope_theta=1e6)
P, R = 6, 6


def reference_model(held):
    return dict(MICRO, experts_held=list(held), first_k_dense_replace=1, norm_topk_prob=True, rms_norm_eps=1e-6,
                prompt_len=P, rollout_steps=R, clip_coef=0.2, vf_coef=0.1, ent_coef=0.0)


@pytest.fixture(scope="module")
def agent_and_params():
    from benchmarks.harness import weights

    agent = PPOLMAgent(T.TransformerConfig(**MICRO, experts_held=(4, 8)), P, R, jnp.float32, jnp.float32)
    shapes = jax.eval_shape(agent.init_params, jax.random.PRNGKey(0))
    return agent, weights.make_weights(shapes, 7)  # no leaf zero: the selection bias takes part


def left_padded(rng, length):
    prompt = np.zeros((P,), np.int32)
    prompt[P - length:] = rng.integers(0, MICRO["vocab_size"], length)
    return prompt


def test_decode_through_the_cache_is_the_whole_sequence_form_and_the_reference(agent_and_params):
    """Two envs side by side, the second reset (a new prompt, prefill) while the
    first keeps decoding: every logit the player produced through prefill and
    the latent cache is the whole-sequence form's and the reference's full
    forward pass's on the same tokens."""
    from benchmarks.harness.adapters import ppo_lm as adapter
    from benchmarks.reference.deepseek_v3_ppo import Reference

    agent, params = agent_and_params
    rng = np.random.default_rng(3)
    lens = {"a": 5, "b": 3, "c": 4}
    prompts = {k: left_padded(rng, n) for k, n in lens.items()}
    fed = {k: rng.integers(0, MICRO["vocab_size"], R).astype(np.int32) for k in lens}  # the response tokens, given
    prefill, decode = jax.jit(agent.prefill), jax.jit(agent.decode)
    key = jax.random.PRNGKey(0)
    got = {k: [] for k in lens}

    def keep(state, names):
        for row, name in enumerate(names):
            if name:
                got[name].append(np.asarray(state["logits"][row]))

    state = agent.init_state(2)
    _, state, key = prefill(params, state, np.stack([prompts["a"], prompts["b"]]), np.array([5, 3]), np.array([True, True]), key)
    keep(state, "ab")
    for t in range(2):
        _, state, key = decode(params, state, np.array([fed["a"][t], fed["b"][t]]), key)
        keep(state, "ab")
    # env 1 starts over from prompt c; env 0's rows, positions and logits stay
    before = np.asarray(state["logits"][0])
    _, state, key = prefill(params, state, np.stack([prompts["a"], prompts["c"]]), np.array([5, 4]), np.array([False, True]), key)
    assert np.array_equal(np.asarray(state["logits"][0]), before) and int(state["pos"][0]) == P + 2 and int(state["pos"][1]) == P
    keep(state, [None, "c"])
    for t in range(2):
        _, state, key = decode(params, state, np.array([fed["a"][2 + t], fed["c"][t]]), key)
        keep(state, "ac")

    ref = Reference(reference_model((4, 8)))
    weights = {k: jnp.asarray(v) for k, v in adapter.to_reference(params).items()}
    with jax.default_matmul_precision("highest"):
        for name, steps in (("a", 5), ("b", 3), ("c", 3)):
            tokens = np.concatenate([prompts[name], fed[name]])
            start = np.array([P - lens[name]])
            whole = np.asarray(agent.evaluate(params, tokens[None], start)[0][0])
            plain = ref.logits(weights, tokens, start[0])
            assert len(got[name]) == steps
            for t, logits in enumerate(got[name]):
                assert np.abs(logits - whole[t]).max() < 1e-4 * np.abs(whole[t]).max(), (name, t)
                assert np.abs(logits - plain[t]).max() < 1e-4 * np.abs(plain[t]).max(), (name, t)


def test_prefill_takes_the_prompts_at_once_only_where_the_kernels_run(agent_and_params, monkeypatch):
    """Eight prompts go four at a time where the plain path runs (its float32
    score block grows with the rows that share it: a loop in what is traced)
    and all at once where the rule says the kernels take them; logits, values
    and cache rows are the same either way."""
    from sheeprl_tpu.algos.ppo_lm import agent as agent_module

    agent, params = agent_and_params
    rng = np.random.default_rng(11)
    lengths = rng.integers(2, P + 1, 8)
    prompts = np.stack([left_padded(rng, n) for n in lengths])

    def prefill():
        return agent.prefill(params, agent.init_state(8), prompts, lengths, np.ones(8, bool), jax.random.PRNGKey(0), greedy=True)

    assert "scan[" in str(jax.make_jaxpr(lambda: prefill())())  # a fresh function each time: traces are cached by it
    (_, _, values), state, _ = prefill()
    # the agent's side of the rule alone: the layer itself still takes the plain path on the CPU
    monkeypatch.setattr(agent_module, "attention_is_fused", lambda *a: True)
    assert "scan[" not in str(jax.make_jaxpr(lambda: prefill())())
    (_, _, values_at_once), state_at_once, _ = prefill()
    for got, want in zip(jax.tree_util.tree_leaves((values_at_once, state_at_once)), jax.tree_util.tree_leaves((values, state))):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-5 * max(np.abs(np.asarray(want)).max(), 1.0)


def test_the_eight_shares_add_up_to_the_uncut_layer(agent_and_params):
    """Section 4's share test: the routed parts that the eight shares of an
    expert layer give, with the shared experts (which every chip computes
    alike) counted once, add up to what the uncut reference gives for the
    whole layer: each share routes over all 16 experts and holds 2."""
    from benchmarks.reference.deepseek_v3_ppo import Net

    rng = np.random.default_rng(5)
    cfg = T.TransformerConfig(**MICRO)  # all 16 held: the shapes of the whole layer
    whole = T.MoE(cfg)
    x = jnp.asarray(rng.normal(size=(2, 9, MICRO["hidden_size"])), jnp.float32)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.3, jnp.float32), params)
    params["norm"]["scale"] = jnp.ones_like(params["norm"]["scale"])
    normed = T.RMSNorm().apply({"params": params["norm"]}, x)
    shared = T.SwiGLU(cfg, 2 * MICRO["moe_intermediate_size"]).apply({"params": params["shared"]}, normed)

    total, slots = shared, 0
    for share in range(8):
        first = 2 * share
        held = T.MoE(T.TransformerConfig(**MICRO, experts_held=(first, 2)))
        mine = dict(params, **{k: params[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")})
        out, stats = held.apply({"params": mine}, x)
        total = total + (out - shared)
        slots += int(stats["held_slots"])
        assert int(stats["routed_slots"]) == 2 * 9 * 6
    assert slots == 2 * 9 * 6  # every (token, choice) slot is some share's: none dropped, none twice

    flat = {"router/l1/norm": params["norm"]["scale"], "router/l1/w": params["router"], "router/l1/bias": params["router_bias"]}
    flat.update({f"experts/l1/{k}": params[k] for k in ("w_gate", "w_up", "w_down")})
    flat.update({f"shared/l1/{k}": v for k, v in params["shared"].items()})
    with jax.default_matmul_precision("highest"):
        uncut = np.stack([np.asarray(Net(reference_model((0, 16))).experts(flat, 1, x[b])[0]) for b in range(2)])
    assert np.abs(np.asarray(total) - uncut).max() < 1e-4 * np.abs(uncut).max()


def test_padding_is_sent_to_no_expert():
    """Positions before a row's ``start`` belong to no context: they are routed
    (the selection is reported) and reach no expert, so the load counted is the
    real tokens', and what a real position gets does not depend on them."""
    rng = np.random.default_rng(23)
    cfg = T.TransformerConfig(**MICRO, experts_held=(0, 8))
    layer = T.MoE(cfg)
    x = jnp.asarray(rng.normal(size=(2, 9, MICRO["hidden_size"])), jnp.float32)
    params = layer.init(jax.random.PRNGKey(3), x)["params"]
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.3, jnp.float32), params)
    real = jnp.arange(9)[None, :] >= jnp.array([4, 0])[:, None]
    out, stats = layer.apply({"params": params}, x, real)
    everything, counted = layer.apply({"params": params}, x)
    assert int(stats["routed_slots"]) == 14 * 6 and int(counted["routed_slots"]) == 18 * 6
    held = (np.asarray(stats["chosen"]) < 8).reshape(2, 9, 6)
    assert int(stats["held_slots"]) == held[np.asarray(real)].sum() == int(stats["expert_tokens"].sum())
    assert int(counted["held_slots"]) == held.sum() > int(stats["held_slots"])
    assert np.array_equal(np.asarray(out)[np.asarray(real)], np.asarray(everything)[np.asarray(real)])
    normed = T.RMSNorm().apply({"params": params["norm"]}, x)
    shared = T.SwiGLU(cfg, 2 * MICRO["moe_intermediate_size"]).apply({"params": params["shared"]}, normed)
    assert np.allclose(np.asarray(out)[~np.asarray(real)], np.asarray(shared)[~np.asarray(real)], atol=1e-6)
    # and the padding gives the experts no gradient
    grads = jax.grad(lambda p, x: layer.apply({"params": p}, x, real)[0][real].sum())(params, x)
    other = jax.grad(lambda p, x: layer.apply({"params": p}, x.at[0, :4].set(7.0), real)[0][real].sum())(params, x)
    assert all(np.allclose(np.asarray(grads[k]), np.asarray(other[k]), atol=1e-5) for k in ("w_gate", "w_up", "w_down"))


def test_the_selection_bias_changes_the_choice_and_never_the_weights():
    rng = np.random.default_rng(11)
    scores = jnp.asarray(rng.uniform(0.05, 0.95, (32, 16)), jnp.float32)
    plain, w_plain = T.route(scores, jnp.zeros(16), 6, True, 2.448)
    bias = jnp.zeros(16).at[3].set(10.0)  # expert 3 now wins a place in every token
    chosen, weights = T.route(scores, bias, 6, True, 2.448)
    assert (np.asarray(chosen) == 3).any(axis=1).all() and not (np.asarray(plain) == 3).any(axis=1).all()
    # the weights are the selected SCORES, normalised and scaled: the bias is not in them
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), axis=1)
    assert np.allclose(np.asarray(weights), picked / picked.sum(1, keepdims=True) * 2.448, rtol=1e-6)
    assert np.allclose(np.asarray(weights).sum(1), 2.448, rtol=1e-6) and np.allclose(np.asarray(w_plain).sum(1), 2.448, rtol=1e-6)
    # and no gradient reaches the bias
    grad = jax.grad(lambda b: T.route(scores, b, 6, True, 2.448)[1].sum())(bias)
    assert not np.asarray(grad).any()


def test_interleaved_rope_is_the_paired_rotation():
    """`rope_interleave`: channels (2i, 2i+1) are a pair. De-interleaving to
    halves and rotating the halves (this module) gives every dot product that
    rotating the pairs in place gives, and differs from rotate-half on the
    raw layout, which pairs (i, i + d/2)."""
    rng = np.random.default_rng(13)
    q, k = (jnp.asarray(rng.normal(size=(7, 8)), jnp.float32) for _ in range(2))
    positions = jnp.arange(7) * 37
    cos, sin = T.rope_tables(positions, 8, 1e6)

    def in_place(x):  # the pair (2i, 2i+1) as a complex number times e^{i angle}
        z = (x[:, 0::2] + 1j * x[:, 1::2]) * (cos + 1j * sin)
        return jnp.stack([z.real, z.imag], axis=-1).reshape(x.shape)

    def half_split(x):  # rotate-half with no de-interleave: pairs (i, i + d/2)
        a, b = x[:, :4], x[:, 4:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)

    ours = T.apply_rope_interleaved(q, cos, sin) @ T.apply_rope_interleaved(k, cos, sin).T
    assert np.allclose(ours, in_place(q) @ in_place(k).T, atol=1e-5)
    assert not np.allclose(ours, half_split(q) @ half_split(k).T, atol=1e-3)
    # the de-interleaved layout itself: evens first, then odds, then rotate-half
    deinterleaved = jnp.concatenate([q[:, 0::2], q[:, 1::2]], axis=-1)
    assert np.allclose(T.apply_rope_interleaved(q, cos, sin), half_split(deinterleaved), atol=1e-6)


def test_no_token_is_dropped_when_every_token_chooses_one_expert():
    """A router that sends every token to the same held experts (eight times an
    even router's share) computes them all: the layer equals the dense sum
    over those experts."""
    rng = np.random.default_rng(17)
    sizes = dict(MICRO, num_experts_per_tok=2)
    cfg = T.TransformerConfig(**sizes, experts_held=(0, 2))
    layer = T.MoE(cfg)
    x = jnp.asarray(rng.normal(size=(1, 600, MICRO["hidden_size"])), jnp.float32)  # 1200 slots, an even share is 150
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.3, jnp.float32), params)
    params["norm"]["scale"] = jnp.ones_like(params["norm"]["scale"])
    params["router_bias"] = jnp.zeros(16).at[jnp.array([0, 1])].set(100.0)
    out, stats = layer.apply({"params": params}, x)
    assert int(stats["held_slots"]) == int(stats["routed_slots"]) == 1200 and list(np.asarray(stats["expert_tokens"])) == [600, 600]

    normed = T.RMSNorm().apply({"params": params["norm"]}, x)[0]
    scores = jax.nn.sigmoid(normed @ params["router"])[:, :2]
    weights = scores / scores.sum(1, keepdims=True) * 2.448
    dense = sum(weights[:, e:e + 1] * ((jax.nn.silu(normed @ params["w_gate"][e]) * (normed @ params["w_up"][e])) @ params["w_down"][e])
                for e in range(2))
    dense = dense + T.SwiGLU(cfg, 32).apply({"params": params["shared"]}, normed)
    assert np.abs(np.asarray(out[0]) - np.asarray(dense)).max() < 1e-4 * np.abs(np.asarray(dense)).max()
    # and the gradient reaches every token through the experts (dispatch and combine are each other's transposes)
    grad = jax.grad(lambda v: layer.apply({"params": params}, v)[0].sum())(x)
    assert np.isfinite(np.asarray(grad)).all() and (np.abs(np.asarray(grad)).sum(-1) > 0).all()


def test_the_expert_layer_is_told_what_it_holds():
    with pytest.raises(ValueError, match="does not lie inside"):
        T.TransformerConfig.from_config(dict(MICRO, experts_held=[12, 8]))
    assert T.TransformerConfig.from_config(dict(MICRO, experts_held=None)).experts_held == (0, 16)
    assert T.TransformerConfig.from_config(dict(MICRO, experts_held=[4, 8])).experts_held == (4, 8)

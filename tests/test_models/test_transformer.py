"""The deepseek_v3 decoder blocks (models/transformer.py) at a micro size on
the CPU, float32: the two forms of latent attention against each other and
against the benchmark's plain reference, the expert layer's share of an
expert-parallel deployment, and the router's rules."""

import os
import sys
from functools import partial

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
    agent, params = agent_and_params
    rng = np.random.default_rng(11)
    lengths = rng.integers(2, P + 1, 8)
    prompts = np.stack([left_padded(rng, n) for n in lengths])

    def prefill():
        return agent.prefill(params, agent.init_state(8), prompts, lengths, np.ones(8, bool), jax.random.PRNGKey(0), greedy=True)

    assert "scan[" in str(jax.make_jaxpr(lambda: prefill())())  # a fresh function each time: traces are cached by it
    (_, _, values), state, _ = prefill()
    # the prefill's side of the rule alone: the layer itself still takes the plain path on the CPU
    monkeypatch.setattr(T.Transformer, "prefill_rows", lambda self, num_envs, prompt_len: None)
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


def every_slot_moe(cfg, params, x, real=None, dtype=jnp.float32):
    """The expert layer as it stood before the chunks, in plain JAX, kept as
    the reference: every routed slot is dispatched in sorted order (the rows of
    the others zero), goes through the three grouped products, and a token
    gathers the rows its held slots stand at, one gather a choice."""
    first, count = cfg.experts_held
    k = cfg.num_experts_per_tok
    xn = T.RMSNorm(cfg.rms_norm_eps).apply({"params": params["norm"]}, x).reshape(-1, x.shape[-1])
    logits = jnp.dot(xn.astype(jnp.float32), params["router"], precision=jax.lax.Precision.HIGHEST)
    chosen, weights = T.route(jax.nn.sigmoid(logits), params["router_bias"], k, cfg.norm_topk_prob, cfg.routed_scaling_factor)
    local = chosen - first
    held = (local >= 0) & (local < count)
    if real is not None:
        held = held & real.reshape(-1, 1)
    group = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(group, stable=True)
    rank = jnp.argsort(order).reshape(held.shape)  # where each slot stands in the sorted order
    sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :], axis=0).astype(jnp.int32)
    live = jnp.arange(order.shape[0]) < jnp.sum(sizes)
    # gathers on float32 copies: their transposes then sum a token's rows in float32 and round once, as the forward sum does
    xs = jnp.where(live[:, None], xn.astype(jnp.float32)[order // k], 0).astype(dtype)
    grouped = partial(jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=dtype)
    ys = grouped(jax.nn.silu(grouped(xs, params["w_gate"].astype(dtype))) * grouped(xs, params["w_up"].astype(dtype)),
                 params["w_down"].astype(dtype))
    slot_weight = jnp.where(live, weights.reshape(-1)[order], 0.0)
    ys = jnp.where(live[:, None], ys.astype(jnp.float32) * slot_weight[:, None], 0).astype(dtype)
    routed = jnp.sum(jnp.where(held[..., None], ys.astype(jnp.float32)[jnp.where(held, rank, 0)], 0), axis=1).astype(dtype)
    shared = T.SwiGLU(cfg, cfg.moe_intermediate_size * cfg.n_shared_experts, dtype).apply({"params": params["shared"]}, xn)
    return (routed + shared).reshape(x.shape), {"held_slots": jnp.sum(sizes), "expert_tokens": sizes}


WIDE = dict(MICRO, n_routed_experts=128)  # 16 of 128 held, as the token cell's chip holds them
#: name -> (sizes, experts_held, shape of x less its width, first real index of each row or None, experts the bias forces or None)
CHUNK_CASES = {
    "few_held": (WIDE, (32, 16), (2, 300), None, None),              # 3600 slots in chunks of 1024: one runs
    "all_held": (MICRO, None, (2, 50), None, None),                  # one chunk is all 600 slots
    "left_padding": (WIDE, (0, 16), (2, 300), (180, 0), None),       # `real` keeps the padding from every expert
    "decode_sized": (WIDE, (0, 16), (16,), None, None),              # 96 slots: one chunk
    "overflow_forced": (MICRO, (2, 4), (2, 300), None, (2, 3, 4, 5)),  # 2400 held slots against chunks of 2048: a second runs
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(CHUNK_CASES))
def test_the_chunked_expert_layer_is_the_every_slot_layer(case, dtype):
    """Value, the gradient of every leaf and of ``x``, and the slots counted:
    the bounded chunks of sorted slots (one body, later chunks only while held
    slots remain) give what dispatch and combine over every slot gave. In
    bfloat16 both round at the same places; a token's rows are summed in
    float32 in another order, which can move the rounded sum by one place."""
    sizes, held, lead, starts, forced = CHUNK_CASES[case]
    cfg = T.TransformerConfig(**sizes, experts_held=held)
    layer = T.MoE(cfg, dtype, jnp.float32)
    rng = np.random.default_rng(29)
    x = jnp.asarray(rng.normal(size=(*lead, sizes["hidden_size"])), dtype)
    params = layer.init(jax.random.PRNGKey(5), x)["params"]
    params = jax.tree_util.tree_map(lambda p: jnp.asarray(rng.normal(size=p.shape) * 0.3, jnp.float32), params)
    if forced is not None:  # every token's choices begin with these experts, all held here
        params["router_bias"] = jnp.zeros(sizes["n_routed_experts"]).at[jnp.array(forced)].set(100.0)
    real = None if starts is None else jnp.arange(lead[1])[None, :] >= jnp.asarray(starts)[:, None]
    weight = jnp.asarray(rng.normal(size=x.shape), jnp.float32)

    def through(forward):
        def loss(p, x):
            out, stats = forward(p, x)
            return jnp.sum(out.astype(jnp.float32) * weight), (out, stats)
        (_, (out, stats)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)
        return out, stats, grads

    out, stats, grads = through(lambda p, x: layer.apply({"params": p}, x, real))
    want, counted, want_grads = through(lambda p, x: every_slot_moe(cfg, p, x, real, dtype))

    slots = int(np.prod(lead)) * cfg.num_experts_per_tok
    rows = T.expert_chunk_rows(slots, cfg.experts_held[1], cfg.n_routed_experts)
    assert int(stats["held_slots"]) == int(counted["held_slots"]) and np.array_equal(stats["expert_tokens"], counted["expert_tokens"])
    assert int(stats["overflow_chunks"]) == max(-(-int(stats["held_slots"]) // rows) - 1, 0)
    if case == "overflow_forced":  # nothing dropped: every token's four forced choices are held and computed
        assert int(stats["held_slots"]) == 4 * 600 > rows and int(stats["overflow_chunks"]) == 1
    else:
        assert int(stats["overflow_chunks"]) == 0 and (rows < slots) == (case in ("few_held", "left_padding"))
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7

    def close(got, ref):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        return np.abs(got - ref).max() <= tol * max(np.abs(ref).max(), 1e-6)

    assert close(out, want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, got), ref in zip(flat, jax.tree_util.tree_leaves(want_grads)):
        assert got.shape == ref.shape and got.dtype == ref.dtype and close(got, ref), jax.tree_util.keystr(path)
    assert np.abs(np.asarray(grads[0]["w_gate"])).max() > 0 and np.abs(np.asarray(grads[1], np.float32)).max() > 0


@pytest.mark.parametrize("slots, held, routed, rows", [
    (4 * 2080 * 6, 16, 128, 12800),    # the token cell's gradient step: about a quarter of its 49 920 slots
    (16 * 2048 * 6, 16, 128, 49152),   # its prefill: a quarter of 196 608
    (16 * 6, 16, 128, 96),             # its decode step: one chunk, all 96 slots
    (8320 * 6, 128, 128, 49920),       # every expert held: one chunk, the whole layer
    (8320 * 6, 64, 128, 49920),        # half of them held: twice an even share is everything
    (600 * 6, 4, 16, 2048),            # whole tiles of 512 rows
])
def test_the_chunk_of_sorted_slots_follows_from_the_shape_and_the_experts_held(slots, held, routed, rows):
    assert T.expert_chunk_rows(slots, held, routed) == rows


def test_one_expert_body_serves_every_chunk():
    """The chunk that always runs and the chunks an overflow needs are one
    traced body: a forward pass holds the three grouped products once (in a
    loop over chunks whose later trips are skipped), the rematerialised
    gradient twelve times (the forward, the chunk made again, two transposes
    of each product), whatever the number of chunks."""
    import re

    cfg = T.TransformerConfig(**WIDE, experts_held=(0, 16))
    layer = T.MoE(cfg)
    x = jnp.zeros((2, 300, MICRO["hidden_size"]))
    assert -(-x.shape[0] * x.shape[1] * 6 // T.expert_chunk_rows(3600, 16, 128)) == 4
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    products = lambda fn: len(re.findall(r"= ragged_dot\w*\[", str(jax.make_jaxpr(fn)(params, x))))  # noqa: E731
    forward = lambda p, x: layer.apply({"params": p}, x)[0]  # noqa: E731
    assert products(forward) == 3
    assert products(jax.grad(lambda p, x: jax.checkpoint(forward)(p, x).sum(), argnums=(0, 1))) == 12
    text = str(jax.make_jaxpr(forward)(params, x))
    assert text.count("while[") == 1 and text.count("scatter-add[") == 1 and "cond[" not in text


def test_the_expert_layer_is_told_what_it_holds():
    with pytest.raises(ValueError, match="does not lie inside"):
        T.TransformerConfig.from_config(dict(MICRO, experts_held=[12, 8]))
    assert T.TransformerConfig.from_config(dict(MICRO, experts_held=None)).experts_held == (0, 16)
    assert T.TransformerConfig.from_config(dict(MICRO, experts_held=[4, 8])).experts_held == (4, 8)

"""The dynamics-learning scan of DreamerV3 contracts its Dense kernels' gradients
after the backward scan (models/deferred_wgrad.py): the gradients are those of a
plain `lax.scan`, no backward scan carries a kernel-shaped array, and the gauges
say how many kernels were taken (CPU, micro widths)."""

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

import sheeprl_tpu
from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as dv3
from sheeprl_tpu.models import deferred_wgrad
from sheeprl_tpu.models.deferred_wgrad import scan_deferred_wgrad
from sheeprl_tpu.telemetry import tracer as tracer_mod

T, B = 6, 3


def _plain_scan(step, variables, carry0, xs, **_):
    return jax.lax.scan(lambda c, x: step(variables, c, x), carry0, xs)


def _micro(decoupled: bool, precision: str = "32-true"):
    """(agent, cfg, world-model parameters, loss arguments) of a micro agent."""
    from sheeprl_tpu.config.loader import compose
    from sheeprl_tpu.core import Runtime

    sheeprl_tpu.register_all()
    cfg = compose("config", [
        "exp=dreamer_v3", "env=dummy", "metric.log_level=0", "env.num_envs=1", "env.screen_size=64",
        "algo.dense_units=8", "algo.mlp_layers=1", f"algo.per_rank_batch_size={B}",
        "algo.world_model.encoder.cnn_channels_multiplier=2",
        "algo.world_model.recurrent_model.recurrent_state_size=8",
        "algo.world_model.recurrent_model.dense_units=6",
        "algo.world_model.representation_model.hidden_size=12",
        "algo.world_model.transition_model.hidden_size=10",
        "algo.world_model.stochastic_size=4", "algo.world_model.discrete_size=4",
        f"algo.world_model.decoupled_rssm={decoupled}",
        "algo.horizon=3", f"algo.per_rank_sequence_length={T}",
        "algo.cnn_keys.encoder=[rgb]", "algo.mlp_keys.encoder=[state]",
        "algo.cnn_keys.decoder=[rgb]", "algo.mlp_keys.decoder=[state]",
        "fabric.accelerator=cpu", "fabric.devices=1", f"fabric.precision={precision}",
    ])
    cfg.env.frame_stack = -1
    runtime = Runtime(devices=1, accelerator="cpu", precision=precision).launch()
    runtime.seed_everything(5)
    obs_space = gym.spaces.Dict({
        "rgb": gym.spaces.Box(0, 255, (64, 64, 3), np.uint8),
        "state": gym.spaces.Box(-1, 1, (5,), np.float32),
    })
    agent, state = dv3.build_agent(runtime, (3,), False, cfg, obs_space)
    # A zero initial state and a zero output kernel would leave whole paths of
    # the backward pass multiplied by zero: move every leaf off its initialiser.
    leaves, treedef = jax.tree_util.tree_flatten(state["world_model"])
    keys = jax.random.split(jax.random.PRNGKey(3), len(leaves))
    wm_params = jax.tree_util.tree_unflatten(
        treedef, [leaf + 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype) for leaf, k in zip(leaves, keys)]
    )
    rng = np.random.default_rng(1)
    is_first = np.zeros((T, B, 1), np.float32)
    is_first[3, 1] = is_first[4, 0] = 1.0  # episodes that begin in mid-sequence
    data = {
        "actions": np.eye(3, dtype=np.float32)[rng.integers(0, 3, (T, B))],
        "rewards": rng.normal(size=(T, B, 1)).astype(np.float32),
        "terminated": (rng.random((T, B, 1)) < 0.2).astype(np.float32),
        "is_first": is_first,
    }
    batch_obs = {
        "rgb": rng.random((T, B, 64, 64, 3)).astype(np.float32) - 0.5,
        "state": rng.normal(size=(T, B, 5)).astype(np.float32),
    }
    keys = jax.random.split(jax.random.PRNGKey(9), T + 1)
    return agent, cfg, wm_params, jax.tree_util.tree_map(jnp.asarray, (data, batch_obs, keys))


def _wm_grads(monkeypatch, decoupled: bool, precision: str, plain: bool):
    agent, cfg, wm_params, args = _micro(decoupled, precision)
    with monkeypatch.context() as patch:
        if plain:
            patch.setattr(dv3, "scan_deferred_wgrad", _plain_scan)
        loss = dv3.make_world_loss_fn(agent, cfg)
        grads = jax.jit(jax.grad(lambda p: loss(p, *args)[0]))(wm_params)
    return {jax.tree_util.keystr(k): np.asarray(g, np.float32) for k, g in jax.tree_util.tree_leaves_with_path(grads)}


def _gaps(got, want):
    """Per leaf, the largest difference relative to the wanted leaf's largest element."""
    assert got.keys() == want.keys()
    return {k: float(np.abs(got[k] - want[k]).max() / max(np.abs(want[k]).max(), 1e-30)) for k in want}


@pytest.mark.parametrize(
    "decoupled,fused_gru", [(False, False), (True, False), (False, True)], ids=["coupled", "decoupled", "fused_gru"]
)
def test_gradients_are_the_plain_scans_in_float32(monkeypatch, decoupled, fused_gru):
    if fused_gru:  # the Pallas cell is no nn.Dense: its kernel stays with the scan, the others are deferred
        monkeypatch.setenv("SHEEPRL_TPU_FUSED_GRU", "1")
    want = _wm_grads(monkeypatch, decoupled, "32-true", plain=True)
    got = _wm_grads(monkeypatch, decoupled, "32-true", plain=False)
    # every leaf has a gradient here (the reset path through `is_first` included)
    assert all(np.abs(g).max() > 0 for g in want.values())
    gaps = _gaps(got, want)
    assert max(gaps.values()) <= 1e-5, sorted(gaps.items(), key=lambda kv: -kv[1])[:5]


@pytest.mark.parametrize("decoupled", [False, True], ids=["coupled", "decoupled"])
def test_gradients_in_bf16_mixed_are_as_near_float32_as_the_plain_scans(monkeypatch, decoupled):
    exact = _wm_grads(monkeypatch, decoupled, "32-true", plain=True)
    plain = _gaps(_wm_grads(monkeypatch, decoupled, "bf16-mixed", plain=True), exact)
    got = _gaps(_wm_grads(monkeypatch, decoupled, "bf16-mixed", plain=False), exact)
    # The tolerance is the plain path's own distance from float32: the deferred
    # contraction rounds once where the plain scan rounds T times, so no leaf
    # may be farther from float32 than the plain path's worst leaf.
    assert max(got.values()) <= 1.5 * max(plain.values()), (max(got.values()), max(plain.values()))


def _scans(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _scans(inner, found)
    return found


def _carry_shapes(fn, *args):
    shapes = set()
    for eqn in _scans(jax.make_jaxpr(fn)(*args).jaxpr, []):
        first = eqn.params["num_consts"]
        shapes.update(v.aval.shape for v in eqn.invars[first : first + eqn.params["num_carry"]])
    return shapes


@pytest.mark.parametrize("decoupled,n_kernels", [(False, 6), (True, 4)], ids=["coupled", "decoupled"])
def test_no_backward_scan_carries_a_kernel_and_the_gauges_say_how_many(monkeypatch, decoupled, n_kernels):
    agent, cfg, wm_params, args = _micro(decoupled)
    params = wm_params["params"]
    scanned = [params["recurrent_model"]["mlp"]["dense_0"], params["recurrent_model"]["rnn"]["linear"],
               params["transition_model"]["dense_0"], params["transition_model"]["output"]]
    if not decoupled:
        scanned += [params["representation_model"]["dense_0"], params["representation_model"]["output"]]
    kernels = {m["kernel"].shape for m in scanned}
    assert len(kernels) == n_kernels  # the micro widths give every kernel a shape of its own

    def grad_fn(make_loss):
        loss = make_loss(agent, cfg)
        return lambda p: jax.grad(lambda q: loss(q, *args)[0])(p)

    tracer = tracer_mod.Tracer(enabled=True)
    previous = tracer_mod.set_current(tracer)
    try:
        carried = _carry_shapes(grad_fn(dv3.make_world_loss_fn), wm_params)
    finally:
        tracer_mod.set_current(previous)
    assert not (carried & kernels), carried & kernels
    counters = tracer.counters()
    assert counters["train/deferred_wgrad_leaves"] == n_kernels
    assert counters["train/deferred_wgrad_bytes"] == sum(4 * m["kernel"].size for m in scanned)

    # what this guards against: the plain scan carries every one of them
    monkeypatch.setattr(dv3, "scan_deferred_wgrad", _plain_scan)
    assert kernels <= _carry_shapes(grad_fn(dv3.make_world_loss_fn), wm_params)


class _Cell(nn.Module):
    """A step that applies one Dense twice, one once, and reads a kernel by hand."""

    @nn.compact
    def __call__(self, h, x):
        twice = nn.Dense(5, name="twice")
        h = jnp.tanh(nn.LayerNorm()(twice(jnp.concatenate([h, x], -1)))) + 0.1 * twice(jnp.concatenate([x, h], -1))
        by_hand = self.param("by_hand", nn.initializers.lecun_normal(), (5, 5))
        return h @ by_hand, (h, nn.Dense(3, use_bias=False, name="out")(h))


def _cell_case():
    cell = _Cell()
    xs = jax.random.normal(jax.random.PRNGKey(0), (T, B, 5))
    keys = jax.random.split(jax.random.PRNGKey(1), T)
    h0 = jnp.zeros((B, 5))
    variables = cell.init(jax.random.PRNGKey(2), h0, xs[0])  # `out` reads the h the step returns: ys[0]

    def step(v, h, x):
        x, key = x
        return cell.apply(v, h, x + 0.1 * jax.random.normal(key, x.shape))

    return step, variables, h0, (xs, keys)


def test_helper_sums_a_kernels_calls_and_leaves_other_leaves_to_the_scan():
    step, variables, h0, xs = _cell_case()

    def loss(scan, v, x):
        _, (hs, ys) = scan(step, v, h0, (x, xs[1]))
        return (ys**2).sum() + hs.sum()

    want = jax.grad(loss, argnums=(1, 2))(_plain_scan, variables, xs[0])
    reported = []
    deferred = lambda *a: scan_deferred_wgrad(  # noqa: E731
        *a, given={"params/out/kernel": lambda ys, xs: ys[0]}, report=lambda n, b: reported.append((n, b))
    )
    got = jax.jit(jax.grad(loss, argnums=(1, 2)), static_argnums=0)(deferred, variables, xs[0])
    for w, g in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    assert reported[0] == (2, 4 * (10 * 5 + 5 * 3))  # `by_hand` is no Dense: it stays with the scan
    carried = _carry_shapes(lambda v: jax.grad(loss, argnums=1)(deferred, v, xs[0]), variables)
    assert (5, 5) in carried and (10, 5) not in carried and (5, 3) not in carried
    # undifferentiated, it is the plain scan
    np.testing.assert_allclose(loss(deferred, variables, xs[0]), loss(_plain_scan, variables, xs[0]), rtol=1e-6)


def test_helper_refuses_a_given_input_it_cannot_use():
    step, variables, h0, xs = _cell_case()
    with pytest.raises(ValueError, match="more than once"):
        scan_deferred_wgrad(step, variables, h0, xs, given={"params/twice/kernel": lambda ys, xs: ys[0]})
    with pytest.raises(ValueError, match="no Dense kernel"):
        scan_deferred_wgrad(step, variables, h0, xs, given={"params/by_hand": lambda ys, xs: ys[0]})
    assert [c.leaf for c in deferred_wgrad.dense_calls(step, variables, h0, xs)] == [5, 5, 3]

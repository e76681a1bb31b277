"""Adapter for the DreamerV3 family of the system under test.

From the program it takes only the system itself: the normal entry point
(`sheeprl_tpu.cli.run`) and, observed from outside, its iteration boundary
(`PreemptionGuard.advance`), its jitted train steps (`make_train_step`,
`make_fused_train_step`), its agent builder (whose weights are replaced with
the benchmark's) and its replay ring. Nothing in the program is edited.

What the harness asks of an adapter is listed in `benchmarks/README.md`
("The adapter's protocol"); everything that names this family is here, in
`reference/dreamer_v3.py`, `flops/dreamer_v3.py` and the configuration's file.
"""

from __future__ import annotations

import contextlib
import re
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmarks.harness import weights as weights_mod

# --------------------------------------------------------------- names
#: The parameter groups the comparison reads, {number's suffix: leaf prefix in
#: the reference's naming}: one optimizer each.
GROUPS = {"world_model": "wm/", "actor": "actor/", "critic": "critic/"}
#: The number that `asked_again` gives, and the one that `acting_reference` gives.
MOVED = "moved.world_model"
ACTING = "player.recurrent"
#: {loss.<name>: the program's own name of that loss}
LOSSES = {"world_model": "Loss/world_model_loss", "policy": "Loss/policy_loss", "value": "Loss/value_loss",
          "observation": "Loss/observation_loss", "reward": "Loss/reward_loss", "continue": "Loss/continue_loss",
          "state": "Loss/state_loss", "kl": "State/kl"}

_RENAMES: List[Tuple[str, str]] = [
    (r"^world_model/params/", "wm/"),
    (r"^(actor|critic|target_critic)/params/", r"\1/"),
    (r"/cnn_encoder/model/", "/enc_cnn/"),
    (r"/mlp_encoder/model/", "/enc_mlp/"),
    (r"/cnn_decoder/fc/", "/dec_fc/"),
    (r"/cnn_decoder/model/", "/dec_cnn/"),
    (r"/mlp_decoder/model/", "/dec_mlp/"),
    (r"/mlp_decoder/head_(\d+)/", r"/dec_mlp/head\1/"),
    (r"/recurrent_model/mlp/", "/rec_in/"),
    (r"/recurrent_model/rnn/linear/kernel$", "/gru/w"),
    (r"/recurrent_model/rnn/norm/LayerNorm_0/scale$", "/gru/g"),
    (r"/recurrent_model/rnn/norm/LayerNorm_0/bias$", "/gru/beta"),
    (r"/representation_model/", "/post/"),
    (r"/transition_model/", "/prior/"),
    (r"/reward_model/", "/reward/"),
    (r"/continue_model/", "/cont/"),
    (r"/initial_recurrent_state$", "/h0"),
    (r"^actor/model/", "actor/"),
    (r"/head_(\d+)/", r"/head\1/"),
    (r"/LayerNorm_(\d+)/LayerNorm_0/scale$", r"/l\1/g"),
    (r"/LayerNorm_(\d+)/LayerNorm_0/bias$", r"/l\1/beta"),
    (r"/(?:dense|conv|deconv)_(\d+)/kernel$", r"/l\1/w"),
    (r"/(?:dense|conv|deconv)_(\d+)/bias$", r"/l\1/b"),
    (r"/output/", "/out/"),
    (r"/kernel$", "/w"),
    (r"/bias$", "/b"),
]


def reference_name(path: Tuple[str, ...]) -> str:
    name = "/".join(path)
    for pattern, repl in _RENAMES:
        name = re.sub(pattern, repl, name)
    return name


def to_reference(tree: Any) -> Dict[str, Any]:
    """A tree in the program's layout as the reference's flat dict."""
    return {reference_name(path): leaf for path, leaf in weights_mod.leaf_paths(tree).items()}


# --------------------------------------------------------------- overrides
def overrides(config: Dict[str, Any], traffic: Dict[str, Any], seed: int, run_dir: str, trace: bool) -> List[str]:
    """What `python -m sheeprl_tpu` is given: the recipe, the benchmark's env,
    the traffic mix, and the switches that keep a run from saving anything."""
    env = dict(config.get("env", {}))
    env.update(traffic.get("env", {}))
    out = [f"exp={config['program']['exp']}", "env=dummy", "env.wrapper._target_=benchmarks.envs.pixel_env.PixelEnv"]
    out += [f"+env.wrapper.{k}={_fmt(v)}" for k, v in env.items()]
    out += [f"+env.wrapper.seed={seed}"]
    for source in (config["program"].get("overrides", {}), traffic.get("overrides", {})):
        out += [f"{k}={_fmt(v)}" for k, v in source.items()]
    out += [
        "env.capture_video=False",
        "env.sync_env=True",
        "algo.total_steps=100000000",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
        "buffer.checkpoint=False",
        "buffer.memmap=False",
        "metric.log_level=0",
        f"telemetry.enabled={bool(trace)}",
        "telemetry.warn_on_recompile=False",
        "telemetry.flight.enabled=False",
        f"seed={seed}",
        "fabric.accelerator=auto",
        f"root_dir={run_dir}",
        "run_name=run",
    ]
    return out


def _fmt(value: Any) -> str:
    if isinstance(value, bool):
        return "True" if value else "False"
    if isinstance(value, (list, tuple)):
        return "[" + ",".join(_fmt(v) for v in value) + "]"
    return str(value)


# --------------------------------------------------------------- the traffic and the recipe
def warm_policy_steps(traffic: Dict[str, Any]) -> int:
    """The policy step from which the window may open: the prefill, then as
    many steps again as the shorter of the env's two fixed first episodes, by
    when both have ended and their reset programs are warm."""
    prefill, lengths = int(traffic["overrides"]["algo.learning_starts"]), traffic["env"]["warm_lengths"]
    if sum(lengths) >= prefill + min(lengths):
        raise SystemExit("benchmark: the traffic's warm_lengths do not end inside set-up")
    return prefill + min(lengths)


def gradient_steps_owed(traffic: Dict[str, Any], policy_steps: int) -> float:
    """Gradient steps a window of ``policy_steps`` owes: the recipe's replay ratio times them."""
    return float(traffic["overrides"]["algo.replay_ratio"]) * policy_steps


def recipe_sizes(cfg: Any) -> Dict[str, Any]:
    """The sizes of the composed recipe ``cfg`` under the keys of the
    configuration file's ``model`` (a list: the keys of the file's dict)."""
    wm = cfg.algo.world_model

    def one(*values):  # what the recipe states in several places, once
        if len(set(values)) != 1:
            raise ValueError(f"the recipe disagrees with itself: {values}")
        return values[0]

    return {
        "recurrent": wm.recurrent_model.recurrent_state_size,
        "dense": cfg.algo.dense_units,
        "mlp_layers": cfg.algo.mlp_layers,
        "hidden": one(wm.transition_model.hidden_size, wm.representation_model.hidden_size),
        "cnn_mult": wm.encoder.cnn_channels_multiplier,
        "stoch": wm.stochastic_size,
        "discrete": wm.discrete_size,
        "batch": cfg.algo.per_rank_batch_size,
        "sequence": cfg.algo.per_rank_sequence_length,
        "horizon": cfg.algo.horizon,
        "bins": one(wm.reward_model.bins, cfg.algo.critic.bins),
        "gamma": float(cfg.algo.gamma),
        "lmbda": cfg.algo.lmbda,
        "compute_dtype": {"bf16-mixed": "bfloat16", "32-true": "float32"}[str(cfg.fabric.precision)],
        "optim": {
            name: {"lr": opt.optimizer.lr, "eps": opt.optimizer.eps, "clip": opt.clip_gradients}
            for name, opt in (("world_model", cfg.algo.world_model), ("actor", cfg.algo.actor), ("critic", cfg.algo.critic))
        },
        "mlp_keys": sorted(cfg.algo.mlp_keys.encoder),
    }


# --------------------------------------------------------------- the step's noise
def step_noise(key: Any, model: Dict[str, Any], batch_shape: Tuple[int, int], fused: bool) -> Tuple[Dict[str, Any], Any]:
    """The Gumbel draws one gradient step makes, from the key the jitted step
    was called with, following the step's own key schedule; in the dtype the
    configuration computes in (a bf16 draw is not the rounding of a float32
    draw), returned as float32. Also returns the key the ring sampler gets."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if model["compute_dtype"] == "bfloat16" else jnp.float32
    T, B = batch_shape
    S, D, H, A = model["stoch"], model["discrete"], model["horizon"], int(sum(model["actions"]))
    _, key = jax.random.split(key)
    sample_key = None
    if fused:
        (key,) = jax.random.split(key, 1)
        sample_key, key = jax.random.split(key)
    k_dyn, k_img0, k_img, _ = jax.random.split(key, 4)
    dyn_keys = jax.random.split(k_dyn, T + 1)[:T]

    def gumbel(k, shape):
        return jax.random.gumbel(k, shape, dtype).astype(jnp.float32)

    post = jax.vmap(lambda k: gumbel(jax.random.split(k)[1], (B, S, D)))(dyn_keys)
    img_keys = jax.vmap(jax.random.split)(jax.random.split(k_img, H))  # [H, 2]
    img_prior = jax.vmap(lambda k: gumbel(k, (T * B, S, D)))(img_keys[:, 0])
    head = lambda k: jax.random.split(k, 1)[0]  # noqa: E731  one action head
    actor0 = gumbel(head(k_img0), (T * B, A))
    actor = jax.vmap(lambda k: gumbel(head(k), (T * B, A)))(img_keys[:, 1])
    noise = {"post": post, "img_prior": img_prior, "actor": jnp.concatenate([actor0[None], actor], 0)}
    return noise, sample_key


def ring_sample(rows: Dict[str, np.ndarray], added: int, key: Any, batch: int, length: int) -> Dict[str, Any]:
    """The [length, batch] windows the in-jit ring sampler draws from ``rows``
    (one env, not yet wrapped): uniform starts over the valid range."""
    import jax
    import jax.numpy as jnp

    _, k_start = jax.random.split(key)
    start = jax.random.randint(k_start, (batch,), 0, jnp.full((batch,), max(added - length + 1, 1)))
    idx = np.asarray(start)[None, :] + np.arange(length)[:, None]  # [T, B]
    return {k: jnp.asarray(v[idx]) for k, v in rows.items()}


def flipped_column(seed: int, batch: Dict[str, Any]) -> int:
    """Which column of the [T, B] batch `flipped` alters: drawn from the seed."""
    return int(seed) % int(next(iter(batch.values())).shape[1])


def flipped(batch: Dict[str, Any], column: int) -> Dict[str, Any]:
    """The batch with the frames (every uint8 key) of one column inverted."""
    import jax.numpy as jnp

    return {k: v.at[:, column].set(255 - v[:, column]) if v.dtype == jnp.uint8 else v
            for k, v in ((k, jnp.asarray(v)) for k, v in batch.items())}


# --------------------------------------------------------------- probes
class StepProbe:
    """Stands where the program's jitted train step stands: calls it, counts
    the gradient steps, and keeps what the first three calls were given and
    returned for the comparison with the reference. Of the first call the
    state's shapes and placement, the key and tau are kept too (the batch is
    already on the host), so that the same compiled step can be asked again
    once the window has closed (`Record.sensitivity`)."""

    CAPTURED = 3

    def __init__(self, fn: Callable, fused: bool, record: "Record") -> None:
        self._fn = fn
        self.fused = fused
        self.record = record

    def __getattr__(self, name: str) -> Any:  # lower(), etc., for the program's own accounting
        return getattr(self._fn, name)

    def __call__(self, state, opt_states, moments, data, key, tau):
        import jax

        rec = self.record
        n = rec.calls
        captured = None
        if n < self.CAPTURED:
            captured = {
                "key": np.asarray(key),
                "tau": float(np.asarray(tau).reshape(-1)[0]),
                "fused": self.fused,
                "data": rec.snapshot(data, self.fused),
            }
        if n == 0 and not self.fused:
            like = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)
            rec.first_call = {"step": self.call, "state": like(state), "opt_states": like(opt_states),
                              "moments": like(moments), "data": captured["data"], "key": key, "tau": tau,
                              "data_sharding": jax.tree_util.tree_map(lambda x: x.sharding, data)}
        out = self.call(state, opt_states, moments, data, key, tau)
        rec.calls += 1
        rec.steps += int(np.asarray(tau).size)
        rec.fused_calls += int(self.fused)
        rec.last = out[3]
        if captured is not None:
            rec.mark(f"train call {n + 1} enqueued")
            captured["losses"] = out[3]
            if n == 0:
                captured["mu"] = jax.device_get(first_moments(out[1]))
            if n == self.CAPTURED - 1:
                captured["params"] = jax.device_get(out[0])
            rec.captured.append(captured)
        return out

    def call(self, *args):
        """The program's step, or the broken one a test planted over it."""
        fault = self.record.fault
        return fault(self._fn, *args) if fault else self._fn(*args)


def first_moments(opt_states: Any) -> Dict[str, Any]:
    """Adam's first moment of each optimizer, as trees shaped like the params."""
    import jax

    out = {}
    for name, state in opt_states.items():
        found = [s for s in jax.tree_util.tree_leaves(state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
        out[name] = found[0].mu
    return out


class Record:
    """What the harness learns about one run of the program, from outside."""

    def __init__(self, seed: int, traffic: Dict[str, Any]) -> None:
        self.seed = seed
        self.ring_expected = bool(traffic.get("ring", False))
        self.calls = 0
        self.steps = 0
        self.fused_calls = 0
        self.last: Any = None
        self.captured: List[Dict[str, Any]] = []
        self.rings: List[Any] = []
        self.fault: Optional[Callable] = None  # tests plant a broken step here
        self.marks: List[Tuple[str, float]] = []  # (what, perf_counter) through set-up
        self.player_steps: List[Tuple[Any, Any]] = []  # (state before, state after) of the acting steps before the first update
        self.first_call: Optional[Dict[str, Any]] = None  # what `sensitivity` asks the compiled step again with
        self.device: Any = None

    def mark(self, what: str) -> None:
        self.marks.append((what, time.perf_counter()))

    def snapshot(self, data: Any, fused: bool) -> Dict[str, Any]:
        import jax

        if not fused:
            return jax.device_get(data)
        added = int(np.asarray(data["added"])[0])
        rows = jax.device_get({k: v[:added, 0] for k, v in data["data"].items()})
        return {"rows": rows, "added": added}

    def sync(self) -> None:
        import jax

        if self.last is not None:
            jax.block_until_ready(self.last)

    def release(self) -> None:
        self.last = None
        self.rings.clear()
        self.player_steps.clear()

    def sensitivity(self) -> Optional[Dict[str, Any]]:
        """Once the window has closed and the program's state is gone: the
        compiled step the window drove, asked twice more for its first step on
        the benchmark's weights, with the first batch as it was and with one
        column's frames inverted (`flipped`). Returns how the world model's
        first gradient moved between the two, in the reference's naming; a
        step that leaves part of its batch out moves it by nothing or by too
        much. None where the step samples its batch itself (the ring)."""
        import jax
        import jax.numpy as jnp

        first, self.first_call = self.first_call, None
        if first is None:
            return None
        kept = first["state"], first["opt_states"], first["moments"]

        def fresh(key):  # what the first call was given: the benchmark's weights, every optimizer at nought
            state = weights_mod.draw(first["state"], key)
            state["target_critic"] = state["critic"]
            zeros = lambda like: jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), like)  # noqa: E731
            return state, zeros(first["opt_states"]), zeros(first["moments"])

        fresh = jax.jit(fresh, out_shardings=jax.tree_util.tree_map(lambda s: s.sharding, kept))
        batch = jax.device_put(first["data"], first["data_sharding"])
        grads = []
        with jax.default_device(self.device):  # as the program ran: the compiled step is found again, not traced anew
            for data in (batch, flipped(batch, flipped_column(self.seed, batch))):
                out = first["step"](*fresh(weights_mod.seed_key(self.seed)), data, first["key"], first["tau"])
                grads.append(jax.device_get(first_moments(out[1])["world_model"]))
                del out
        moved = jax.tree_util.tree_map(lambda a, b: (np.asarray(b) - np.asarray(a)) / 0.1, *grads)
        return to_reference({"world_model": moved})

    def acted(self) -> List[Dict[str, Any]]:
        """The kept acting steps on the host: previous stochastic state,
        action and recurrent state, and the recurrent state the player made."""
        import jax

        return [
            {
                "z": np.asarray(before["stochastic_state"], np.float32),
                "a": np.asarray(before["actions"], np.float32),
                "h": np.asarray(before["recurrent_state"], np.float32),
                "h_new": np.asarray(after["recurrent_state"], np.float32),
            }
            for before, after in jax.device_get(self.player_steps)
        ]

    def fell_back(self) -> Optional[str]:
        """Why the run did not take the path its traffic asks for (the device
        ring), or None: the runner fails the run on anything said here."""
        if not self.ring_expected:
            return None
        if not self.rings:
            return "the traffic asks for the device ring but the program built none"
        ring = self.rings[-1]
        if not ring.active:
            return f"the device ring deactivated itself: {ring.inactive_reason}"
        if self.fused_calls != self.calls:
            return f"{self.calls - self.fused_calls} of {self.calls} train calls sampled the host buffer, not the ring"
        return None


@contextlib.contextmanager
def installed(record: Record, on_iteration: Callable[[int], None]) -> Iterator[None]:
    """The hooks, for the length of one run of the program."""
    import jax

    from sheeprl_tpu.algos.dreamer_v3 import dreamer_v3 as main_mod
    from sheeprl_tpu.core import interact, resilience

    saved = {
        (resilience.PreemptionGuard, "advance"): resilience.PreemptionGuard.advance,
        (interact.InteractionPipeline, "interact"): interact.InteractionPipeline.interact,
        (main_mod, "make_train_step"): main_mod.make_train_step,
        (main_mod, "make_fused_train_step"): main_mod.make_fused_train_step,
        (main_mod, "build_agent"): main_mod.build_agent,
        (main_mod, "DeviceReplayRing"): main_mod.DeviceReplayRing,
    }
    advance = saved[(resilience.PreemptionGuard, "advance")]

    def patched_advance(guard, policy_step):
        if record.marks[-1][0] == "agent built":
            record.mark("first iteration")
        on_iteration(int(policy_step))
        return advance(guard, policy_step)

    def patched_interact(pipeline, envs, obs, policy, **kwargs):
        # The acting steps before the first gradient step use the benchmark's
        # own weights: keep what they were given and what they made
        # (references to small device arrays; nothing is read back here).
        if record.calls != 0 or len(record.player_steps) >= 8:
            return saved[(interact.InteractionPipeline, "interact")](pipeline, envs, obs, policy, **kwargs)

        def watched(np_obs, state, key):
            out = policy(np_obs, state, key)
            record.player_steps.append((state, out[1]))
            return out

        return saved[(interact.InteractionPipeline, "interact")](pipeline, envs, obs, watched, **kwargs)

    def patched_make_train_step(*args, **kwargs):
        return StepProbe(saved[(main_mod, "make_train_step")](*args, **kwargs), False, record)

    def patched_make_fused(*args, **kwargs):
        return StepProbe(saved[(main_mod, "make_fused_train_step")](*args, **kwargs), True, record)

    def patched_build_agent(runtime, *args, **kwargs):
        # Shapes from the program's own builder, traced and not run; values
        # from the benchmark, made on the mesh's first device in one call.
        built = {}

        def shapes():
            agent, state = saved[(main_mod, "build_agent")](runtime, *args, **kwargs)
            built["agent"] = agent
            return state

        state = weights_mod.make_weights(jax.eval_shape(shapes), record.seed, runtime.mesh.devices.flat[0])
        state["target_critic"] = jax.tree_util.tree_map(lambda x: x + 0, state["critic"])
        record.device = runtime.device  # the program runs under jax.default_device(this)
        record.mark("agent built")
        return built["agent"], state

    def patched_ring(*args, **kwargs):
        ring = saved[(main_mod, "DeviceReplayRing")](*args, **kwargs)
        record.rings.append(ring)
        return ring

    resilience.PreemptionGuard.advance = patched_advance
    interact.InteractionPipeline.interact = patched_interact
    main_mod.make_train_step = patched_make_train_step
    main_mod.make_fused_train_step = patched_make_fused
    main_mod.build_agent = patched_build_agent
    main_mod.DeviceReplayRing = patched_ring
    try:
        yield
    finally:
        for (owner, name), value in saved.items():
            setattr(owner, name, value)


def annotation_targets() -> List[Tuple[Any, str, str]]:
    """(owner, attribute, label) of the host-side layer boundaries that a
    traced run wraps in profiler annotations."""
    from benchmarks.envs import pixel_env
    from sheeprl_tpu.core import interact
    from sheeprl_tpu.data import device_buffer, infeed

    return [
        (pixel_env.PixelEnv, "step", "bench/env_step"),
        (interact.PendingFetch, "harvest", "bench/action_fetch"),
        (infeed.ReplayInfeed, "take_or_sample", "bench/replay_sample"),
        (infeed.ReplayInfeed, "stage", "bench/replay_stage"),
        (device_buffer.DeviceReplayRing, "flush", "bench/ring_flush"),
        (StepProbe, "__call__", "bench/train_dispatch"),
    ]


def run_program(args: List[str]) -> None:
    from sheeprl_tpu import cli

    cli.run(args)


# --------------------------------------------------------------- the comparison
def reference_initial(weights: Any) -> Any:
    """The benchmark's weights as the program starts from them: the target critic begins as the critic."""
    weights["target_critic"] = weights["critic"]
    return weights


def reference_step(ref: Any, state: Any, batch: Dict[str, Any], noise: Dict[str, Any], captured: Dict[str, Any]):
    """One step of the reference on what one captured step of the program was given."""
    return ref.step(state, batch, noise, captured["tau"])


def asked_again(ref: Any, state: Any, batch: Dict[str, Any], noise: Dict[str, Any], captured: Dict[str, Any]):
    """The world model's first gradient on ``batch`` (the first batch with one
    column altered), for `MOVED`; None where the program's step samples its
    own batch and cannot be asked again."""
    return None if captured["fused"] else ref.world_model_gradient(state, batch, noise)


def acting_reference(ref: Any, params: Dict[str, Any], acted: List[Dict[str, Any]]) -> List[np.ndarray]:
    """For `ACTING`: the recurrent state of each kept acting step, from the
    reference's equations on the program's previous state and the benchmark's
    weights (``params``: the reference run's ``initial``)."""
    import jax.numpy as jnp

    params = {k: jnp.asarray(v) for k, v in params.items() if k.startswith(GROUPS["world_model"])}
    return [
        np.asarray(ref.player_recurrent(params, *(jnp.asarray(step[k], jnp.float32) for k in ("z", "a", "h"))))
        for step in acted
    ]


def half_of_the_batch(model: Dict[str, Any]) -> Callable:
    """`calibrate.py`'s fault: ``mutate(batch, noise)`` that leaves half of the
    batch's columns out, with the noise of the kept rows."""
    T, B = model["sequence"], model["batch"]

    def mutate(batch, noise):
        half = B // 2
        batch = {k: v[:, :half] for k, v in batch.items()}

        def rows(x):  # [H, T*B, ...] -> the rows of the kept batch columns
            return x.reshape((x.shape[0], T, B) + x.shape[2:])[:, :, :half].reshape((x.shape[0], T * half) + x.shape[2:])

        noise = {"post": noise["post"][:, :half], "img_prior": rows(noise["img_prior"]), "actor": rows(noise["actor"])}
        return batch, noise

    return mutate


def reference_inputs(config: Dict[str, Any], captured: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(batch, noise) of one captured step, for the reference."""
    import jax.numpy as jnp

    model = config["model"]
    T, B = model["sequence"], model["batch"]
    noise, sample_key = step_noise(jnp.asarray(captured["key"]), model, (T, B), captured["fused"])
    if captured["fused"]:
        batch = ring_sample(captured["data"]["rows"], captured["data"]["added"], sample_key, B, T)
    else:
        batch = {k: jnp.asarray(v) for k, v in captured["data"].items()}
    return batch, noise


def program_numbers(
    captured: List[Dict[str, Any]], acted: Optional[List[Dict[str, Any]]] = None, moved: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """What the program's first three steps said, in the reference's naming;
    ``moved`` is what `Record.sensitivity` returned."""
    import jax

    losses = jax.device_get([c["losses"] for c in captured])
    mu = captured[0]["mu"]
    first = to_reference({name: mu[name] for name in GROUPS})
    return {
        "losses": [{k: float(np.asarray(step[v]).reshape(-1)[0]) for k, v in LOSSES.items()} for step in losses],
        "first_grads": {k: np.asarray(v) / 0.1 for k, v in first.items()},  # mu_1 = (1 - b1) g_1, b1 = 0.9
        "params": {k: np.asarray(v) for k, v in to_reference(captured[-1]["params"]).items()},
        "acting": [step["h_new"] for step in acted or []],
        "moved": moved,
    }

"""Adapter for the token-policy family of the system under test (`algo=ppo_lm`:
PPO over a `deepseek_v3` decoder, `sheeprl_tpu/algos/ppo_lm`).

From the program it takes only the system itself: the normal entry point
(`sheeprl_tpu.cli.run`) and, observed from outside, its iteration boundary
(`PreemptionGuard.advance`, one policy step), its jitted gradient step
(`make_train_step`, one minibatch a call), its agent builder (whose weights
are replaced with the benchmark's) and its player (the policy handed to
`InteractionPipeline.interact`). Nothing in the program is edited.

What the harness asks of an adapter is listed in `benchmarks/README.md`
("The adapter's protocol"); everything that names this family is here, in
`reference/deepseek_v3_ppo.py`, `flops/deepseek_v3_ppo.py`,
`envs/token_env.py` and the configuration's files.

Two numbers of its own. ``moved.step``: the compiled gradient step asked again
for its first step with one sequence's prompt replaced (`flipped`) has to move
its first gradient as the reference's moves; a step that leaves sequences out
does not. Both sides are asked with the surrogate's clip open (`ASKED_CLIP`, an
operand of the step): a replaced prompt moves that sequence's log-probabilities
by about 1 nat a token, so its ratios lie on both sides of 1 +- clip_coef, and
a token that two precisions put on two sides of that edge has its whole
gradient in one answer and none in the other (PERF.md section 6).
``player.logits``: the logits the player produced through prefill and the
latent cache over the first rollout, before any update, against the
reference's full forward pass on the same tokens: logits, never sampled
tokens. And one shown beside them: ``loss.route_flips``, the share of (token,
choice) slots of the real positions of the first three steps where program
and reference chose another expert (0 = every slot agrees; `route.agree` =
1 - this), the known hazard of a top-k over near-ties in two precisions.
"""

from __future__ import annotations

import contextlib
import gc
import re
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from benchmarks.harness import weights as weights_mod

# --------------------------------------------------------------- names
#: {number's suffix: leaf prefix in the reference's naming}; one optimizer updates them all.
GROUPS = {"attention": "attention/", "experts": "experts/", "shared": "shared/", "router": "router/",
          "dense": "dense/", "embed_head": "embed_head/", "value": "value/"}
MOVED = "moved.step"
#: The surrogate's clip in the step asked again, program and reference alike: wider than any ratio, so that
#: what is compared is a smooth function of the logits. The three steps compared keep the recipe's clip.
ASKED_CLIP = 1e9
ACTING = "player.logits"
#: {loss.<name>: the program's own name of that loss}; `route_flips` is the adapter's (see the module's docstring)
LOSSES = {"policy": "policy_loss", "value": "value_loss", "entropy": "entropy_loss", "route_flips": None}

_RENAMES: List[Tuple[str, str]] = [
    (r"^params/backbone/embedding$", "embed_head/embed"),
    (r"^params/backbone/final_norm/scale$", "embed_head/final_norm"),
    (r"^params/head$", "embed_head/head"),
    (r"^params/value_head$", "value/w"),
    (r"^params/backbone/layers_(\d+)/attn/(norm|kv_norm)/scale$", r"attention/l\1/\2"),
    (r"^params/backbone/layers_(\d+)/attn/(\w+)$", r"attention/l\1/\2"),
    (r"^params/backbone/layers_(\d+)/mlp_norm/scale$", r"dense/l\1/norm"),
    (r"^params/backbone/layers_(\d+)/mlp/(\w+)$", r"dense/l\1/\2"),
    (r"^params/backbone/layers_(\d+)/moe/norm/scale$", r"router/l\1/norm"),
    (r"^params/backbone/layers_(\d+)/moe/router$", r"router/l\1/w"),
    (r"^params/backbone/layers_(\d+)/moe/router_bias$", r"router/l\1/bias"),
    (r"^params/backbone/layers_(\d+)/moe/shared/(\w+)$", r"shared/l\1/\2"),
    (r"^params/backbone/layers_(\d+)/moe/(w_gate|w_up|w_down)$", r"experts/l\1/\2"),
]


def reference_name(path: Tuple[str, ...]) -> str:
    name = "/".join(path)
    for pattern, repl in _RENAMES:
        renamed, n = re.subn(pattern, repl, name)
        if n:
            return renamed
    raise SystemExit(f"benchmark: the program's leaf {name!r} has no name in the reference")


def to_reference(tree: Any) -> Dict[str, Any]:
    """A tree in the program's layout as the reference's flat dict."""
    return {reference_name(path): leaf for path, leaf in weights_mod.leaf_paths(tree).items()}


def reference_initial(weights: Any) -> Any:
    return weights


# --------------------------------------------------------------- overrides
def _fmt(value: Any) -> str:
    if isinstance(value, bool):
        return "True" if value else "False"
    if isinstance(value, (list, tuple)):
        return "[" + ",".join(_fmt(v) for v in value) + "]"
    return str(value)


def overrides(config: Dict[str, Any], traffic: Dict[str, Any], seed: int, run_dir: str, trace: bool) -> List[str]:
    """What `python -m sheeprl_tpu` is given: the recipe at its published sizes,
    the chip's share, the benchmark's env, the traffic mix, nothing saved."""
    env = dict(config.get("env", {}))
    env.update(traffic.get("env", {}))
    out = [f"exp={config['program']['exp']}", "env.wrapper._target_=benchmarks.envs.token_env.TokenBenchEnv"]
    out += [f"{'' if k in ('max_prompt_len', 'min_prompt_len') else '+'}env.wrapper.{k}={_fmt(v)}" for k, v in env.items()]
    out += [f"+env.wrapper.run_seed={seed}", "+env.wrapper.rank=0"]
    for source in (config["program"].get("overrides", {}), traffic.get("overrides", {})):
        out += [f"{k}={_fmt(v)}" for k, v in source.items()]
    out += [
        "env.capture_video=False",
        "env.sync_env=True",
        "algo.total_steps=1000000000",
        "algo.run_test=False",
        "checkpoint.every=0",
        "checkpoint.save_last=False",
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


# --------------------------------------------------------------- the traffic and the recipe
def _rollout(traffic: Dict[str, Any]) -> int:
    mix = traffic["overrides"]
    return int(mix["env.num_envs"]) * int(mix["algo.rollout_steps"])


def warm_policy_steps(traffic: Dict[str, Any]) -> int:
    """The window may open at the first policy step (the prefill) of the
    rollout after the traffic's warm rollouts, whose updates are then through
    (the first three gradient steps are waited for besides). A window that
    opens there holds whole cycles of prefill, decode steps and update, and
    its last edge falls inside an update, the one iteration long enough that
    the edge does not wander across a phase with the machine's speed
    (PERF.md section 2)."""
    return int(traffic.get("warm_rollouts", 1)) * _rollout(traffic) + int(traffic["overrides"]["env.num_envs"])


def gradient_steps_owed(traffic: Dict[str, Any], policy_steps: int) -> float:
    """A rollout of envs x rollout steps policy steps owes epochs x minibatches gradient steps."""
    mix = traffic["overrides"]
    return policy_steps / _rollout(traffic) * int(mix["algo.update_epochs"]) * int(mix["algo.per_rank_num_batches"])


def recipe_sizes(cfg: Any) -> Dict[str, Any]:
    """The sizes of the composed recipe ``cfg`` (at its published sizes) under
    the keys of the configuration file's ``model``; what the share overrides is
    compared as ``published``."""
    model = cfg.algo.model
    same = ("hidden_size", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
            "intermediate_size", "moe_intermediate_size", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "routed_scaling_factor", "norm_topk_prob", "rope_theta", "rms_norm_eps",
            "n_routed_experts")
    sizes = {key: model[key] for key in same}
    sizes["published"] = {key: model[key] for key in ("num_hidden_layers", "n_routed_experts", "experts_held", "vocab_size")}
    sizes.update(
        rollout_steps=cfg.algo.rollout_steps,
        batch=cfg.env.num_envs // cfg.algo.per_rank_num_batches,
        gamma=float(cfg.algo.gamma),
        lmbda=cfg.algo.gae_lambda,
        clip_coef=cfg.algo.clip_coef,
        vf_coef=cfg.algo.vf_coef,
        ent_coef=float(cfg.algo.ent_coef),
        optim={"lr": cfg.algo.optimizer.lr, "eps": cfg.algo.optimizer.eps, "clip": cfg.algo.max_grad_norm},
        compute_dtype={"bf16-mixed": "bfloat16", "32-true": "float32"}[str(cfg.fabric.precision)],
    )
    return sizes


# --------------------------------------------------------------- the altered batch
def flipped_column(seed: int, batch: Dict[str, Any]) -> int:
    """Which sequence of the minibatch `flipped` alters: drawn from the seed."""
    return int(seed) % int(np.shape(batch["tokens"])[0])


def flipped(batch: Dict[str, Any], column: int) -> Dict[str, Any]:
    """The minibatch with one sequence's prompt replaced: its real prompt ids
    in reverse order (so they stay in the vocabulary); response and the
    rollout's numbers as they were."""
    tokens = np.array(batch["tokens"])
    prompt = tokens.shape[1] - np.shape(batch["mask"])[1]
    begins = int(np.asarray(batch["start"])[column])
    tokens[column, begins:prompt] = tokens[column, begins:prompt][::-1]
    return dict(batch, tokens=tokens)


# --------------------------------------------------------------- probes
def first_moments(opt_state: Any) -> Any:
    """Adam's first moment, a tree shaped like the params."""
    import jax

    found = [s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    return found[0].mu


class StepProbe:
    """Stands where the program's jitted gradient step stands: calls it, counts
    the gradient steps, and keeps what the first three calls were given and
    returned. Of the first call the shapes and placement of the state are kept
    too, so that the same compiled step can be asked again once the window
    has closed (`Record.sensitivity`)."""

    CAPTURED = 3

    def __init__(self, fn: Callable, record: "Record") -> None:
        self._fn = fn
        self.record = record

    def __getattr__(self, name: str) -> Any:
        return getattr(self._fn, name)

    def __call__(self, params, opt_state, batch, clip_coef, ent_coef):
        import jax

        rec = self.record
        n = rec.calls
        if n == 0:
            like = lambda tree: jax.tree_util.tree_map(  # noqa: E731
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), tree)
            rec.first_call = {"step": self.call, "params": like(params), "opt_state": like(opt_state),
                              "batch": {k: np.array(v) for k, v in batch.items()}, "clip_coef": clip_coef,
                              "ent_coef": ent_coef}
        out = self.call(params, opt_state, batch, clip_coef, ent_coef)
        rec.calls += 1
        rec.steps += 1
        rec.last = out[2]
        if n < self.CAPTURED:
            rec.mark(f"train call {n + 1} enqueued")
            captured = {"data": {k: np.array(v) for k, v in batch.items()}, "losses": out[2],
                        "routes": jax.device_get(out[3])}
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


class Record:
    """What the harness learns about one run of the program, from outside."""

    def __init__(self, seed: int, traffic: Dict[str, Any]) -> None:
        self.seed = seed
        self.calls = 0
        self.steps = 0
        self.last: Any = None
        self.captured: List[Dict[str, Any]] = []
        self.fault: Optional[Callable] = None  # tests plant a broken step here
        self.marks: List[Tuple[str, float]] = []
        self.first_call: Optional[Dict[str, Any]] = None
        self.device: Any = None
        # the first rollout, before any update: the prompts (host) and per policy step (token, logits) on the device
        self.prompts: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self.player_steps: List[Tuple[Any, Any]] = []

    def mark(self, what: str) -> None:
        self.marks.append((what, time.perf_counter()))

    def sync(self) -> None:
        import jax

        if self.last is not None:
            jax.block_until_ready(self.last)

    def release(self) -> None:
        self.last = None
        self.player_steps.clear()

    def acted(self) -> List[Dict[str, Any]]:
        """The first rollout, env by env: the whole sequence ``[prompt |
        response]`` the player produced, where its context begins, and the
        logits [R, V] each response token was drawn from."""
        import jax

        if self.prompts is None or not self.player_steps:
            return []
        prompts, lengths = self.prompts
        steps = jax.device_get(self.player_steps)
        tokens = np.stack([np.asarray(t) for t, _ in steps], axis=1)  # [E, R]
        logits = np.stack([np.asarray(lg, np.float32) for _, lg in steps], axis=1)  # [E, R, V]
        whole = np.concatenate([prompts, tokens], axis=1).astype(np.int32)
        return [{"tokens": whole[e], "start": int(prompts.shape[1] - lengths[e]), "logits": logits[e]}
                for e in range(len(whole))]

    def sensitivity(self) -> Optional[Dict[str, Any]]:
        """Once the window has closed and the program's state is gone: the
        compiled step the window drove, asked twice more for its first step on
        the benchmark's weights and an optimizer at nought, with the first
        minibatch as it was and with one sequence's prompt replaced
        (`flipped`), the surrogate's clip open both times (`ASKED_CLIP`; the
        same compiled step: the clip is its operand). Returns how the first
        gradient moved between the two, in the reference's naming; a step
        that leaves sequences out moves it by nothing or by too much."""
        import jax
        import jax.numpy as jnp

        first, self.first_call = self.first_call, None
        if first is None:
            return None
        gc.collect()
        kept = first["params"], first["opt_state"]

        def fresh(key):
            zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), first["opt_state"])
            return weights_mod.draw(first["params"], key), zeros

        fresh = jax.jit(fresh, out_shardings=jax.tree_util.tree_map(lambda s: s.sharding, kept))
        batch = first["batch"]
        clip = np.full_like(first["clip_coef"], ASKED_CLIP)
        grads = []
        with jax.default_device(self.device):
            for data in (batch, flipped(batch, flipped_column(self.seed, batch))):
                out = first["step"](*fresh(weights_mod.seed_key(self.seed)), data, clip, first["ent_coef"])
                grads.append(jax.device_get(first_moments(out[1])))
                del out
        as_it_was, altered = (to_reference(g) for g in grads)
        del grads
        return {k: (np.asarray(altered.pop(k)) - np.asarray(as_it_was.pop(k))) / 0.1 for k in list(as_it_was)}


@contextlib.contextmanager
def installed(record: Record, on_iteration: Callable[[int], None]) -> Iterator[None]:
    """The hooks, for the length of one run of the program."""
    import jax

    from sheeprl_tpu.algos.ppo_lm import ppo_lm as main_mod
    from sheeprl_tpu.core import interact, resilience

    saved = {
        (resilience.PreemptionGuard, "advance"): resilience.PreemptionGuard.advance,
        (interact.InteractionPipeline, "interact"): interact.InteractionPipeline.interact,
        (main_mod, "make_train_step"): main_mod.make_train_step,
        (main_mod, "build_agent"): main_mod.build_agent,
    }
    advance = saved[(resilience.PreemptionGuard, "advance")]

    def patched_advance(guard, policy_step):
        if record.marks[-1][0] == "agent built":
            record.mark("first iteration")
        on_iteration(int(policy_step))
        return advance(guard, policy_step)

    def patched_interact(pipeline, envs, obs, policy, **kwargs):
        # The first rollout acts on the benchmark's own weights: keep the
        # prompts and, step by step, references to the token drawn and the
        # logits it was drawn from (small device arrays; nothing is read back here).
        if record.calls != 0:
            return saved[(interact.InteractionPipeline, "interact")](pipeline, envs, obs, policy, **kwargs)
        if record.prompts is None:
            record.prompts = (np.array(obs["prompt"]), np.array(obs["prompt_len"][:, 0]))

        def watched(np_obs, state, key):
            out = policy(np_obs, state, key)
            record.player_steps.append((out[0][0], out[1]["logits"]))
            return out

        return saved[(interact.InteractionPipeline, "interact")](pipeline, envs, obs, watched, **kwargs)

    def patched_make_train_step(*args, **kwargs):
        return StepProbe(saved[(main_mod, "make_train_step")](*args, **kwargs), record)

    def patched_build_agent(runtime, cfg, vocab_size, *args, **kwargs):
        # Shapes from the program's own builder, traced and not run; values
        # from the benchmark, made on the mesh's first device in one call.
        built = {}

        def shapes():
            agent, params = saved[(main_mod, "build_agent")](runtime, cfg, vocab_size, *args, **kwargs)
            built["agent"] = agent
            return params

        params = weights_mod.make_weights(jax.eval_shape(shapes), record.seed, runtime.mesh.devices.flat[0])
        record.device = runtime.device
        record.mark("agent built")
        return built["agent"], params

    resilience.PreemptionGuard.advance = patched_advance
    interact.InteractionPipeline.interact = patched_interact
    main_mod.make_train_step = patched_make_train_step
    main_mod.build_agent = patched_build_agent
    try:
        yield
    finally:
        for (owner, name), value in saved.items():
            setattr(owner, name, value)


def annotation_targets() -> List[Tuple[Any, str, str]]:
    """(owner, attribute, label) of the host-side layer boundaries that a traced run wraps in profiler annotations."""
    from benchmarks.envs import token_env
    from sheeprl_tpu.core import interact

    return [
        (token_env.TokenBenchEnv, "step", "bench/env_step"),
        (token_env.TokenBenchEnv, "reset", "bench/env_reset"),
        (interact.PendingFetch, "harvest", "bench/action_fetch"),
        (StepProbe, "__call__", "bench/train_dispatch"),
    ]


def run_program(args: List[str]) -> None:
    from sheeprl_tpu import cli

    cli.run(args)


# --------------------------------------------------------------- the comparison
def reference_inputs(config: Dict[str, Any], captured: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(minibatch, noise) of one captured step: a gradient step draws nothing."""
    return {k: np.asarray(v) for k, v in captured["data"].items()}, {}


def route_flips(program: np.ndarray, reference: Optional[np.ndarray], start: np.ndarray) -> float:
    """Share of the (token, choice) slots of the real positions where the
    reference chose an expert the program did not: ``program`` [L, B*S, k],
    ``reference`` [L, B, S, k], ``start`` [B]."""
    if reference is None or program.size != reference.size:  # no expert layer, or a minibatch that was cut (a fault)
        return 0.0
    L, B, S, k = reference.shape
    program = program.reshape(L, B, S, k)
    real = np.arange(S)[None, :] >= np.asarray(start)[:, None]  # [B, S]
    missed = ~(reference[..., :, None] == program[..., None, :]).any(-1)  # [L, B, S, k]
    return float(missed[:, real].mean())


def reference_step(ref: Any, state: Any, batch: Dict[str, Any], noise: Dict[str, Any], captured: Dict[str, Any]):
    """One step of the reference on the minibatch one captured step of the
    program was given. ``loss.route_flips`` is made here, where both sides'
    routes meet: the program's side reports 1 and the reference's side
    1 + the share of slots that flipped, so the number compared is that share."""
    state, out = ref.step(state, batch)
    flips = route_flips(np.asarray(captured["routes"]), out["routes"], batch["start"]) if "routes" in captured else 0.0
    out["losses"] = dict(out["losses"], route_flips=1.0 / (1.0 - min(flips, 0.999999)))
    return state, out


def asked_again(ref: Any, state: Any, batch: Dict[str, Any], noise: Dict[str, Any], captured: Dict[str, Any]):
    """The reference's first gradient on ``batch`` (the first minibatch with one sequence's prompt replaced) with
    the surrogate's clip open, for `MOVED`. `compare.reference_run` takes the first step's own gradient from it: on
    the minibatch as it was every ratio is within rounding of 1, so that gradient is the same with the clip open."""
    return ref.first_gradient(state, batch, clip_coef=ASKED_CLIP)


def acting_reference(ref: Any, params: Dict[str, Any], acted: List[Dict[str, Any]]) -> List[np.ndarray]:
    """For `ACTING`: the reference's full forward pass on each env's whole
    sequence of the first rollout, logits at the response positions, on the
    benchmark's weights (``params``: the reference run's ``initial``)."""
    import jax.numpy as jnp

    on_device = {k: jnp.asarray(v) for k, v in params.items()}
    return [ref.logits(on_device, step["tokens"], step["start"]) for step in acted]


def half_of_the_batch(model: Dict[str, Any]) -> Callable:
    """`calibrate.py`'s fault: ``mutate(batch, noise)`` that leaves half of the minibatch's sequences out."""

    def mutate(batch, noise):
        half = max(len(batch["tokens"]) // 2, 1)
        return {k: v[:half] for k, v in batch.items()}, noise

    return mutate


def program_numbers(
    captured: List[Dict[str, Any]], acted: Optional[List[Dict[str, Any]]] = None, moved: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """What the program's first three steps said, in the reference's naming."""
    import jax

    losses = jax.device_get([c["losses"] for c in captured])
    # mu_1 = (1 - b1) g_1, b1 = 0.9; leaf by leaf, each moment let go as its gradient is made (2.3 GB at the cell's size)
    mu = to_reference(captured[0].pop("mu"))
    first = {k: np.asarray(mu.pop(k)) / 0.1 for k in list(mu)}
    return {
        "losses": [dict({k: float(step[v]) for k, v in LOSSES.items() if v}, route_flips=1.0) for step in losses],
        "first_grads": first,
        "params": {k: np.asarray(v) for k, v in to_reference(captured[-1]["params"]).items()},
        "acting": [step["logits"] for step in acted or []],
        "moved": moved,
    }

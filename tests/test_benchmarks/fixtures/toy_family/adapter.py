"""Adapter of the toy family, and the toy program itself: on-policy (a rollout
of R policy steps, then K gradient steps on it; no prefill, no replay ratio),
one parameter group, no target network, nothing recurrent in the player,
scopes `toy/*`. It answers the harness's protocol (`benchmarks/README.md`)
and leaves out what it has nothing for: `asked_again` / `MOVED`,
`acting_reference` / `ACTING`, `flipped`, `Record.fell_back`, and
`annotation_targets` (it is never traced).
"""

import contextlib
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import weights as weights_mod

GROUPS = {"policy": "pi/"}
LOSSES = {"policy": "loss"}
_HOOKS = {}  # what `installed` hands the program for the length of one run


def to_reference(tree):
    names = {"kernel": "w", "bias": "b"}
    return {f"pi/{path[-2]}/{names[path[-1]]}": leaf for path, leaf in weights_mod.leaf_paths(tree).items()}


def reference_initial(weights):
    return weights


# --------------------------------------------------------------- the traffic and the recipe
def overrides(config, traffic, seed, run_dir, trace):
    model, mix = config["model"], traffic["overrides"]
    if model["rows"] != mix["rollout_steps"]:
        raise SystemExit("benchmark: the toy step's rows are the traffic's rollout")
    return [f"{k}={model[k]}" for k in ("obs", "hidden", "actions", "lr")] + [
        f"rollout={mix['rollout_steps']}", f"epochs={mix['update_epochs']}", f"seed={seed}"]


def warm_policy_steps(traffic):
    return 2 * int(traffic["overrides"]["rollout_steps"])


def gradient_steps_owed(traffic, policy_steps):
    mix = traffic["overrides"]
    return mix["update_epochs"] * policy_steps / mix["rollout_steps"]


# --------------------------------------------------------------- probes
class StepProbe:
    CAPTURED = 3

    def __init__(self, fn, record):
        self._fn, self.record = fn, record

    def __call__(self, params, batch):
        rec, n = self.record, self.record.calls
        out = self._fn(params, batch)
        rec.calls += 1
        rec.steps += 1
        rec.last = out[1]
        if n < self.CAPTURED:
            captured = {"data": jax.device_get(batch), "losses": out[1]}
            if n == 0:
                captured["grads"] = jax.device_get(out[2])
            if n == self.CAPTURED - 1:
                captured["params"] = jax.device_get(out[0])
            rec.captured.append(captured)
        return out


class Record:
    def __init__(self, seed, traffic):
        self.seed, self.calls, self.steps, self.last = seed, 0, 0, None
        self.captured, self.marks = [], []

    def mark(self, what):
        self.marks.append((what, time.perf_counter()))

    def sync(self):
        if self.last is not None:
            jax.block_until_ready(self.last)

    def release(self):
        self.last = None

    def sensitivity(self):
        return None

    def acted(self):
        return []


@contextlib.contextmanager
def installed(record, on_iteration):
    _HOOKS.update(record=record, on_iteration=on_iteration)
    try:
        yield
    finally:
        _HOOKS.clear()


# --------------------------------------------------------------- the program
def toy_step(params, batch, lr):
    def loss_fn(p):
        with jax.named_scope("toy/policy"):
            hidden = jnp.tanh(batch["obs"] @ p["policy"]["l0"]["kernel"] + p["policy"]["l0"]["bias"])
            logp = jax.nn.log_softmax(hidden @ p["policy"]["l1"]["kernel"] + p["policy"]["l1"]["bias"])
            chosen = jnp.take_along_axis(logp, batch["actions"][:, None], axis=1)[:, 0]
            return -jnp.mean(batch["returns"] * chosen)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    with jax.named_scope("toy/optim"):
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    return params, {"loss": loss}, grads


def run_program(args):
    """R policy steps (the iteration hook at each), then K gradient steps,
    until SIGTERM: the loop leaves at its next boundary."""
    opts = {k: float(v) if "." in v else int(v) for k, v in (a.split("=") for a in args)}
    record, on_iteration = _HOOKS["record"], _HOOKS["on_iteration"]
    sizes = ((opts["obs"], opts["hidden"]), (opts["hidden"], opts["actions"]))
    shapes = {"policy": {f"l{i}": {"kernel": jax.ShapeDtypeStruct(s, jnp.float32), "bias": jax.ShapeDtypeStruct(s[1:], jnp.float32)}
                         for i, s in enumerate(sizes)}}
    params = weights_mod.make_weights(shapes, opts["seed"])
    step = StepProbe(jax.jit(lambda p, b: toy_step(p, b, opts["lr"])), record)
    rng = np.random.default_rng(opts["seed"])
    stopped = []
    previous = signal.signal(signal.SIGTERM, lambda *_: stopped.append(True))
    try:
        policy_step = 0
        while not stopped:
            rows = []
            for _ in range(opts["rollout"]):
                on_iteration(policy_step)
                rows.append((rng.normal(size=opts["obs"]), rng.integers(opts["actions"]), rng.normal()))
                policy_step += 1
            obs, actions, returns = (np.stack(column) for column in zip(*rows))
            batch = {"obs": obs.astype(np.float32), "actions": actions.astype(np.int32), "returns": returns.astype(np.float32)}
            for _ in range(opts["epochs"]):
                params, _, _ = step(params, batch)
    finally:
        signal.signal(signal.SIGTERM, previous)


# --------------------------------------------------------------- the comparison
def reference_inputs(config, captured):
    return {k: jnp.asarray(v) for k, v in captured["data"].items()}, {}


def reference_step(ref, state, batch, noise, captured):
    return ref.step(state, batch)


def half_of_the_batch(model):
    return lambda batch, noise: ({k: v[: model["rows"] // 2] for k, v in batch.items()}, noise)


def program_numbers(captured, acted=None, moved=None):
    losses = jax.device_get([c["losses"] for c in captured])
    return {
        "losses": [{k: float(step[v]) for k, v in LOSSES.items()} for step in losses],
        "first_grads": {k: np.asarray(v) for k, v in to_reference(captured[0]["grads"]).items()},
        "params": {k: np.asarray(v) for k, v in to_reference(captured[-1]["params"]).items()},
    }

"""Adapter for the token-policy family over a `phi4flash` backbone (`algo=ppo_lm`
with `algo.model.model_type=phi4flash`: `sheeprl_tpu/models/hybrid_decoder.py`).

The program's loop, gradient step, agent builder and player are the ones
`adapters/ppo_lm.py` observes, so its protocol is taken from there as it is:
the hooks (`installed`, `StepProbe`), the overrides, the traffic's arithmetic,
the altered batch (`flipped`), `MOVED` with its open clip (`ASKED_CLIP`) and
`ACTING` (the logits the player produced through prefill, the window's ring,
the shared keys and values and the recurrent state, against the reference's
full forward pass). What names leaves and sizes is replaced here: the parameter
groups, the renames into `reference/phi4flash_ppo.py`'s naming, the recipe's
sizes, and the losses (this family has no router, so no `route_flips`).
"""

from __future__ import annotations

import gc
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchmarks.harness import weights as weights_mod
from benchmarks.harness.adapters import ppo_lm as base
from benchmarks.harness.adapters.ppo_lm import (  # noqa: F401  the protocol, as the harness asks for it by name
    ACTING,
    ASKED_CLIP,
    MOVED,
    StepProbe,
    acting_reference,
    annotation_targets,
    asked_again,
    flipped,
    flipped_column,
    gradient_steps_owed,
    half_of_the_batch,
    installed,
    overrides,
    reference_initial,
    reference_inputs,
    run_program,
    warm_policy_steps,
)

#: {number's suffix: leaf prefix in the reference's naming}; one optimizer updates them all.
GROUPS = {"ssm": "ssm/", "window_attn": "window_attn/", "full_attn": "full_attn/", "cross_attn": "cross_attn/",
          "gmu": "gmu/", "mlp": "mlp/", "embed_head": "embed_head/", "value": "value/"}
#: {loss.<name>: the program's own name of that loss}
LOSSES = {"policy": "policy_loss", "value": "value_loss", "entropy": "entropy_loss"}

_MIXERS = {"ssm": "ssm", "swa": "window_attn", "full": "full_attn", "cross": "cross_attn", "gmu": "gmu"}
_LAYER = re.compile(r"^params/backbone/layer_(\d+)/(\w+?)(?:/(norm|subln))?/(\w+)$")
_TOP = {"params/backbone/embedding": "embed_head/embed", "params/backbone/final_norm/scale": "embed_head/final_norm_scale",
        "params/backbone/final_norm/bias": "embed_head/final_norm_bias", "params/value_head": "value/w"}


def reference_name(path: Tuple[str, ...]) -> str:
    name = "/".join(path)
    if name in _TOP:
        return _TOP[name]
    found = _LAYER.match(name)
    if found:
        index, owner, norm, leaf = found.groups()
        if owner in _MIXERS or owner in ("mlp", "mlp_norm"):
            group = _MIXERS.get(owner, "mlp")
            if owner == "mlp_norm" or norm == "norm":
                leaf = "norm_" + leaf
            elif norm == "subln":
                leaf = "subln"
            return f"{group}/l{index}/{leaf}"
    raise SystemExit(f"benchmark: the program's leaf {name!r} has no name in the reference")


def to_reference(tree: Any) -> Dict[str, Any]:
    """A tree in the program's layout as the reference's flat dict."""
    return {reference_name(path): leaf for path, leaf in weights_mod.leaf_paths(tree).items()}


def recipe_sizes(cfg: Any) -> Dict[str, Any]:
    """The sizes of the composed recipe ``cfg`` (at its published sizes) under
    the keys of the configuration file's ``model``; what the share overrides is
    compared as ``published``."""
    model = cfg.algo.model
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size", "sliding_window",
            "mb_per_layer", "layer_norm_eps", "d_state", "d_conv", "expand", "dt_rank")
    sizes = {key: model[key] for key in same}
    sizes["published"] = {key: model[key] for key in ("num_hidden_layers", "layers_held", "vocab_size")}
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


class Record(base.Record):
    """`adapters/ppo_lm.py`'s record; the step asked again speaks this family's names."""

    def sensitivity(self) -> Optional[Dict[str, Any]]:
        """As `adapters/ppo_lm.py:Record.sensitivity`: the window's compiled step asked twice more for its first
        step (the first minibatch as it was, and with one sequence's prompt replaced), the clip open both times."""
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
                grads.append(to_reference(jax.device_get(base.first_moments(out[1]))))
                del out
        as_it_was, altered = grads
        return {k: (np.asarray(altered.pop(k)) - np.asarray(as_it_was.pop(k))) / 0.1 for k in list(as_it_was)}


def reference_step(ref: Any, state: Any, batch: Dict[str, Any], noise: Dict[str, Any], captured: Dict[str, Any]):
    """One step of the reference on the minibatch one captured step of the program was given."""
    return ref.step(state, batch)


def program_numbers(
    captured: List[Dict[str, Any]], acted: Optional[List[Dict[str, Any]]] = None, moved: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """What the program's first three steps said, in the reference's naming."""
    import jax

    losses = jax.device_get([c["losses"] for c in captured])
    mu = to_reference(captured[0].pop("mu"))  # mu_1 = (1 - b1) g_1, b1 = 0.9; each moment let go as its gradient is made
    first = {k: np.asarray(mu.pop(k)) / 0.1 for k in list(mu)}
    return {
        "losses": [{k: float(step[v]) for k, v in LOSSES.items()} for step in losses],
        "first_grads": first,
        "params": {k: np.asarray(v) for k, v in to_reference(captured[-1]["params"]).items()},
        "acting": [step["logits"] for step in acted or []],
        "moved": moved,
    }

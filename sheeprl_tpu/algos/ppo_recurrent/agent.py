"""Recurrent PPO agent (flax): encoder -> LSTM -> actor heads + critic
(reference: sheeprl/algos/ppo_recurrent/agent.py:18-470).

TPU-first sequence handling: the LSTM runs as ONE `nn.scan` over the time
axis with an in-scan hidden-state reset driven by the previous step's done
flag — a single code path serves both the player (a length-1 sequence) and
BPTT training (fixed-length chunks). The reference's variable-length padded
episode splitting + pack_padded_sequence machinery (ppo_recurrent.py:414-444)
is replaced by equal-length chunks with in-scan resets: same data coverage,
static shapes, no masking needed because every step is real.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import gymnasium
import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from sheeprl_tpu.algos.ppo.agent import (
    CNNEncoder,
    MLPEncoder,
    PPOActor,
    _tanh_correction,
)
from sheeprl_tpu.algos.ppo.utils import normalize_obs
from sheeprl_tpu.models import MLP, MultiEncoder
from sheeprl_tpu.utils.distribution import Independent, Normal, OneHotCategorical
from sheeprl_tpu.utils.ops import safeatanh, safetanh

_EPS = 1e-6


class _ResetLSTMCell(nn.Module):
    """LSTM cell whose carry is zeroed when the step's reset flag is set
    (the player's on-done reset, reproduced inside BPTT)."""

    hidden_size: int

    @nn.compact
    def __call__(self, carry, inp):
        x, reset = inp
        c, h = carry
        c = c * (1.0 - reset)
        h = h * (1.0 - reset)
        (c, h), out = nn.OptimizedLSTMCell(self.hidden_size, name="cell")((c, h), x)
        return (c, h), out


class RecurrentPPOModule(nn.Module):
    """Full parameter set; one sequence-shaped __call__
    ([T, B, ...] inputs, (c0, h0) carry) serves player (T=1) and training."""

    actions_dim: Sequence[int]
    is_continuous: bool
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    encoder_cfg: Dict[str, Any]
    rnn_cfg: Dict[str, Any]
    actor_cfg: Dict[str, Any]
    critic_cfg: Dict[str, Any]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        carry: Tuple[jax.Array, jax.Array],
        prev_dones: jax.Array,
    ) -> Tuple[List[jax.Array], jax.Array, Tuple[jax.Array, jax.Array]]:
        cnn_encoder = (
            CNNEncoder(
                keys=list(self.cnn_keys),
                features_dim=self.encoder_cfg["cnn_features_dim"],
                dtype=self.dtype,
                name="cnn_encoder",
            )
            if len(self.cnn_keys) > 0
            else None
        )
        mlp_encoder = (
            MLPEncoder(
                keys=list(self.mlp_keys),
                features_dim=self.encoder_cfg["mlp_features_dim"],
                dense_units=self.encoder_cfg["dense_units"],
                mlp_layers=self.encoder_cfg["mlp_layers"],
                dense_act=self.encoder_cfg["dense_act"],
                layer_norm=self.encoder_cfg["layer_norm"],
                dtype=self.dtype,
                name="mlp_encoder",
            )
            if len(self.mlp_keys) > 0
            else None
        )
        feat = MultiEncoder(cnn_encoder, mlp_encoder, name="feature_extractor")(obs)  # [T, B, F]
        x = jnp.concatenate([feat, prev_actions], axis=-1)

        pre_cfg = self.rnn_cfg["pre_rnn_mlp"]
        if pre_cfg["apply"]:
            x = MLP(
                hidden_sizes=[pre_cfg["dense_units"]],
                activation=pre_cfg["activation"],
                layer_args={"bias": pre_cfg["bias"]},
                norm_layer="layer_norm" if pre_cfg["layer_norm"] else None,
                norm_args={"eps": 1e-3} if pre_cfg["layer_norm"] else {},
                dtype=self.dtype,
                name="pre_rnn_mlp",
            )(x)

        scan_cell = nn.scan(
            _ResetLSTMCell,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(hidden_size=self.rnn_cfg["lstm"]["hidden_size"], name="lstm")
        carry, out = scan_cell(carry, (x, prev_dones))  # out: [T, B, H]

        post_cfg = self.rnn_cfg["post_rnn_mlp"]
        if post_cfg["apply"]:
            out = MLP(
                hidden_sizes=[post_cfg["dense_units"]],
                activation=post_cfg["activation"],
                layer_args={"bias": post_cfg["bias"]},
                norm_layer="layer_norm" if post_cfg["layer_norm"] else None,
                norm_args={"eps": 1e-3} if post_cfg["layer_norm"] else {},
                dtype=self.dtype,
                name="post_rnn_mlp",
            )(out)

        actor_out = PPOActor(
            actions_dim=self.actions_dim,
            is_continuous=self.is_continuous,
            dense_units=self.actor_cfg["dense_units"],
            mlp_layers=self.actor_cfg["mlp_layers"],
            dense_act=self.actor_cfg["dense_act"],
            layer_norm=self.actor_cfg["layer_norm"],
            dtype=self.dtype,
            name="actor",
        )(out)
        values = MLP(
            hidden_sizes=[self.critic_cfg["dense_units"]] * self.critic_cfg["mlp_layers"],
            output_dim=1,
            activation=self.critic_cfg["dense_act"],
            norm_layer="layer_norm" if self.critic_cfg["layer_norm"] else None,
            dtype=self.dtype,
            name="critic",
        )(out)
        return actor_out, values, carry


@dataclass(frozen=True)
class RecurrentPPOAgent:
    """Bundles the module with action metadata; the LSTM carry is an explicit
    (c, h) pytree threaded through jitted calls."""

    module: RecurrentPPOModule
    actions_dim: Tuple[int, ...]
    is_continuous: bool
    distribution: str
    rnn_hidden_size: int
    cnn_keys: Tuple[str, ...] = ()

    def initial_states(self, n_envs: int) -> Tuple[jax.Array, jax.Array]:
        z = jnp.zeros((n_envs, self.rnn_hidden_size), jnp.float32)
        return (z, z)

    def reset_states(self, carry, reset_mask: jax.Array):
        """Zero the carry where reset_mask ([B, 1]) is set."""
        return tuple(s * (1.0 - reset_mask) for s in carry)

    # ------------------------------------------------------------- player
    def player_step(
        self,
        params: Any,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        carry,
        key: jax.Array,
    ):
        """One env step = a length-1 sequence: (actions_cat, real_actions,
        logprobs[B,1], values[B,1], new_carry, next_key). Obs normalization
        and the PRNG split happen in-graph (cf. ppo/agent.py player_step) so
        one jitted call is the step's only dispatch — no per-step host
        round trip when the player lives on a mesh device."""
        obs = normalize_obs(obs, self.cnn_keys, list(obs.keys()))
        next_key, key = jax.random.split(key)
        obs = {k: v[None] for k, v in obs.items()}
        zeros = jnp.zeros((1, prev_actions.shape[0], 1), jnp.float32)
        actor_out, values, carry = self.module.apply(params, obs, prev_actions[None], carry, zeros)
        actor_out = [a[0] for a in actor_out]
        values = values[0]
        if self.is_continuous:
            mean, log_std = jnp.split(actor_out[0], 2, axis=-1)
            dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
            actions = dist.sample(key)
            if self.distribution == "tanh_normal":
                tanh_actions = safetanh(actions, _EPS)
                logprob = dist.log_prob(actions) - _tanh_correction(tanh_actions)
                actions = tanh_actions
            else:
                logprob = dist.log_prob(actions)
            return actions, actions, logprob[..., None], values, carry, next_key
        actions = []
        real_actions = []
        logprobs = []
        keys = jax.random.split(key, len(actor_out))
        for logits, k in zip(actor_out, keys):
            dist = OneHotCategorical(logits=logits)
            a = dist.sample(k)
            actions.append(a)
            real_actions.append(jnp.argmax(a, axis=-1))
            logprobs.append(dist.log_prob(a))
        return (
            jnp.concatenate(actions, -1),
            jnp.stack(real_actions, -1),
            jnp.stack(logprobs, -1).sum(-1, keepdims=True),
            values,
            carry,
            next_key,
        )

    def get_values(self, params: Any, obs: Dict[str, jax.Array], prev_actions: jax.Array, carry) -> jax.Array:
        obs = normalize_obs(obs, self.cnn_keys, list(obs.keys()))
        obs = {k: v[None] for k, v in obs.items()}
        zeros = jnp.zeros((1, prev_actions.shape[0], 1), jnp.float32)
        _, values, _ = self.module.apply(params, obs, prev_actions[None], carry, zeros)
        return values[0]

    def get_actions(
        self,
        params: Any,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        carry,
        key: Optional[jax.Array] = None,
        greedy: bool = False,
    ):
        """Env-facing actions + carry (test/eval path)."""
        obs = normalize_obs(obs, self.cnn_keys, list(obs.keys()))
        obs = {k: v[None] for k, v in obs.items()}
        zeros = jnp.zeros((1, prev_actions.shape[0], 1), jnp.float32)
        actor_out, _, carry = self.module.apply(params, obs, prev_actions[None], carry, zeros)
        actor_out = [a[0] for a in actor_out]
        if self.is_continuous:
            mean, log_std = jnp.split(actor_out[0], 2, axis=-1)
            if greedy:
                actions = mean
            else:
                actions = Independent(Normal(mean, jnp.exp(log_std)), 1).sample(key)
            if self.distribution == "tanh_normal":
                actions = safetanh(actions, _EPS)
            return actions, actions, carry
        actions = []
        real_actions = []
        keys = jax.random.split(key, len(actor_out)) if key is not None else [None] * len(actor_out)
        for logits, k in zip(actor_out, keys):
            dist = OneHotCategorical(logits=logits)
            a = dist.mode if greedy else dist.sample(k)
            actions.append(a)
            real_actions.append(jnp.argmax(a, axis=-1))
        return jnp.concatenate(actions, -1), jnp.stack(real_actions, -1), carry

    # ----------------------------------------------------------- training
    def evaluate_sequence(
        self,
        params: Any,
        obs: Dict[str, jax.Array],
        prev_actions: jax.Array,
        carry,
        prev_dones: jax.Array,
        actions: jax.Array,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """(logprobs[T,B,1], entropy[T,B,1], values[T,B,1]) for stored
        actions along a [T, B] sequence chunk."""
        actor_out, values, _ = self.module.apply(params, obs, prev_actions, carry, prev_dones)
        if self.is_continuous:
            mean, log_std = jnp.split(actor_out[0], 2, axis=-1)
            dist = Independent(Normal(mean, jnp.exp(log_std)), 1)
            if self.distribution == "tanh_normal":
                raw = safeatanh(actions, _EPS)
                logprob = dist.log_prob(raw) - _tanh_correction(actions)
            else:
                logprob = dist.log_prob(actions)
            return logprob[..., None], dist.entropy()[..., None], values
        logprobs = []
        entropies = []
        splits = np.cumsum(self.actions_dim)[:-1]
        per_dim_actions = jnp.split(actions, splits, axis=-1)
        for logits, act in zip(actor_out, per_dim_actions):
            dist = OneHotCategorical(logits=logits)
            logprobs.append(dist.log_prob(act))
            entropies.append(dist.entropy())
        return (
            jnp.stack(logprobs, -1).sum(-1, keepdims=True),
            jnp.stack(entropies, -1).sum(-1, keepdims=True),
            values,
        )


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    agent_state: Optional[Any] = None,
) -> Tuple[RecurrentPPOAgent, Any]:
    """Construct module + initial (or restored) params
    (reference: build_agent, agent.py:380-470)."""
    distribution = str(cfg.distribution.get("type", "auto")).lower()
    if distribution not in ("auto", "normal", "tanh_normal", "discrete"):
        raise ValueError(
            "The distribution must be on of: `auto`, `discrete`, `normal` and `tanh_normal`. "
            f"Found: {distribution}"
        )
    if distribution == "discrete" and is_continuous:
        raise ValueError("You have choose a discrete distribution but `is_continuous` is true")
    if distribution == "auto":
        distribution = "normal" if is_continuous else "discrete"

    module = RecurrentPPOModule(
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=is_continuous,
        cnn_keys=list(cfg.algo.cnn_keys.encoder),
        mlp_keys=list(cfg.algo.mlp_keys.encoder),
        encoder_cfg=dict(cfg.algo.encoder),
        rnn_cfg={
            "lstm": dict(cfg.algo.rnn.lstm),
            "pre_rnn_mlp": dict(cfg.algo.rnn.pre_rnn_mlp),
            "post_rnn_mlp": dict(cfg.algo.rnn.post_rnn_mlp),
        },
        actor_cfg=dict(cfg.algo.actor),
        critic_cfg=dict(cfg.algo.critic),
        dtype=runtime.precision.compute_dtype,
    )
    agent = RecurrentPPOAgent(
        module=module,
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=is_continuous,
        distribution=distribution,
        rnn_hidden_size=int(cfg.algo.rnn.lstm.hidden_size),
        cnn_keys=tuple(cfg.algo.cnn_keys.encoder),
    )
    if agent_state is not None:
        params = jax.tree_util.tree_map(jnp.asarray, agent_state)
    else:
        n = 1
        dummy_obs = {
            k: jnp.zeros((1, n, *obs_space[k].shape), jnp.float32)
            for k in list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
        }
        dummy_actions = jnp.zeros((1, n, int(np.sum(actions_dim))), jnp.float32)
        dummy_dones = jnp.zeros((1, n, 1), jnp.float32)
        params = module.init(
            runtime.root_key, dummy_obs, dummy_actions, agent.initial_states(n), dummy_dones
        )
    return agent, params

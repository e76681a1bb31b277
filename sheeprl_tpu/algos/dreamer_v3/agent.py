"""DreamerV3 agent (flax): world model (RSSM), actor, critic.

Capability parity with the reference agent
(sheeprl/algos/dreamer_v3/agent.py:42-1236), re-designed for XLA:

- The RSSM time loop is NOT here: `dynamic` / `imagination` are single-step
  pure methods; the training step scans them with `lax.scan` (the reference
  python-loops GRU cells, dreamer_v3.py:134-145 — SURVEY §7.2's #1 hazard).
- Pixels are NHWC end-to-end; the encoder/decoder convs are k4/s2/p1 stages
  exactly like the reference (agent.py:42-97, 154-226) but channel-last.
- Hafner initialization (agent.py:1170-1180; utils.py:143-186) maps onto
  `variance_scaling(fan_avg)` initializers — truncated-normal for trunks
  (jax applies the 0.8796 truncation std correction internally) and uniform
  for the special heads.
- The player is functional: its recurrent/stochastic/action state is an
  explicit pytree threaded through jitted steps, so the reference's stateful
  PlayerDV3 (agent.py:596-691) becomes `player_step(state, obs, key)` and
  reset is a masked lerp with the learned initial state.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import gymnasium
import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from sheeprl_tpu.models import MLP, CNN, DeCNN, LayerNorm, LayerNormGRUCell
from sheeprl_tpu.utils.distribution import (
    Independent,
    Normal,
    OneHotCategoricalStraightThrough,
    uniform_mix,
)
from sheeprl_tpu.utils.ops import symlog

# Hafner initializers (reference: dreamer_v3/utils.py:143-186). jax's
# truncated_normal variance-scaling already folds in the 0.87962566 std
# correction the reference applies by hand.
trunc_normal_init = jax.nn.initializers.variance_scaling(1.0, "fan_avg", "truncated_normal")


def uniform_init(scale: float):
    if scale == 0.0:
        return jax.nn.initializers.zeros
    return jax.nn.initializers.variance_scaling(scale, "fan_avg", "uniform")


def _ln_cfg(cfg: Dict[str, Any]) -> Tuple[Optional[str], Dict[str, Any]]:
    """Map a reference-style layer_norm config node {cls, kw} to (norm_layer,
    norm_args) for the model library; Identity cls → no norm + biased layers."""
    cls = str(cfg.get("cls", "")).lower()
    if "identity" in cls or cls in ("", "none", "null"):
        return None, {}
    return "layer_norm", dict(cfg.get("kw", {"eps": 1e-3}))


class CNNEncoder(nn.Module):
    """Stage-halving conv encoder, NHWC (reference: agent.py:42-97):
    `stages` convs k4/s2/p1 with channels [1,2,4,8,...]*multiplier, LN+SiLU,
    64x64 → 4x4, flattened."""

    keys: Sequence[str]
    channels_multiplier: int
    stages: int = 4
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate([obs[k] for k in self.keys], axis=-1)
        x = CNN(
            hidden_channels=[(2**i) * self.channels_multiplier for i in range(self.stages)],
            layer_args={"kernel_size": 4, "stride": 2, "padding": 1, "bias": self.layer_norm is None},
            activation=self.activation,
            norm_layer=self.layer_norm,
            norm_args=self.layer_norm_kw or {"eps": 1e-3},
            kernel_init=trunc_normal_init,
            dtype=self.dtype,
            name="model",
        )(x)
        return x.reshape(*x.shape[:-3], -1)


class MLPEncoder(nn.Module):
    """Symlog-squashed vector encoder (reference: agent.py:100-151)."""

    keys: Sequence[str]
    mlp_layers: int = 4
    dense_units: int = 512
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    symlog_inputs: bool = True
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, obs: Dict[str, jax.Array]) -> jax.Array:
        x = jnp.concatenate(
            [symlog(obs[k]) if self.symlog_inputs else obs[k] for k in self.keys], axis=-1
        )
        return MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_args={"bias": self.layer_norm is None},
            norm_layer=self.layer_norm,
            norm_args=self.layer_norm_kw or {"eps": 1e-3},
            kernel_init=trunc_normal_init,
            dtype=self.dtype,
            name="model",
        )(x)


class CNNDecoder(nn.Module):
    """Inverse of CNNEncoder: latent → Linear → [4,4,C] → transposed convs →
    per-key HWC reconstructions (reference: agent.py:154-226)."""

    keys: Sequence[str]
    output_channels: Sequence[int]
    channels_multiplier: int
    cnn_encoder_output_dim: int
    image_size: Tuple[int, int]
    stages: int = 4
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        batch_shape = latent_states.shape[:-1]
        x = nn.Dense(
            self.cnn_encoder_output_dim, kernel_init=trunc_normal_init, dtype=self.dtype, name="fc"
        )(latent_states)
        x = x.reshape(-1, 4, 4, self.cnn_encoder_output_dim // 16)
        out_ch = int(sum(self.output_channels))
        hidden = [(2**i) * self.channels_multiplier for i in reversed(range(self.stages - 1))] + [out_ch]
        x = DeCNN(
            hidden_channels=hidden,
            layer_args=[
                {"kernel_size": 4, "stride": 2, "padding": 1, "bias": self.layer_norm is None}
                for _ in range(self.stages - 1)
            ]
            + [{"kernel_size": 4, "stride": 2, "padding": 1}],
            activation=[self.activation] * (self.stages - 1) + [None],
            norm_layer=[self.layer_norm] * (self.stages - 1) + [None],
            norm_args=[self.layer_norm_kw or {"eps": 1e-3}] * (self.stages - 1) + [None],
            kernel_init=[trunc_normal_init] * (self.stages - 1) + [uniform_init(1.0)],
            dtype=self.dtype,
            name="model",
        )(x)
        x = x.reshape(*batch_shape, *self.image_size, out_ch)
        splits = np.cumsum(self.output_channels)[:-1]
        return {k: v for k, v in zip(self.keys, jnp.split(x, splits, axis=-1))}


class MLPDecoder(nn.Module):
    """Inverse of MLPEncoder: shared trunk + one linear head per key
    (reference: agent.py:229-278)."""

    keys: Sequence[str]
    output_dims: Sequence[int]
    mlp_layers: int = 4
    dense_units: int = 512
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_args={"bias": self.layer_norm is None},
            norm_layer=self.layer_norm,
            norm_args=self.layer_norm_kw or {"eps": 1e-3},
            kernel_init=trunc_normal_init,
            dtype=self.dtype,
            name="model",
        )(latent_states)
        return {
            k: nn.Dense(dim, kernel_init=uniform_init(1.0), dtype=self.dtype, name=f"head_{i}")(x)
            for i, (k, dim) in enumerate(zip(self.keys, self.output_dims))
        }


class RecurrentModel(nn.Module):
    """Dense+LN+SiLU projection into a LayerNormGRUCell
    (reference: agent.py:281-341)."""

    recurrent_state_size: int
    dense_units: int
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, recurrent_state: jax.Array) -> jax.Array:
        feat = MLP(
            hidden_sizes=[self.dense_units],
            activation=self.activation,
            layer_args={"bias": self.layer_norm is None},
            norm_layer=self.layer_norm,
            norm_args=self.layer_norm_kw or {"eps": 1e-3},
            kernel_init=trunc_normal_init,
            dtype=self.dtype,
            name="mlp",
        )(x)
        return LayerNormGRUCell(
            hidden_size=self.recurrent_state_size, bias=False, layer_norm=True, dtype=self.dtype, name="rnn"
        )(recurrent_state, feat)


def compute_stochastic_state(
    logits: jax.Array, discrete: int, key: Optional[jax.Array] = None, sample: bool = True
) -> jax.Array:
    """Sample (straight-through) or take the mode of the [..., stoch, discrete]
    categorical state (reference: dreamer_v2/utils.py:44-61). Input logits are
    flat [..., stoch*discrete]; output keeps the [..., stoch, discrete] shape.
    """
    logits = logits.reshape(*logits.shape[:-1], -1, discrete)
    dist = OneHotCategoricalStraightThrough(logits=logits)
    return dist.rsample(key) if sample else dist.mode


class WorldModel(nn.Module):
    """Encoder + RSSM + decoders + reward/continue heads as ONE module with
    method-based apply (reference: WorldModel container at
    dreamer_v2/agent.py:707-733 + RSSM at dreamer_v3/agent.py:344-498).

    The stochastic state travels FLAT ([..., stoch*discrete]); reshaping to
    [stoch, discrete] happens only inside sampling/KL.
    """

    # observation space metadata
    cnn_keys: Sequence[str]
    mlp_keys: Sequence[str]
    cnn_input_channels: Sequence[int]
    mlp_input_dims: Sequence[int]
    image_size: Tuple[int, int]
    actions_dim: Sequence[int]
    # architecture (mirrors cfg.algo.world_model)
    stochastic_size: int = 32
    discrete_size: int = 32
    recurrent_state_size: int = 4096
    recurrent_dense_units: int = 1024
    transition_hidden_size: int = 1024
    representation_hidden_size: int = 1024
    encoder_cnn_channels_multiplier: int = 96
    encoder_mlp_layers: int = 5
    encoder_dense_units: int = 1024
    decoder_cnn_channels_multiplier: int = 96
    decoder_mlp_layers: int = 5
    decoder_dense_units: int = 1024
    reward_bins: int = 255
    reward_mlp_layers: int = 5
    reward_dense_units: int = 1024
    continue_mlp_layers: int = 5
    continue_dense_units: int = 1024
    cnn_stages: int = 4
    cnn_act: str = "silu"
    dense_act: str = "silu"
    cnn_layer_norm: Optional[str] = "layer_norm"
    cnn_layer_norm_kw: Optional[Dict[str, Any]] = None
    mlp_layer_norm: Optional[str] = "layer_norm"
    mlp_layer_norm_kw: Optional[Dict[str, Any]] = None
    unimix: float = 0.01
    learnable_initial_recurrent_state: bool = True
    decoupled_rssm: bool = False
    dtype: Any = jnp.float32

    @property
    def stoch_state_size(self) -> int:
        return self.stochastic_size * self.discrete_size

    @property
    def latent_state_size(self) -> int:
        return self.stoch_state_size + self.recurrent_state_size

    def setup(self) -> None:
        mlp_ln_kw = self.mlp_layer_norm_kw or {"eps": 1e-3}
        cnn_ln_kw = self.cnn_layer_norm_kw or {"eps": 1e-3}
        self.cnn_encoder = (
            CNNEncoder(
                keys=self.cnn_keys,
                channels_multiplier=self.encoder_cnn_channels_multiplier,
                stages=self.cnn_stages,
                activation=self.cnn_act,
                layer_norm=self.cnn_layer_norm,
                layer_norm_kw=cnn_ln_kw,
                dtype=self.dtype,
            )
            if len(self.cnn_keys) > 0
            else None
        )
        self.mlp_encoder = (
            MLPEncoder(
                keys=self.mlp_keys,
                mlp_layers=self.encoder_mlp_layers,
                dense_units=self.encoder_dense_units,
                activation=self.dense_act,
                layer_norm=self.mlp_layer_norm,
                layer_norm_kw=mlp_ln_kw,
                dtype=self.dtype,
            )
            if len(self.mlp_keys) > 0
            else None
        )
        self.recurrent_model = RecurrentModel(
            recurrent_state_size=self.recurrent_state_size,
            dense_units=self.recurrent_dense_units,
            activation=self.dense_act,
            layer_norm=self.mlp_layer_norm,
            layer_norm_kw=mlp_ln_kw,
            dtype=self.dtype,
        )
        self.representation_model = MLP(
            hidden_sizes=[self.representation_hidden_size],
            output_dim=self.stoch_state_size,
            activation=self.dense_act,
            layer_args={"bias": self.mlp_layer_norm is None},
            norm_layer=self.mlp_layer_norm,
            norm_args=mlp_ln_kw,
            kernel_init=trunc_normal_init,
            output_kernel_init=uniform_init(1.0),
            dtype=self.dtype,
        )
        self.transition_model = MLP(
            hidden_sizes=[self.transition_hidden_size],
            output_dim=self.stoch_state_size,
            activation=self.dense_act,
            layer_args={"bias": self.mlp_layer_norm is None},
            norm_layer=self.mlp_layer_norm,
            norm_args=mlp_ln_kw,
            kernel_init=trunc_normal_init,
            output_kernel_init=uniform_init(1.0),
            dtype=self.dtype,
        )
        cnn_encoder_output_dim = (
            (2 ** (self.cnn_stages - 1)) * self.encoder_cnn_channels_multiplier * 4 * 4
        )
        self.cnn_decoder = (
            CNNDecoder(
                keys=self.cnn_keys,
                output_channels=self.cnn_input_channels,
                channels_multiplier=self.decoder_cnn_channels_multiplier,
                cnn_encoder_output_dim=cnn_encoder_output_dim,
                image_size=self.image_size,
                stages=self.cnn_stages,
                activation=self.cnn_act,
                layer_norm=self.cnn_layer_norm,
                layer_norm_kw=cnn_ln_kw,
                dtype=self.dtype,
            )
            if len(self.cnn_keys) > 0
            else None
        )
        self.mlp_decoder = (
            MLPDecoder(
                keys=self.mlp_keys,
                output_dims=self.mlp_input_dims,
                mlp_layers=self.decoder_mlp_layers,
                dense_units=self.decoder_dense_units,
                activation=self.dense_act,
                layer_norm=self.mlp_layer_norm,
                layer_norm_kw=mlp_ln_kw,
                dtype=self.dtype,
            )
            if len(self.mlp_keys) > 0
            else None
        )
        self.reward_model = MLP(
            hidden_sizes=[self.reward_dense_units] * self.reward_mlp_layers,
            output_dim=self.reward_bins,
            activation=self.dense_act,
            layer_args={"bias": self.mlp_layer_norm is None},
            norm_layer=self.mlp_layer_norm,
            norm_args=mlp_ln_kw,
            kernel_init=trunc_normal_init,
            output_kernel_init=uniform_init(0.0),
            dtype=self.dtype,
        )
        self.continue_model = MLP(
            hidden_sizes=[self.continue_dense_units] * self.continue_mlp_layers,
            output_dim=1,
            activation=self.dense_act,
            layer_args={"bias": self.mlp_layer_norm is None},
            norm_layer=self.mlp_layer_norm,
            norm_args=mlp_ln_kw,
            kernel_init=trunc_normal_init,
            output_kernel_init=uniform_init(1.0),
            dtype=self.dtype,
        )
        self.initial_recurrent_state = self.param(
            "initial_recurrent_state",
            jax.nn.initializers.zeros,
            (self.recurrent_state_size,),
            jnp.float32,
        )

    # --------------------------------------------------------------- encoder
    def embed_obs(self, obs: Dict[str, jax.Array]) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs))
        return jnp.concatenate(outs, axis=-1) if len(outs) > 1 else outs[0]

    # ------------------------------------------------------------------ rssm
    def _uniform_mix(self, logits: jax.Array) -> jax.Array:
        logits = logits.reshape(*logits.shape[:-1], -1, self.discrete_size)
        logits = uniform_mix(logits, self.unimix)
        return logits.reshape(*logits.shape[:-2], -1)

    def _representation(
        self, recurrent_state: jax.Array, embedded_obs: jax.Array, key: Optional[jax.Array]
    ) -> Tuple[jax.Array, jax.Array]:
        """(logits, sampled posterior) (reference: agent.py:451-465). With the
        decoupled RSSM the recurrent state is not an input (agent.py:582-593)."""
        if self.decoupled_rssm:
            x = embedded_obs
        else:
            x = jnp.concatenate([recurrent_state, embedded_obs], axis=-1)
        logits = self._uniform_mix(self.representation_model(x))
        post = compute_stochastic_state(logits, self.discrete_size, key)
        return logits, post.reshape(*post.shape[:-2], -1)

    def _transition(
        self, recurrent_out: jax.Array, key: Optional[jax.Array], sample_state: bool = True
    ) -> Tuple[jax.Array, jax.Array]:
        """(logits, sampled/mode prior) (reference: agent.py:467-480)."""
        logits = self._uniform_mix(self.transition_model(recurrent_out))
        prior = compute_stochastic_state(logits, self.discrete_size, key, sample=sample_state)
        return logits, prior.reshape(*prior.shape[:-2], -1)

    def get_initial_states(self, batch_shape: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        """tanh'd learned initial recurrent state + its prior mode
        (reference: agent.py:391-394)."""
        h0 = jnp.tanh(self.initial_recurrent_state.astype(self.dtype))
        h0 = jnp.broadcast_to(h0, (*batch_shape, h0.shape[-1]))
        _, z0 = self._transition(h0, key=None, sample_state=False)
        return h0, z0

    def dynamic(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        embedded_obs: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
        initial_states: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
        """One step of dynamic learning (reference: agent.py:396-435):
        is_first reset-mix (zeroed action, learned initial h/z), GRU step,
        prior from transition, posterior from representation.
        All states are FLAT; batch leading dim only (the time loop is the
        caller's lax.scan, which passes `initial_states` so that
        `get_initial_states` runs once per sequence, not once per step)."""
        k1, k2 = jax.random.split(key)
        action = (1 - is_first) * action
        if initial_states is None:
            initial_states = self.get_initial_states(recurrent_state.shape[:-1])
        h0, z0 = initial_states
        recurrent_state = (1 - is_first) * recurrent_state + is_first * h0
        posterior = (1 - is_first) * posterior + is_first * z0
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits, prior = self._transition(recurrent_state, k1)
        posterior_logits, posterior = self._representation(recurrent_state, embedded_obs, k2)
        return recurrent_state, posterior, prior, posterior_logits, prior_logits

    def posterior_obs_only(
        self, embedded_obs: jax.Array, key: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """Decoupled-RSSM posterior: obs-only, so it vectorizes over the whole
        [T, B] sequence as one batched matmul instead of T scan steps
        (reference: DecoupledRSSM._representation, agent.py:583-593)."""
        logits = self._uniform_mix(self.representation_model(embedded_obs))
        post = compute_stochastic_state(logits, self.discrete_size, key)
        return logits, post.reshape(*post.shape[:-2], -1)

    def dynamic_decoupled(
        self,
        posterior: jax.Array,
        recurrent_state: jax.Array,
        action: jax.Array,
        is_first: jax.Array,
        key: jax.Array,
        initial_states: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
        """One decoupled dynamic step (reference: DecoupledRSSM.dynamic,
        agent.py:542-581): the posterior arrives precomputed (obs-only), so
        only the recurrent state and the prior are produced here."""
        action = (1 - is_first) * action
        if initial_states is None:
            initial_states = self.get_initial_states(recurrent_state.shape[:-1])
        h0, z0 = initial_states
        recurrent_state = (1 - is_first) * recurrent_state + is_first * h0
        posterior = (1 - is_first) * posterior + is_first * z0
        recurrent_state = self.recurrent_model(
            jnp.concatenate([posterior, action], -1), recurrent_state
        )
        prior_logits, prior = self._transition(recurrent_state, key)
        return recurrent_state, prior, prior_logits

    def imagination(
        self, prior: jax.Array, recurrent_state: jax.Array, actions: jax.Array, key: jax.Array
    ) -> Tuple[jax.Array, jax.Array]:
        """One-step latent imagination (reference: agent.py:482-498)."""
        recurrent_state = self.recurrent_model(
            jnp.concatenate([prior, actions], -1), recurrent_state
        )
        _, imagined_prior = self._transition(recurrent_state, key)
        return imagined_prior, recurrent_state

    # ----------------------------------------------------------------- heads
    def decode(self, latent_states: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(latent_states))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(latent_states))
        return out

    def reward_logits(self, latent_states: jax.Array) -> jax.Array:
        return self.reward_model(latent_states)

    def continue_logits(self, latent_states: jax.Array) -> jax.Array:
        return self.continue_model(latent_states)

    def __call__(self, obs: Dict[str, jax.Array], actions: jax.Array, key: jax.Array):
        """Init-only pass touching every submodule once."""
        embedded = self.embed_obs(obs)
        batch = embedded.shape[:-1]
        h0, z0 = self.get_initial_states(batch)
        h, post, prior, post_logits, prior_logits = self.dynamic(
            z0, h0, actions, embedded, jnp.zeros((*batch, 1), self.dtype), key
        )
        latent = jnp.concatenate([post, h], -1)
        return self.decode(latent), self.reward_logits(latent), self.continue_logits(latent)


class Actor(nn.Module):
    """DV3 actor: MLP trunk + one head per action dim; discrete actions use
    1%-unimix straight-through categoricals, continuous use normal variants
    (reference: agent.py:694-845). Returns raw head outputs; sampling and
    distributions live in `actor_forward` so PRNG keys stay explicit."""

    actions_dim: Sequence[int]
    is_continuous: bool
    dense_units: int = 1024
    mlp_layers: int = 5
    activation: str = "silu"
    layer_norm: Optional[str] = "layer_norm"
    layer_norm_kw: Optional[Dict[str, Any]] = None
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, state: jax.Array) -> List[jax.Array]:
        x = MLP(
            hidden_sizes=[self.dense_units] * self.mlp_layers,
            activation=self.activation,
            layer_args={"bias": self.layer_norm is None},
            norm_layer=self.layer_norm,
            norm_args=self.layer_norm_kw or {"eps": 1e-3},
            kernel_init=trunc_normal_init,
            dtype=self.dtype,
            name="model",
        )(state)
        if self.is_continuous:
            return [
                nn.Dense(
                    int(np.sum(self.actions_dim)) * 2,
                    kernel_init=uniform_init(1.0),
                    dtype=self.dtype,
                    name="head_0",
                )(x)
            ]
        return [
            nn.Dense(dim, kernel_init=uniform_init(1.0), dtype=self.dtype, name=f"head_{i}")(x)
            for i, dim in enumerate(self.actions_dim)
        ]


@dataclass(frozen=True)
class ActorSpec:
    """Distribution metadata for the actor head outputs
    (reference Actor attributes: agent.py:746-781).

    ``mask_mode`` selects env-provided action masking at sampling time:
    "minedojo" applies the MineDojo mask protocol (the reference subclasses
    the module as MinedojoActor, agent.py:848-932; here the module is
    unchanged and masking is a pure transform in `actor_forward`)."""

    actions_dim: Tuple[int, ...]
    is_continuous: bool
    distribution: str  # discrete | scaled_normal | tanh_normal | normal
    init_std: float = 2.0
    min_std: float = 0.1
    max_std: float = 1.0
    unimix: float = 0.01
    action_clip: float = 1.0
    mask_mode: str = "none"  # none | minedojo


def _continuous_dist(pre_dist: jax.Array, spec: ActorSpec):
    mean, std = jnp.split(pre_dist, 2, axis=-1)
    if spec.distribution == "tanh_normal":
        mean = 5 * jnp.tanh(mean / 5)
        std = jax.nn.softplus(std + spec.init_std) + spec.min_std
        return Independent(Normal(mean, std), 1), True  # tanh-transformed
    if spec.distribution == "normal":
        return Independent(Normal(mean, std), 1), False
    # scaled_normal (the continuous default, agent.py:813-816)
    std = (spec.max_std - spec.min_std) * jax.nn.sigmoid(std + spec.init_std) + spec.min_std
    return Independent(Normal(jnp.tanh(mean), std), 1), False


# Finite stand-in for -inf on masked logits: softmax underflows it to an
# exact 0 probability, but entropies/log-probs of the distribution stay
# finite (torch's -inf would make entropy NaN on the masked support).
_MASK_NEG = -1e9

# MineDojo flattened functional-action ids (envs/minedojo.py ACTION_MAP;
# reference MinedojoActor hardcodes the same ids, agent.py:905-925).
_MINEDOJO_CRAFT = 15
_MINEDOJO_EQUIP = 16
_MINEDOJO_PLACE = 17
_MINEDOJO_DESTROY = 18


def _minedojo_mask_head(
    i: int, logits: jax.Array, functional_action: Optional[jax.Array], mask: Dict[str, jax.Array]
) -> jax.Array:
    """Mask one MineDojo head's logits (vectorized analog of the reference's
    per-(t,b) python loops, agent.py:903-925):

    - head 0 (action type): invalid action ids are masked out always;
    - head 1 (craft arg): masked by mask_craft_smelt only where head 0
      sampled the craft action;
    - head 2 (inventory arg): masked by mask_equip_place where head 0
      sampled equip/place, by mask_destroy where it sampled destroy.
    """

    def valid(name: str) -> jax.Array:
        return jnp.asarray(mask[name]) > 0.5

    if i == 0:
        return jnp.where(valid("mask_action_type"), logits, _MASK_NEG)
    if i == 1:
        craft = (functional_action == _MINEDOJO_CRAFT)[..., None]
        return jnp.where(craft & ~valid("mask_craft_smelt"), _MASK_NEG, logits)
    if i == 2:
        equip_place = (
            (functional_action == _MINEDOJO_EQUIP) | (functional_action == _MINEDOJO_PLACE)
        )[..., None]
        destroy = (functional_action == _MINEDOJO_DESTROY)[..., None]
        logits = jnp.where(equip_place & ~valid("mask_equip_place"), _MASK_NEG, logits)
        return jnp.where(destroy & ~valid("mask_destroy"), _MASK_NEG, logits)
    return logits


def actor_forward(
    pre_dist: List[jax.Array],
    spec: ActorSpec,
    key: Optional[jax.Array] = None,
    greedy: bool = False,
    mask: Optional[Dict[str, jax.Array]] = None,
) -> Tuple[List[jax.Array], List[Any]]:
    """Turn head outputs into (sampled actions, distributions)
    (reference: Actor.forward, agent.py:783-837; with ``mask`` the MineDojo
    masking of MinedojoActor.forward, agent.py:848-932)."""
    if spec.is_continuous:
        dist, tanh_transformed = _continuous_dist(pre_dist[0], spec)
        if not greedy:
            actions = dist.rsample(key)
        else:
            # Reference mode approximation: 100 samples, argmax log-prob
            # (agent.py:819-822).
            sample = dist.sample(key, (100,))
            log_prob = dist.log_prob(sample)
            idx = jnp.argmax(log_prob, axis=0)
            actions = jnp.take_along_axis(sample, idx[None, ..., None], axis=0)[0]
        if tanh_transformed:
            actions = jnp.tanh(actions)
        if spec.action_clip > 0.0:
            clip = jnp.full_like(actions, spec.action_clip)
            actions = actions * jax.lax.stop_gradient(clip / jnp.maximum(clip, jnp.abs(actions)))
        return [actions], [dist]
    dists = []
    actions = []
    functional_action = None
    keys = jax.random.split(key, len(pre_dist)) if key is not None else [None] * len(pre_dist)
    for i, (logits, k) in enumerate(zip(pre_dist, keys)):
        logits = uniform_mix(logits, spec.unimix)
        if mask is not None and spec.mask_mode == "minedojo":
            logits = _minedojo_mask_head(i, logits, functional_action, mask)
        d = OneHotCategoricalStraightThrough(logits=logits)
        dists.append(d)
        actions.append(d.mode if greedy else d.rsample(k))
        if functional_action is None:
            # Sequential head dependency: later heads are masked according to
            # the action TYPE the first head actually sampled.
            functional_action = jnp.argmax(actions[0], axis=-1)
    return actions, dists


def continuous_log_prob_and_entropy(dist, actions: jax.Array, spec: ActorSpec):
    """log-prob/entropy for continuous actor dists; tanh_normal entropy is
    unavailable (reference falls back to zeros, dreamer_v3.py:293-296)."""
    if spec.distribution == "tanh_normal":
        raw = jnp.arctanh(jnp.clip(actions, -1 + 1e-6, 1 - 1e-6))
        log_prob = dist.log_prob(raw) - (2.0 * (jnp.log(2.0) - raw - jax.nn.softplus(-2.0 * raw))).sum(-1)
        return log_prob, None
    return dist.log_prob(actions), dist.entropy()


def build_world_model_module(cfg: Dict[str, Any], obs_space, actions_dim, dtype) -> WorldModel:
    wm_cfg = cfg.algo.world_model
    cnn_keys = list(cfg.algo.cnn_keys.encoder)
    mlp_keys = list(cfg.algo.mlp_keys.encoder)
    cnn_stages = int(np.log2(cfg.env.screen_size) - np.log2(4))
    cnn_ln, cnn_ln_kw = _ln_cfg(cfg.algo.get("cnn_layer_norm", {}))
    mlp_ln, mlp_ln_kw = _ln_cfg(cfg.algo.get("mlp_layer_norm", {}))
    return WorldModel(
        cnn_keys=tuple(cnn_keys),
        mlp_keys=tuple(mlp_keys),
        cnn_input_channels=tuple(int(obs_space[k].shape[-1]) for k in cnn_keys),
        mlp_input_dims=tuple(int(obs_space[k].shape[0]) for k in mlp_keys),
        image_size=tuple(obs_space[cnn_keys[0]].shape[:2]) if cnn_keys else (64, 64),
        actions_dim=tuple(actions_dim),
        stochastic_size=wm_cfg.stochastic_size,
        discrete_size=wm_cfg.discrete_size,
        recurrent_state_size=wm_cfg.recurrent_model.recurrent_state_size,
        recurrent_dense_units=wm_cfg.recurrent_model.dense_units,
        transition_hidden_size=wm_cfg.transition_model.hidden_size,
        representation_hidden_size=wm_cfg.representation_model.hidden_size,
        encoder_cnn_channels_multiplier=wm_cfg.encoder.cnn_channels_multiplier,
        encoder_mlp_layers=wm_cfg.encoder.mlp_layers,
        encoder_dense_units=wm_cfg.encoder.dense_units,
        decoder_cnn_channels_multiplier=wm_cfg.observation_model.cnn_channels_multiplier,
        decoder_mlp_layers=wm_cfg.observation_model.mlp_layers,
        decoder_dense_units=wm_cfg.observation_model.dense_units,
        reward_bins=wm_cfg.reward_model.bins,
        reward_mlp_layers=wm_cfg.reward_model.mlp_layers,
        reward_dense_units=wm_cfg.reward_model.dense_units,
        continue_mlp_layers=wm_cfg.discount_model.mlp_layers,
        continue_dense_units=wm_cfg.discount_model.dense_units,
        cnn_stages=cnn_stages,
        cnn_act="silu",
        dense_act="silu",
        cnn_layer_norm=cnn_ln,
        cnn_layer_norm_kw=cnn_ln_kw,
        mlp_layer_norm=mlp_ln,
        mlp_layer_norm_kw=mlp_ln_kw,
        unimix=cfg.algo.unimix,
        learnable_initial_recurrent_state=wm_cfg.learnable_initial_recurrent_state,
        decoupled_rssm=wm_cfg.decoupled_rssm,
        dtype=dtype,
    )


@dataclass(frozen=True)
class DV3Agent:
    """Bundles the three modules + metadata; params live in the train state
    {world_model, actor, critic, target_critic}."""

    world_model: WorldModel
    actor: Actor
    critic: Any  # MLP
    actor_spec: ActorSpec
    actions_dim: Tuple[int, ...]
    is_continuous: bool

    # method-based applies
    def wm(self, params, *args, method: str):
        return self.world_model.apply(params, *args, method=getattr(WorldModel, method))

    def critic_logits(self, params, latent: jax.Array) -> jax.Array:
        return self.critic.apply(params, latent)

    def actor_pre_dist(self, params, latent: jax.Array) -> List[jax.Array]:
        return self.actor.apply(params, latent)

    # ---------------------------------------------------------------- player
    def init_player_state(self, wm_params, n_envs: int) -> Dict[str, jax.Array]:
        """Fresh player state for all envs (reference: PlayerDV3.init_states,
        agent.py:643-659)."""
        h0, z0 = self.wm(wm_params, (n_envs,), method="get_initial_states")
        return {
            "recurrent_state": h0,
            "stochastic_state": z0,
            "actions": jnp.zeros((n_envs, int(np.sum(self.actions_dim))), h0.dtype),
        }

    def reset_player_state(
        self, wm_params, state: Dict[str, jax.Array], reset_mask: jax.Array
    ) -> Dict[str, jax.Array]:
        """Masked reset: envs with reset_mask=1 get fresh initial states."""
        fresh = self.init_player_state(wm_params, state["recurrent_state"].shape[0])
        m = reset_mask[..., None]
        return {k: (1 - m) * state[k] + m * fresh[k] for k in state}

    def player_step(
        self,
        wm_params,
        actor_params,
        state: Dict[str, jax.Array],
        obs: Dict[str, jax.Array],
        key: jax.Array,
        greedy: bool = False,
    ):
        """One acting step (reference: PlayerDV3.get_actions, agent.py:661-691):
        embed obs → GRU step with previous (z, a) → posterior → actor sample.
        Returns (actions_cat, real_actions, new_state). With a mask-aware
        actor (spec.mask_mode), the env-provided mask_* observations gate the
        sampled actions (reference: dreamer_v3.py:574-577)."""
        mask = None
        if self.actor_spec.mask_mode != "none":
            mask = {k: v for k, v in obs.items() if k.startswith("mask")} or None
            if mask is None:
                # Obs keys are static, so this fires at trace time, not per
                # step: a mask-aware actor on an env without mask_* obs is a
                # misconfiguration that would otherwise silently run unmasked.
                import warnings

                warnings.warn(
                    f"algo.actor.cls={self.actor_spec.mask_mode!r} but the observations "
                    f"carry no mask_* keys ({sorted(obs)}); actions will NOT be masked. "
                    "Add the mask keys to algo.mlp_keys.encoder (see exp/dreamer_v3_minedojo.yaml)."
                )
            elif self.actor_spec.mask_mode == "minedojo":
                required = {"mask_action_type", "mask_craft_smelt", "mask_equip_place", "mask_destroy"}
                missing = required - set(mask)
                if missing:
                    raise ValueError(
                        f"algo.actor.cls=minedojo needs all of {sorted(required)} in the "
                        f"observations; missing {sorted(missing)} — add them to "
                        "algo.mlp_keys.encoder (see exp/dreamer_v3_minedojo.yaml)."
                    )
        k1, k2 = jax.random.split(key)
        embedded = self.wm(wm_params, obs, method="embed_obs")
        recurrent_state = self.world_model.apply(
            wm_params,
            jnp.concatenate([state["stochastic_state"], state["actions"]], -1),
            state["recurrent_state"],
            method=lambda wm, x, h: wm.recurrent_model(x, h),
        )
        _, stochastic_state = self.world_model.apply(
            wm_params, recurrent_state, embedded, k1, method=WorldModel._representation
        )
        latent = jnp.concatenate([stochastic_state, recurrent_state], -1)
        pre_dist = self.actor.apply(actor_params, latent)
        actions, _ = actor_forward(pre_dist, self.actor_spec, k2, greedy, mask=mask)
        actions_cat = jnp.concatenate(actions, -1)
        if self.is_continuous:
            real_actions = actions_cat
        else:
            real_actions = jnp.stack([jnp.argmax(a, -1) for a in actions], -1)
        new_state = {
            "recurrent_state": recurrent_state,
            "stochastic_state": stochastic_state,
            "actions": actions_cat,
        }
        return actions_cat, real_actions, new_state


def build_agent(
    runtime,
    actions_dim: Sequence[int],
    is_continuous: bool,
    cfg: Dict[str, Any],
    obs_space: gymnasium.spaces.Dict,
    world_model_state: Optional[Any] = None,
    actor_state: Optional[Any] = None,
    critic_state: Optional[Any] = None,
    target_critic_state: Optional[Any] = None,
) -> Tuple[DV3Agent, Dict[str, Any]]:
    """Construct modules + initial (or restored) params
    (reference: build_agent, agent.py:935-1236; no Fabric setup/weight-tying —
    the player shares the same param trees)."""
    dtype = runtime.precision.compute_dtype
    distribution = str(cfg.distribution.get("type", "auto")).lower()
    if distribution not in ("auto", "normal", "tanh_normal", "discrete", "scaled_normal"):
        raise ValueError(
            "The distribution must be on of: `auto`, `discrete`, `normal`, `tanh_normal` and `scaled_normal`. "
            f"Found: {distribution}"
        )
    if distribution == "discrete" and is_continuous:
        raise ValueError("You have choose a discrete distribution but `is_continuous` is true")
    if distribution == "auto":
        distribution = "scaled_normal" if is_continuous else "discrete"

    wm = build_world_model_module(cfg, obs_space, actions_dim, dtype)
    mlp_ln, mlp_ln_kw = _ln_cfg(cfg.algo.get("mlp_layer_norm", {}))
    actor = Actor(
        actions_dim=tuple(actions_dim),
        is_continuous=is_continuous,
        dense_units=cfg.algo.actor.dense_units,
        mlp_layers=cfg.algo.actor.mlp_layers,
        activation="silu",
        layer_norm=mlp_ln,
        layer_norm_kw=mlp_ln_kw,
        dtype=dtype,
    )
    critic = MLP(
        hidden_sizes=[cfg.algo.critic.dense_units] * cfg.algo.critic.mlp_layers,
        output_dim=cfg.algo.critic.bins,
        activation="silu",
        layer_args={"bias": mlp_ln is None},
        norm_layer=mlp_ln,
        norm_args=mlp_ln_kw,
        kernel_init=trunc_normal_init,
        output_kernel_init=uniform_init(0.0),
        dtype=dtype,
    )
    actor_cls = str(cfg.algo.actor.get("cls", "default") or "default").lower()
    if actor_cls not in ("default", "minedojo"):
        raise ValueError(f"algo.actor.cls must be one of default|minedojo, got {actor_cls!r}")
    spec = ActorSpec(
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=is_continuous,
        distribution=distribution,
        init_std=cfg.algo.actor.init_std,
        min_std=cfg.algo.actor.min_std,
        max_std=cfg.algo.actor.get("max_std", 1.0),
        unimix=cfg.algo.unimix,
        action_clip=cfg.algo.actor.action_clip,
        mask_mode="minedojo" if actor_cls == "minedojo" else "none",
    )
    agent = DV3Agent(
        world_model=wm,
        actor=actor,
        critic=critic,
        actor_spec=spec,
        actions_dim=tuple(int(d) for d in actions_dim),
        is_continuous=is_continuous,
    )

    k_wm, k_actor, k_critic, k_call = jax.random.split(runtime.root_key, 4)
    n = 1
    dummy_obs = {
        k: jnp.zeros((n, *obs_space[k].shape), jnp.float32)
        for k in list(cfg.algo.cnn_keys.encoder) + list(cfg.algo.mlp_keys.encoder)
    }
    dummy_actions = jnp.zeros((n, int(np.sum(actions_dim))), jnp.float32)
    latent_size = wm.latent_state_size

    if world_model_state is not None:
        wm_params = jax.tree_util.tree_map(jnp.asarray, world_model_state)
    else:
        wm_params = wm.init({"params": k_wm, "sample": k_call}, dummy_obs, dummy_actions, k_call)
    actor_params = (
        jax.tree_util.tree_map(jnp.asarray, actor_state)
        if actor_state is not None
        else actor.init(k_actor, jnp.zeros((n, latent_size), jnp.float32))
    )
    critic_params = (
        jax.tree_util.tree_map(jnp.asarray, critic_state)
        if critic_state is not None
        else critic.init(k_critic, jnp.zeros((n, latent_size), jnp.float32))
    )
    target_critic_params = (
        jax.tree_util.tree_map(jnp.asarray, target_critic_state)
        if target_critic_state is not None
        else jax.tree_util.tree_map(jnp.copy, critic_params)
    )
    state = {
        "world_model": wm_params,
        "actor": actor_params,
        "critic": critic_params,
        "target_critic": target_critic_params,
    }
    return agent, state

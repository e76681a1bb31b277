"""The token policy: a `models/transformer.py` backbone with a vocabulary head
and a value head, and the two forms it runs in.

- :meth:`PPOLMAgent.evaluate` is the update's form: whole left-padded
  sequences ``[prompt | response]`` through the whole-sequence attention, the
  heads only at the positions the loss reads (``P-1 .. P+R-2``, whose logits
  the response tokens were drawn from).
- :meth:`PPOLMAgent.prefill` / :meth:`PPOLMAgent.decode` are the player's
  forms over its state, the per-env latent cache:
  ``{"c": L x [E, T, kv_lora_rank], "kr": L x [E, T, qk_rope_head_dim],
  "pos": [E], "start": [E], "logits": [E, V]}``. ``start`` is where an env's
  context begins in its cache row (prompts are left-padded to ``P``), ``pos``
  the index its next token is written at, ``logits`` what its last token was
  drawn from. Prefill runs the envs named by ``reset`` through the
  whole-sequence form and fills their rows; decode feeds one token per env
  through the absorbed form. Both sample at temperature 1 from the softmax
  over the vocabulary the model holds.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.models.transformer import Transformer, TransformerConfig, _init, attention_is_fused, merge_moe_stats
from sheeprl_tpu.telemetry import scopes

HIGHEST = jax.lax.Precision.HIGHEST
#: Matrices that compute in float32 under every precision policy: the player's copy keeps them so.
FLOAT32_LEAVES = ("router", "value_head")
#: Prompts that share one block of float32 attention scores where the prefill's softmax runs in plain JAX (all of
#: them where they do not divide). The fused kernels make no such block: there every prompt goes through at once.
PREFILL_GROUP = 4


class LMPolicy(nn.Module):
    cfg: TransformerConfig
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.backbone = Transformer(c, self.dtype, self.param_dtype)
        self.head = self.param("head", _init(c), (c.hidden_size, c.vocab_size), self.param_dtype)
        self.value_head = self.param("value_head", _init(c), (c.hidden_size, 1), jnp.float32)

    def heads(self, hidden: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Float32 logits [..., V] and values [...] of hidden states [..., H] (before the final norm)."""
        normed = self.backbone.final_norm(hidden)
        logits = jnp.dot(normed, self.head.astype(self.dtype), preferred_element_type=jnp.float32)
        values = jnp.dot(normed.astype(jnp.float32), self.value_head, precision=HIGHEST)[..., 0]
        return logits, values

    def __call__(self, tokens: jax.Array, start: jax.Array, first: int, count: int):
        """Logits and values at the ``count`` positions from ``first`` on, and the expert layers' stats."""
        hidden, _, stats = self.backbone(tokens, start)
        with scopes.scope(scopes.LM_HEAD_LOSS):
            logits, values = self.heads(hidden[:, first:first + count])
        return logits, values, merge_moe_stats(stats)

    def prefill(self, tokens: jax.Array, start: jax.Array):
        hidden, kept, _ = self.backbone(tokens, start)
        logits, values = self.heads(hidden[:, -1])
        return logits, values, kept

    def decode(self, tokens: jax.Array, cache: Dict[str, Any], pos: jax.Array, start: jax.Array):
        hidden, cache = self.backbone.decode(tokens, cache, pos, start)
        logits, values = self.heads(hidden)
        return logits, values, cache


def _sample(logits: jax.Array, key: jax.Array, greedy: bool):
    token = jnp.argmax(logits, axis=-1) if greedy else jax.random.categorical(key, logits, axis=-1)
    logprob = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1), token[:, None], axis=-1)[:, 0]
    return token.astype(jnp.int32), logprob


class PPOLMAgent:
    """Functional wrapper over :class:`LMPolicy`: parameters are passed in."""

    def __init__(self, model: TransformerConfig, prompt_len: int, rollout_steps: int, dtype: Any, param_dtype: Any) -> None:
        self.model = model
        self.prompt_len = int(prompt_len)
        self.rollout_steps = int(rollout_steps)
        self.context = self.prompt_len + self.rollout_steps
        self.dtype = dtype
        self.module = LMPolicy(model, dtype, param_dtype)

    def init_params(self, key: jax.Array) -> Any:
        tokens, start = jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32)
        return jax.jit(lambda k: self.module.init(k, tokens, start, 0, 1))(key)

    def acting_params(self, params: Any) -> Any:
        """The player's copy: every matrix in the compute dtype (norm gains and the float32 parts stay)."""

        def cast(path, leaf):
            keep = leaf.ndim < 2 or getattr(path[-1], "key", None) in FLOAT32_LEAVES
            return leaf if keep else leaf.astype(self.dtype)

        return jax.tree_util.tree_map_with_path(cast, params)

    # ------------------------------------------------------------ the update's form
    def evaluate(self, params: Any, tokens: jax.Array, start: jax.Array):
        """``tokens`` [B, P+R], ``start`` [B]: logits [B, R, V], values [B, R], expert stats."""
        return self.module.apply(params, tokens, start, self.prompt_len - 1, self.rollout_steps)

    # ------------------------------------------------------------ the player's forms
    def init_state(self, num_envs: int) -> Dict[str, Any]:
        m, T = self.model, self.context
        rows = lambda width: tuple(jnp.zeros((num_envs, T, width), self.dtype) for _ in range(m.num_hidden_layers))  # noqa: E731
        return {
            "c": rows(m.kv_lora_rank),
            "kr": rows(m.qk_rope_head_dim),
            "pos": jnp.full((num_envs,), self.prompt_len, jnp.int32),
            "start": jnp.zeros((num_envs,), jnp.int32),
            "logits": jnp.zeros((num_envs, m.vocab_size), jnp.float32),
        }

    def prefill(self, params: Any, state: Dict[str, Any], prompt: jax.Array, prompt_len: jax.Array, reset: jax.Array,
                key: jax.Array, greedy: bool = False):
        """``prompt`` [E, P] left-padded, ``prompt_len`` [E], ``reset`` [E] bool:
        the named envs start over from their prompts; the others keep their rows.
        Returns ``(token, logprob, value)``, the new state and the next key."""
        with scopes.scope(scopes.LM_ACT_PREFILL):
            E, P = prompt.shape
            start = (P - prompt_len).astype(jnp.int32)

            def some(args):
                return self.module.apply(params, *args, method=LMPolicy.prefill)

            if attention_is_fused(self.model, P, self.dtype) or E % PREFILL_GROUP:
                logits, values, kept = some((prompt, start))
            else:
                grouped = lambda x: x.reshape(E // PREFILL_GROUP, PREFILL_GROUP, *x.shape[1:])  # noqa: E731
                out = jax.lax.map(some, (grouped(prompt), grouped(start)))
                logits, values, kept = jax.tree_util.tree_map(lambda x: x.reshape(E, *x.shape[2:]), out)
            keep = lambda new, old: jnp.where(reset.reshape((E,) + (1,) * (old.ndim - 1)), new, old)  # noqa: E731
            fill = lambda new, old: keep(old.at[:, :P].set(new.astype(old.dtype)), old)  # noqa: E731
            new_state = {
                "c": tuple(fill(c, old) for (c, _), old in zip(kept, state["c"])),
                "kr": tuple(fill(kr, old) for (_, kr), old in zip(kept, state["kr"])),
                "pos": keep(jnp.full((E,), P, jnp.int32), state["pos"]),
                "start": keep(start, state["start"]),
                "logits": keep(logits, state["logits"]),
            }
            next_key, sub = jax.random.split(key)
            token, logprob = _sample(new_state["logits"], sub, greedy)
            return (token, logprob, values), new_state, next_key

    def decode(self, params: Any, state: Dict[str, Any], token: jax.Array, key: jax.Array, greedy: bool = False):
        """Feed ``token`` [E] (each env's last token) at ``state["pos"]`` and draw the next."""
        with scopes.scope(scopes.LM_ACT_DECODE):
            cache = {"c": state["c"], "kr": state["kr"]}
            logits, values, cache = self.module.apply(
                params, token, cache, state["pos"], state["start"], method=LMPolicy.decode
            )
            new_state = {"c": cache["c"], "kr": cache["kr"], "pos": state["pos"] + 1, "start": state["start"],
                         "logits": logits}
            next_key, sub = jax.random.split(key)
            token, logprob = _sample(logits, sub, greedy)
            return (token, logprob, values), new_state, next_key


def build_agent(runtime: Any, cfg: Dict[str, Any], vocab_size: int, prompt_len: int,
                agent_state: Optional[Dict[str, Any]] = None) -> Tuple[PPOLMAgent, Any]:
    """The agent and its parameters (fresh from ``runtime.root_key`` or a checkpoint's)."""
    model = TransformerConfig.from_config(cfg.algo.model)
    if model.vocab_size != int(vocab_size):
        raise ValueError(
            f"algo.model.vocab_size ({model.vocab_size}) is not the env's vocabulary ({vocab_size}): "
            "ids, logits, sampling and loss are over the vocabulary the model holds"
        )
    precision = runtime.precision
    agent = PPOLMAgent(model, prompt_len, cfg.algo.rollout_steps, precision.compute_dtype, precision.param_dtype)
    params = agent.init_params(jax.random.fold_in(runtime.root_key, 17))
    if agent_state is not None:
        params = jax.tree_util.tree_map(lambda like, saved: jnp.asarray(saved, like.dtype), params, agent_state)
    return agent, params

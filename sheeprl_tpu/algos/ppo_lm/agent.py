"""The token policy: a decoder backbone with a vocabulary head and a value
head, and the two forms it runs in. The backbone is chosen by
``algo.model.model_type`` (:data:`BACKBONES`): `deepseek_v3`
(`models/transformer.py`) or `phi4flash` (`models/hybrid_decoder.py`). It owns
the player's state (``init_cache``, the whole-sequence call that returns what
the cache keeps, ``prefill_cache``, ``decode``); heads, sampling, the acting
copy and everything in `ppo_lm.py` are one code path for both.

- :meth:`PPOLMAgent.evaluate` is the update's form: whole left-padded
  sequences ``[prompt | response]`` through the whole-sequence attention, the
  heads only at the positions the loss reads (``P-1 .. P+R-2``, whose logits
  the response tokens were drawn from).
- :meth:`PPOLMAgent.prefill` / :meth:`PPOLMAgent.decode` are the player's
  forms over its state: the backbone's cache leaves (`deepseek_v3`: the latent
  cache ``{"c": L x [E, T, kv_lora_rank], "kr": L x [E, T,
  qk_rope_head_dim]}``; `phi4flash`: window rings, the shared keys and values
  of the whole context, recurrent state) and :data:`REST`: ``{"pos": [E],
  "start": [E], "logits": [E, V]}``. ``start`` is where an env's context
  begins in its cache row (prompts are left-padded to ``P``), ``pos`` the
  index its next token is written at, ``logits`` what its last token was
  drawn from. Prefill runs the envs named by ``reset`` through the
  whole-sequence form and fills their rows; decode feeds one token per env
  through the one-token form. Both sample at temperature 1 from the softmax
  over the vocabulary the model holds.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.models.hybrid_decoder import HybridConfig
from sheeprl_tpu.models.transformer import TransformerConfig, _init, merge_moe_stats
from sheeprl_tpu.telemetry import scopes

HIGHEST = jax.lax.Precision.HIGHEST
#: ``algo.model.model_type`` -> the backbone's config; its ``backbone(dtype, param_dtype)`` is the decoder, which says
#: everything of the player's state that the agent needs (``init_cache``, ``prefill_cache``, ``prefill_rows``,
#: ``decode``, ``cache_kinds``, ``scan_chunks``, ``fused_scan_layers``, ``fused_attention_layers``,
#: ``attention_tile_visits``): nothing below asks
#: which family it has.
BACKBONES = {"deepseek_v3": TransformerConfig, "phi4flash": HybridConfig}
#: The player's state beside the backbone's cache: small, not donated, readable after a call.
REST = ("pos", "start", "logits")
#: Matrices that compute in float32 under every precision policy: the player's copy keeps them so.
FLOAT32_LEAVES = ("router", "value_head", "A_log")


class LMPolicy(nn.Module):
    backbone: nn.Module  # a config's ``backbone(dtype, param_dtype)``; its parameters live under "backbone"

    def setup(self) -> None:
        c = self.backbone.cfg
        self.tied = getattr(c, "tie_word_embeddings", False)
        if not self.tied:
            self.head = self.param("head", _init(c), (c.hidden_size, c.vocab_size), self.backbone.param_dtype)
        self.value_head = self.param("value_head", _init(c), (c.hidden_size, 1), jnp.float32)

    def heads(self, hidden: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Float32 logits [..., V] and values [...] of hidden states [..., H] (before the final norm)."""
        normed, dtype = self.backbone.final_norm(hidden), self.backbone.dtype
        if self.tied:  # the head is the embedding
            logits = jnp.einsum("...h,vh->...v", normed, self.backbone.embedding.astype(dtype), preferred_element_type=jnp.float32)
        else:
            logits = jnp.dot(normed, self.head.astype(dtype), preferred_element_type=jnp.float32)
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

    def __init__(self, model: Any, prompt_len: int, rollout_steps: int, dtype: Any, param_dtype: Any) -> None:
        self.model = model
        self.prompt_len = int(prompt_len)
        self.rollout_steps = int(rollout_steps)
        self.context = self.prompt_len + self.rollout_steps
        self.dtype = dtype
        self.backbone = model.backbone(dtype, param_dtype)
        self.module = LMPolicy(self.backbone)

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
        return {
            **self.backbone.init_cache(num_envs, self.context),
            "pos": jnp.full((num_envs,), self.prompt_len, jnp.int32),
            "start": jnp.zeros((num_envs,), jnp.int32),
            "logits": jnp.zeros((num_envs, self.model.vocab_size), jnp.float32),
        }

    def cache_bytes(self, num_envs: int) -> Dict[str, int]:
        """Bytes of the player's cache by kind of state (`player/cache_bytes/<kind>`), from its shapes."""
        kinds = self.backbone.cache_kinds
        out: Dict[str, int] = {}
        for name, leaves in jax.eval_shape(lambda: self.backbone.init_cache(num_envs, self.context)).items():
            out[kinds[name]] = out.get(kinds[name], 0) + sum(leaf.size * leaf.dtype.itemsize for leaf in leaves)
        return out

    def scan_chunks(self) -> int:
        """Chunks the state-space scans of one gradient step's sequences work through (0 for a backbone with none)."""
        return self.backbone.scan_chunks(self.context)

    def fused_scan_layers(self) -> int:
        """State-space layers of a gradient step's sequences whose scan runs as kernels (`ssm/scan_fused`; 0 = the plain path)."""
        return self.backbone.fused_scan_layers(self.context)

    def fused_attention_layers(self) -> int:
        """Attention layers of a gradient step's sequences that run as fused kernels (`lm/attention_fused`; 0 = the plain path)."""
        return self.backbone.fused_attention_layers(self.context)

    def attention_tile_visits(self, start: jax.Array) -> Optional[Tuple[jax.Array, jax.Array]]:
        """The fused attention kernels' tile visits in a gradient step over rows whose keys begin at ``start`` [B], and
        those skipped as left padding (`mla/tile_visits`, `mla/tile_visits_skipped`; None = no such count here)."""
        return self.backbone.attention_tile_visits(start, self.context)

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

            group = self.backbone.prefill_rows(E, P)
            if group is None:
                logits, values, kept = some((prompt, start))
            else:
                grouped = lambda x: x.reshape(E // group, group, *x.shape[1:])  # noqa: E731
                out = jax.lax.map(some, (grouped(prompt), grouped(start)))
                logits, values, kept = jax.tree_util.tree_map(lambda x: x.reshape(E, *x.shape[2:]), out)
            keep = lambda new, old: jnp.where(reset.reshape((E,) + (1,) * (old.ndim - 1)), new, old)  # noqa: E731
            cache = {k: v for k, v in state.items() if k not in REST}
            new_state = {
                **self.backbone.prefill_cache(cache, kept, P, keep),
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
            cache = {k: v for k, v in state.items() if k not in REST}
            logits, values, cache = self.module.apply(
                params, token, cache, state["pos"], state["start"], method=LMPolicy.decode
            )
            new_state = {**cache, "pos": state["pos"] + 1, "start": state["start"], "logits": logits}
            next_key, sub = jax.random.split(key)
            token, logprob = _sample(logits, sub, greedy)
            return (token, logprob, values), new_state, next_key


def build_agent(runtime: Any, cfg: Dict[str, Any], vocab_size: int, prompt_len: int,
                agent_state: Optional[Dict[str, Any]] = None) -> Tuple[PPOLMAgent, Any]:
    """The agent and its parameters (fresh from ``runtime.root_key`` or a checkpoint's)."""
    model_type = str(cfg.algo.model.get("model_type") or "deepseek_v3")
    if model_type not in BACKBONES:
        raise ValueError(f"algo.model.model_type {model_type!r} is no backbone of ppo_lm (have {sorted(BACKBONES)})")
    model = BACKBONES[model_type].from_config(cfg.algo.model)
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

"""The one table of device scope names, and the only caller of
``jax.named_scope`` in the program.

A scope only writes into the ``op_name`` metadata of the instructions traced
under it (``jit(train_step)/jvp(dv3/rssm)/while/body/...``); the compiled
program, its memory and its compilation-cache key are unchanged. JAX itself
tells the directions apart: the backward pass of a scope entered inside
``value_and_grad`` arrives as ``transpose(jvp(<scope>))``. A device profile
(``telemetry=profile``, or the benchmark's traced run) is reduced to per-phase
self time by these names (``benchmarks/layer_metrics/_scopes.py``), so a name
here is part of that yardstick: rename one only with its reader.
"""

from __future__ import annotations

import jax

# The DreamerV3 gradient step (algos/dreamer_v3/dreamer_v3.py:make_step_core)
DV3_ENCODER = "dv3/encoder"  # observation normalisation and encoders (CNN + MLP)
DV3_RSSM = "dv3/rssm"  # the dynamics-learning scan over the sequence
DV3_HEADS = "dv3/heads"  # decoders, reward and continue heads, world-model loss and KL
DV3_IMAGINE = "dv3/imagine"  # the imagination scan over the horizon (actor sample + world model)
DV3_ACTOR_CRITIC = "dv3/actor_critic"  # lambda returns, moments, actor and critic losses, target critic
DV3_OPTIM = "dv3/optim"  # clipping, the three optimizer updates, the target EMA, gradient norms
DV3_STEP = (DV3_ENCODER, DV3_RSSM, DV3_HEADS, DV3_IMAGINE, DV3_ACTOR_CRITIC, DV3_OPTIM)
# Outside the gradient step
DV3_ACT = "dv3/act"  # the player's acting step
RING_SAMPLE = "replay/ring_sample"  # the in-jit sampler of the device replay ring
RING_WRITE = "replay/ring_write"  # the ring's donated write program
# The token policy's gradient step (algos/ppo_lm/ppo_lm.py:make_train_step over models/transformer.py)
LM_EMBED = "lm/embed"  # the token embedding lookup
LM_MLA = "lm/mla"  # pre-norm, latent attention (projections, RoPE, scores, output projection)
LM_MOE_ROUTE = "lm/moe_route"  # pre-norm of the feed-forward half, router scores, top-k, the sort by expert
LM_MOE_EXPERTS = "lm/moe_experts"  # dispatch, the grouped products over the held experts, combine
LM_MOE_SHARED = "lm/moe_shared"  # the shared experts' SwiGLU
LM_DENSE_MLP = "lm/dense_mlp"  # the leading dense layers' SwiGLU
LM_HEAD_LOSS = "lm/head_loss"  # final norm, vocabulary head, value head, the PPO loss
LM_OPTIM = "lm/optim"  # clipping and the optimizer update
LM_STEP = (LM_EMBED, LM_MLA, LM_MOE_ROUTE, LM_MOE_EXPERTS, LM_MOE_SHARED, LM_DENSE_MLP, LM_HEAD_LOSS, LM_OPTIM)
# The same step over a `phi4flash` backbone (models/hybrid_decoder.py): its mixers in place of latent attention and experts
LM_SSM = "lm/ssm"  # a Mamba layer's mixer: pre-norm, projections, convolution, selective scan, gate
LM_SWA = "lm/swa"  # pre-norm and differential attention over the sliding window
LM_FULL_ATTN = "lm/full_attn"  # the same over the whole context (the layer whose keys and values the cross layers read)
LM_CROSS_ATTN = "lm/cross_attn"  # pre-norm, query and output projections, attention over the full layer's keys and values
LM_GMU = "lm/gmu"  # a gated memory unit over the memory layer's scan output
LM_HYBRID_STEP = (LM_EMBED, LM_SSM, LM_SWA, LM_FULL_ATTN, LM_CROSS_ATTN, LM_GMU, LM_DENSE_MLP, LM_HEAD_LOSS, LM_OPTIM)
# Outside the gradient step: the player's two programs
LM_ACT_PREFILL = "lm/act_prefill"  # whole prompts through the whole-sequence form, filling the player's cache
LM_ACT_DECODE = "lm/act_decode"  # one token per env through the one-token form over the cache


def scope(name: str):
    """``with scope(DV3_RSSM): ...`` inside traced code."""
    return jax.named_scope(name)

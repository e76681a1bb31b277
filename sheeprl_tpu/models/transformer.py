"""Decoder blocks of the DeepSeek-V3 family (flax.linen), TPU-first.

What `model_type: deepseek_v3` configurations are made of, as published:

- **RMSNorm** with float32 statistics.
- **Interleaved RoPE**: the weights pair channels (2i, 2i+1); the pair is
  rotated and the halves are laid out de-interleaved (evens, then odds), on
  queries and keys alike, so every dot product is the published one.
- **Latent attention (MLA)**, no query compression, in two forms over the
  same parameters. :meth:`MLA.__call__` runs whole sequences: keys and values
  are expanded from the latent, and the causal softmax over left-padded keys
  is one algorithm in two implementations chosen by what the code can see
  (`pallas_mla_attention.ineligible_reason`, the whole rule): on a TPU, at an
  eligible shape, the fused kernels of `models/pallas_mla_attention.py`
  (online softmax forward, probabilities made again from the saved
  log-sum-exp backward: no block of scores goes to HBM); elsewhere
  :func:`blocked_attention`, plain JAX query block by query block under
  ``jax.checkpoint``, which is what the CPU tests and the micro sizes run and
  what the kernels are held to. Both share the projections, RoPE and the
  output product, and both sit under the ``lm/mla`` scope.
  :meth:`MLA.decode` is the absorbed single-token form over a cache of
  ``(c, k_rope)``, ``kv_lora_rank + qk_rope_head_dim`` numbers a token:
  ``q' = q_nope W_kb^T``, scores ``q'.c + q_rope.k_rope``, ``(P c) W_vb``.
- **SwiGLU**, dense or as the shared experts (one SwiGLU of their summed width).
- **The expert layer** (:class:`MoE`): a float32 sigmoid router over all
  ``n_routed_experts``, top-k of ``score + bias`` with the bias used for the
  selection only, the selected scores normalised and scaled. The layer is
  *told which experts it holds* (``experts_held = (first, count)``): it routes
  over all of them and computes the part of the result its own experts give,
  as one rank of an expert-parallel deployment does before the exchange. The
  slots that chose a held expert are sorted by expert, first in the order,
  and go through grouped matrix products (``jax.lax.ragged_dot``) a bounded
  chunk of rows at a time (:func:`routed_experts`): a gather of the chunk's
  tokens, the products, a scatter-add onto the tokens, so the layer costs by
  the slots this chip holds and not by all that were routed. The chunk follows
  from the shape and the experts held (:func:`expert_chunk_rows`); chunks past
  the first run only while held slots remain, through the same body: there is
  no capacity and no token is dropped.

The player's state of this family is the latent cache (`Transformer.init_cache`,
`prefill_cache`, `decode`); `Transformer.cache_kinds` says what kind of state each leaf is.

Compute dtype and parameter dtype come from the precision policy
(``bf16-mixed``: float32 parameters, bfloat16 products, float32 softmax, norm
statistics and router). Device scopes (`telemetry/scopes.py`) name the phases.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.models import pallas_mla_attention
from sheeprl_tpu.telemetry import scopes

Dtype = Any
MASKED = pallas_mla_attention.MASKED  # a masked score: finite, so a row with no valid key stays finite
ATTN_BLOCK = 512  # most queries of one block of the plain whole-sequence attention (a short sequence still goes in four)
#: Prompts that share one block of float32 attention scores where the prefill's softmax runs in plain JAX (all of
#: them where they do not divide). The fused kernels make no such block: there every prompt goes through at once.
PREFILL_GROUP = 4


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """The published keys of a `deepseek_v3` config.json, plus what this chip holds (``experts_held``)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    experts_held: Optional[Tuple[int, int]] = None  # (first, count) of the routed experts held here; None = all of them

    def __post_init__(self) -> None:
        held = self.experts_held or (0, 0)
        first, count = int(held[0]), int(held[1])
        if count <= 0:
            first, count = 0, int(self.n_routed_experts)
        if first < 0 or first + count > int(self.n_routed_experts):
            raise ValueError(f"experts_held {tuple(held)} does not lie inside the {self.n_routed_experts} routed experts")
        object.__setattr__(self, "experts_held", (first, count))

    @classmethod
    def from_config(cls, model_cfg: Mapping[str, Any]) -> "TransformerConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in dict(model_cfg).items() if k in known and v is not None})

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def backbone(self, dtype: Dtype, param_dtype: Dtype) -> "Transformer":
        """The decoder of this config (unbound: what it says of the player's state needs no parameters)."""
        return Transformer(self, dtype, param_dtype)


# --------------------------------------------------------------------- pieces
class RMSNorm(nn.Module):
    epsilon: float = 1e-6
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.epsilon)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope_tables(positions: jax.Array, dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos and sin ``[..., dim // 2]`` of the positions' angles, float32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the channel pairs (2i, 2i+1) of the last axis; the result holds
    the first members of the pairs, then the second (`rope_interleave: true`:
    de-interleave to halves, then rotate-half)."""
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin, odd * cos + even * sin], axis=-1).astype(x.dtype)


def _init(cfg: TransformerConfig):
    return nn.initializers.normal(cfg.initializer_range)


class SwiGLU(nn.Module):
    cfg: TransformerConfig
    width: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        hidden = x.shape[-1]
        gate = self.param("w_gate", _init(self.cfg), (hidden, self.width), self.param_dtype).astype(self.dtype)
        up = self.param("w_up", _init(self.cfg), (hidden, self.width), self.param_dtype).astype(self.dtype)
        down = self.param("w_down", _init(self.cfg), (self.width, hidden), self.param_dtype).astype(self.dtype)
        return (nn.silu(x @ gate) * (x @ up)) @ down


# ------------------------------------------------------------ latent attention
def blocked_attention(q_nope: jax.Array, q_rope: jax.Array, k_nope: jax.Array, k_rope: jax.Array, v: jax.Array,
                      start: jax.Array, scale: float) -> jax.Array:
    """The causal softmax of left-padded whole sequences in plain JAX, query
    block by query block over the keys up to the block's end, each block under
    ``jax.checkpoint`` so that no ``[heads, S, S]`` tensor outlives its block:
    ``q_nope``, ``k_nope`` [B, S, h, dn], ``q_rope`` [B, S, h, dr], ``k_rope``
    [B, S, dr], ``v`` [B, S, h, dv]; row b's keys are valid from ``start[b]``
    on. What runs where `pallas_mla_attention` does not, and what it is held to."""

    def block(qn, qr, kn, kr, v, first, start):
        # queries [first, first + len) against the keys [0, first + len)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qn, kn, preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("bqhd,bkd->bhqk", qr, kr, preferred_element_type=jnp.float32)
        key_at = jnp.arange(kn.shape[1])
        causal = key_at[None, :] <= (first + jnp.arange(qn.shape[1]))[:, None]
        valid = causal[None, None] & (key_at[None, :] >= start[:, None])[:, None, None, :]
        probs = jax.nn.softmax(jnp.where(valid, scores * scale, MASKED), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)

    block = jax.checkpoint(block, static_argnums=(5,))
    S = q_nope.shape[1]
    size = min(ATTN_BLOCK, -(-S // 4))
    outs = []
    for first in range(0, S, size):
        end = min(first + size, S)
        outs.append(block(q_nope[:, first:end], q_rope[:, first:end], k_nope[:, :end], k_rope[:, :end],
                          v[:, :end], first, start))
    return jnp.concatenate(outs, axis=1)


def attention_is_fused(cfg: TransformerConfig, seq: int, dtype: Dtype) -> bool:
    """Whether :meth:`MLA.__call__` hands whole sequences of ``seq`` positions
    to the fused kernels where this is traced; else :func:`blocked_attention`
    runs, whose float32 score block grows with the rows that share it."""
    return pallas_mla_attention.ineligible_reason(
        seq, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, dtype) is None


class MLA(nn.Module):
    cfg: TransformerConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        heads = c.num_attention_heads
        self.norm = RMSNorm(c.rms_norm_eps, self.param_dtype)
        self.wq = self.param("wq", _init(c), (c.hidden_size, heads * c.qk_head_dim), self.param_dtype)
        self.wkv_a = self.param("wkv_a", _init(c), (c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim), self.param_dtype)
        self.kv_norm = RMSNorm(c.rms_norm_eps, self.param_dtype)
        self.wkv_b = self.param(
            "wkv_b", _init(c), (c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim)), self.param_dtype
        )
        self.wo = self.param("wo", _init(c), (heads * c.v_head_dim, c.hidden_size), self.param_dtype)

    def _project(self, x: jax.Array, positions: jax.Array):
        """``x`` [..., H] at ``positions`` [...]: q_nope [..., h, dn], q_rope
        [..., h, dr] (rotated), the normed latent c [..., r], k_rope [..., dr] (rotated)."""
        c = self.cfg
        heads = c.num_attention_heads
        xn = self.norm(x)
        q = (xn @ self.wq.astype(self.dtype)).reshape(*x.shape[:-1], heads, c.qk_head_dim)
        q_nope, q_rope = q[..., : c.qk_nope_head_dim], q[..., c.qk_nope_head_dim:]
        kv = xn @ self.wkv_a.astype(self.dtype)
        latent = self.kv_norm(kv[..., : c.kv_lora_rank])
        cos, sin = rope_tables(positions, c.qk_rope_head_dim, c.rope_theta)
        k_rope = apply_rope_interleaved(kv[..., c.kv_lora_rank:], cos, sin)
        q_rope = apply_rope_interleaved(q_rope, cos[..., None, :], sin[..., None, :])
        return q_nope, q_rope, latent, k_rope

    def _wkv_b(self):
        c = self.cfg
        w = self.wkv_b.astype(self.dtype).reshape(c.kv_lora_rank, c.num_attention_heads, c.qk_nope_head_dim + c.v_head_dim)
        return w[..., : c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]  # W_kb, W_vb: [r, h, d]

    def __call__(self, x: jax.Array, positions: jax.Array, start: jax.Array):
        """Whole sequences: ``x`` [B, S, H], ``positions`` [B, S]; the keys of
        row b are valid from index ``start[b]`` on (left padding). Returns
        the attention output and ``(c, k_rope)``, what a cache keeps. On the
        plain path a block of float32 scores ``[B, heads, ATTN_BLOCK, S]`` goes
        through memory, so a caller with many rows bounds ``B`` itself where
        :func:`attention_is_fused` says no (the player's prefill does)."""
        c = self.cfg
        B, S, _ = x.shape
        q_nope, q_rope, latent, k_rope = self._project(x, positions)
        scale = c.qk_head_dim ** -0.5
        if attention_is_fused(c, S, self.dtype):
            # the kernels' wrapper expands keys and values itself: `wkv_b` holds each head's key columns beside its value columns
            out = pallas_mla_attention.mla_attention(q_nope, q_rope, latent, self.wkv_b.astype(self.dtype), k_rope, start, scale)
        else:
            w_kb, w_vb = self._wkv_b()
            k_nope = jnp.einsum("bsr,rhd->bshd", latent, w_kb)
            value = jnp.einsum("bsr,rhd->bshd", latent, w_vb)
            out = blocked_attention(q_nope, q_rope, k_nope, k_rope, value, start, scale)
        out = out.reshape(B, S, c.num_attention_heads * c.v_head_dim)
        return out @ self.wo.astype(self.dtype), (latent, k_rope)

    def decode(self, x: jax.Array, cache_c: jax.Array, cache_kr: jax.Array, pos: jax.Array, start: jax.Array):
        """One token per env through the absorbed form: ``x`` [E, H] is the
        token at index ``pos`` [E] of its env's context; the cache rows
        [E, T, r] / [E, T, dr] get it written there, and the env attends to
        its indices ``start..pos``. Returns the output and the two caches."""
        c = self.cfg
        q_nope, q_rope, latent, k_rope = self._project(x, pos - start)
        write = jax.vmap(lambda row, new, at: jax.lax.dynamic_update_slice(row, new[None], (at, 0)))
        cache_c = write(cache_c, latent.astype(cache_c.dtype), pos)
        cache_kr = write(cache_kr, k_rope.astype(cache_kr.dtype), pos)
        w_kb, w_vb = self._wkv_b()
        q_latent = jnp.einsum("ehd,rhd->ehr", q_nope, w_kb)
        scores = jnp.einsum("ehr,etr->eht", q_latent, cache_c, preferred_element_type=jnp.float32)
        scores = scores + jnp.einsum("ehd,etd->eht", q_rope, cache_kr, preferred_element_type=jnp.float32)
        key_at = jnp.arange(cache_c.shape[1])
        valid = (key_at[None, :] >= start[:, None]) & (key_at[None, :] <= pos[:, None])
        probs = jax.nn.softmax(jnp.where(valid[:, None, :], scores * c.qk_head_dim ** -0.5, MASKED), axis=-1)
        context = jnp.einsum("eht,etr->ehr", probs.astype(cache_c.dtype), cache_c)
        out = jnp.einsum("ehr,rhd->ehd", context, w_vb).reshape(x.shape[0], -1)
        return out @ self.wo.astype(self.dtype), cache_c, cache_kr


# ------------------------------------------------------------ the expert layer
def route(scores: jax.Array, bias: jax.Array, k: int, normalise: bool, scaling: float):
    """``scores`` [N, E] (sigmoid, float32): the top ``k`` of ``scores + bias``
    and their weights. The bias takes part in the selection only."""
    _, chosen = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return chosen, weights * scaling


EXPERT_ROW_TILE = 512  # a chunk of sorted slots is a whole number of these rows


def expert_chunk_rows(slots: int, held: int, routed: int) -> int:
    """Rows of one chunk of the sorted slots, from what the code can see: twice
    what an even router sends to ``held`` of ``routed`` experts, in whole tiles;
    all ``slots`` where that is no fewer (every expert held, a decode step, the
    micro sizes), and then the one chunk is the whole layer."""
    rows = -(-2 * slots * held // (routed * EXPERT_ROW_TILE)) * EXPERT_ROW_TILE
    return min(rows, slots)


def chunk_trips(n_held, rows: int):
    """Chunks of ``rows`` sorted slots that run: the first always, a later one while held slots remain."""
    return jnp.maximum(-(-n_held // rows), 1)


def _chunks(x, weights, order, sizes, n_held, rows: int):
    """The sorted slots ``rows`` at a time: the number of chunks, and ``take(j)``
    -> each row's slot (token x k + choice) and token, whether it chose a held
    expert, the chunk's part of every group, the tokens' rows (dispatched: the
    others zero) and the slots' float32 weights. Dispatch and combine, forward
    and transpose, all index through this."""
    k = weights.shape[1]
    count = -(-order.shape[0] // rows)
    order = jnp.pad(order, (0, count * rows - order.shape[0]))  # the last chunk's tail: never live
    ends = jnp.cumsum(sizes)
    starts, flat = ends - sizes, weights.reshape(-1)

    def take(j):
        base = j * rows
        slot = jax.lax.dynamic_slice(order, (base,), (rows,))
        live = base + jnp.arange(rows) < n_held
        part = jnp.clip(ends - base, 0, rows) - jnp.clip(starts - base, 0, rows)
        src = slot // k
        return slot, src, live, part, jnp.where(live[:, None], x[src], 0), jnp.where(live, flat[slot], 0.0)

    return count, take


def _chunk_rows(xs, slot_weight, live, w_gate, w_up, w_down, sizes):
    """The held experts over one chunk's dispatched rows, each scaled by its slot's float32 weight."""
    grouped = partial(jax.lax.ragged_dot, group_sizes=sizes, preferred_element_type=w_gate.dtype)
    ys = grouped(nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up), w_down)
    # rows past the last group are not written by the grouped product
    return jnp.where(live[:, None], ys.astype(jnp.float32) * slot_weight[:, None], 0).astype(w_gate.dtype)


def _over_chunks(body, carry, n_held, rows: int, count: int):
    """``body(j, carry)`` for the chunks that run: the later trips are skipped, not masked."""
    if count == 1:
        return body(0, carry)
    trips = chunk_trips(n_held, rows)
    return jax.lax.while_loop(lambda c: c[0] < trips, lambda c: (c[0] + 1, body(c[0], c[1])), (jnp.zeros_like(trips), carry))[1]


@partial(jax.custom_vjp, nondiff_argnums=(8,))
def routed_experts(x, w_gate, w_up, w_down, weights, order, sizes, n_held, rows: int):
    """What the held experts give every token: ``x`` [N, D], ``weights`` [N, k]
    float32, ``order`` the slots sorted by expert with the ``n_held`` that chose
    a held expert first, ``sizes`` the held experts' groups. The sorted slots
    are worked on ``rows`` at a time by one body: a gather of the chunk's
    tokens, the three grouped products, a scatter-add of the weighted rows
    (float32 sums, rounded once, as a sum over a token's choices is). No slot
    is dropped: held slots past the first chunk run the same body again."""
    count, take = _chunks(x, weights, order, sizes, n_held, rows)

    def chunk(j, out):
        _, src, live, part, xs, slot_weight = take(j)
        return out.at[src].add(_chunk_rows(xs, slot_weight, live, w_gate, w_up, w_down, part).astype(out.dtype))

    return _over_chunks(chunk, jnp.zeros(x.shape, jnp.float32), n_held, rows, count).astype(x.dtype)


def _routed_experts_fwd(x, w_gate, w_up, w_down, weights, order, sizes, n_held, rows):
    saved = (x, w_gate, w_up, w_down, weights, order, sizes, n_held)
    return routed_experts(*saved, rows), saved


def _routed_experts_bwd(rows, saved, g):
    """The transpose, chunk by chunk over the same index: the combine's is one
    gather of the cotangent's rows, the dispatch's one scatter-add; the chunk's
    own rows are made again from its gather (nothing a chunk computed is kept)."""
    x, w_gate, w_up, w_down, weights, order, sizes, n_held = saved
    count, take = _chunks(x, weights, order, sizes, n_held, rows)

    def chunk(j, carry):
        dx, d_flat, d_gate, d_up, d_down = carry
        slot, src, live, part, xs, slot_weight = take(j)
        _, pull = jax.vjp(lambda xs, sw, a, b, c: _chunk_rows(xs, sw, live, a, b, c, part), xs, slot_weight, w_gate, w_up, w_down)
        d_xs, d_sw, a, b, c = pull(jnp.where(live[:, None], g[src], 0))
        dx = dx.at[src].add(jnp.where(live[:, None], d_xs, 0).astype(dx.dtype))
        d_flat = d_flat.at[slot].add(jnp.where(live, d_sw, 0.0))
        return dx, d_flat, d_gate + a, d_up + b, d_down + c

    zeros = (jnp.zeros(x.shape, jnp.float32), jnp.zeros(weights.size, weights.dtype), *map(jnp.zeros_like, (w_gate, w_up, w_down)))
    dx, d_flat, d_gate, d_up, d_down = _over_chunks(chunk, zeros, n_held, rows, count)
    return dx.astype(x.dtype), d_gate, d_up, d_down, d_flat.reshape(weights.shape), None, None, None


routed_experts.defvjp(_routed_experts_fwd, _routed_experts_bwd)


class MoE(nn.Module):
    """Router over all experts, grouped products over the held ones, plus the
    shared experts. Returns ``(y, stats)``; ``stats`` are small device arrays
    (expert counts of the held experts, slots routed and held, the selection)."""

    cfg: TransformerConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        first, count = c.experts_held
        width = c.moe_intermediate_size
        self.norm = RMSNorm(c.rms_norm_eps, self.param_dtype)
        self.router = self.param("router", _init(c), (c.hidden_size, c.n_routed_experts), jnp.float32)
        self.router_bias = self.param("router_bias", nn.initializers.zeros, (c.n_routed_experts,), jnp.float32)
        self.w_gate = self.param("w_gate", _init(c), (count, c.hidden_size, width), self.param_dtype)
        self.w_up = self.param("w_up", _init(c), (count, c.hidden_size, width), self.param_dtype)
        self.w_down = self.param("w_down", _init(c), (count, width, c.hidden_size), self.param_dtype)
        self.shared = SwiGLU(c, c.moe_intermediate_size * c.n_shared_experts, self.dtype, self.param_dtype)

    def __call__(self, x: jax.Array, real: Optional[jax.Array] = None):
        """``real`` (the shape of ``x`` less its last axis) is false at the
        padding of a left-padded batch: a position that belongs to no context
        is routed (the selection is reported for every position) and sent to
        no expert, so the experts' load is that of the real tokens."""
        c = self.cfg
        first, count = c.experts_held
        k = c.num_experts_per_tok
        shape = x.shape
        with scopes.scope(scopes.LM_MOE_ROUTE):
            xn = self.norm(x).reshape(-1, shape[-1])
            logits = jnp.dot(xn.astype(jnp.float32), self.router, precision=jax.lax.Precision.HIGHEST)
            chosen, weights = route(jax.nn.sigmoid(logits), self.router_bias, k, c.norm_topk_prob, c.routed_scaling_factor)
            local = chosen - first
            held = (local >= 0) & (local < count)  # [N, K]
            routed_slots = jnp.asarray(held.size, jnp.int32)
            if real is not None:
                held = held & real.reshape(-1, 1)
                routed_slots = jnp.sum(real).astype(jnp.int32) * k
            group = jnp.where(held, local, count).reshape(-1)  # the other slots sort last
            order = jnp.argsort(group, stable=True)
            sizes = jnp.sum((group[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32), axis=0)
            n_held = jnp.sum(sizes)
            rows = expert_chunk_rows(group.shape[0], count, c.n_routed_experts)
        with scopes.scope(scopes.LM_MOE_EXPERTS):
            routed = routed_experts(xn, self.w_gate.astype(self.dtype), self.w_up.astype(self.dtype),
                                    self.w_down.astype(self.dtype), weights, order, sizes, n_held, rows)
        with scopes.scope(scopes.LM_MOE_SHARED):
            shared = self.shared(xn)
        stats = {"expert_tokens": sizes, "held_slots": n_held, "routed_slots": routed_slots, "chosen": chosen,
                 "overflow_chunks": chunk_trips(n_held, rows) - 1}
        return (routed + shared).reshape(shape), stats


# ------------------------------------------------------------------ the blocks
class DecoderLayer(nn.Module):
    cfg: TransformerConfig
    dense: bool
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        self.attn = MLA(self.cfg, self.dtype, self.param_dtype)
        if self.dense:
            self.mlp_norm = RMSNorm(self.cfg.rms_norm_eps, self.param_dtype)
            self.mlp = SwiGLU(self.cfg, self.cfg.intermediate_size, self.dtype, self.param_dtype)
        else:
            self.moe = MoE(self.cfg, self.dtype, self.param_dtype)

    def _feed_forward(self, x: jax.Array, real: Optional[jax.Array] = None):
        if self.dense:
            with scopes.scope(scopes.LM_DENSE_MLP):
                return x + self.mlp(self.mlp_norm(x)), None
        y, stats = self.moe(x, real)
        return x + y, stats

    def __call__(self, x: jax.Array, positions: jax.Array, start: jax.Array):
        with scopes.scope(scopes.LM_MLA):
            attn, kept = self.attn(x, positions, start)
            x = x + attn
        x, stats = self._feed_forward(x, jnp.arange(x.shape[1])[None, :] >= start[:, None])
        return x, kept, stats

    def decode(self, x: jax.Array, cache_c, cache_kr, pos, start):
        attn, cache_c, cache_kr = self.attn.decode(x, cache_c, cache_kr, pos, start)
        x, _ = self._feed_forward(x + attn)
        return x, cache_c, cache_kr


class Transformer(nn.Module):
    """Embedding, the decoder layers, the final norm. Heads are the caller's."""

    cfg: TransformerConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.embedding = self.param("embedding", _init(c), (c.vocab_size, c.hidden_size), self.param_dtype)
        layer = nn.remat(DecoderLayer)  # a layer's internals are made again in the backward pass, its input alone is kept
        self.layers = [
            layer(c, i < c.first_k_dense_replace, self.dtype, self.param_dtype) for i in range(c.num_hidden_layers)
        ]
        self.final_norm = RMSNorm(c.rms_norm_eps, self.param_dtype)

    def embed(self, tokens: jax.Array) -> jax.Array:
        with scopes.scope(scopes.LM_EMBED):
            return jnp.take(self.embedding, tokens, axis=0).astype(self.dtype)

    def __call__(self, tokens: jax.Array, start: jax.Array):
        """``tokens`` [B, S] left-padded: row b's context begins at index
        ``start[b]``. Returns the hidden states before the final norm [B, S, H],
        per layer what a cache keeps, and per expert layer its stats."""
        positions = jnp.maximum(jnp.arange(tokens.shape[1])[None, :] - start[:, None], 0)
        x = self.embed(tokens)
        kept, stats = [], []
        for layer in self.layers:
            x, kv, layer_stats = layer(x, positions, start)
            kept.append(kv)
            if layer_stats is not None:
                stats.append(layer_stats)
        return x, kept, stats

    # ------------------------------------------------------------ the player's state (no parameters, no scope of its own)
    #: cache leaf -> the kind of player state it is (`player/cache_bytes/<kind>`): rows of the whole context
    cache_kinds = {"c": "full", "kr": "full"}

    @nn.nowrap
    def scan_chunks(self, seq: int) -> int:
        """Chunks of a state-space scan over ``seq`` positions: this family has no such layer."""
        return 0

    @nn.nowrap
    def fused_scan_layers(self, seq: int) -> int:
        """State-space layers whose whole-sequence scan runs as kernels: this family has none."""
        return 0

    @nn.nowrap
    def fused_attention_layers(self, seq: int) -> int:
        """Attention layers whose whole-sequence form over ``seq`` positions runs as fused kernels where this is asked."""
        return self.cfg.num_hidden_layers * attention_is_fused(self.cfg, seq, self.dtype)

    @nn.nowrap
    def attention_tile_visits(self, start: jax.Array, seq: int) -> Optional[Tuple[jax.Array, jax.Array]]:
        """Tile visits of the fused attention kernels in one gradient step over rows of ``seq`` positions whose keys
        begin at ``start`` [B], and the visits skipped as wholly left padding
        (`pallas_mla_attention.tile_visits`), over every layer's forward, the forward `nn.remat` makes again and the
        backward, and every head; None where the plain path runs."""
        if not attention_is_fused(self.cfg, seq, self.dtype):
            return None
        visits, skipped = pallas_mla_attention.tile_visits(start, seq)
        calls = self.cfg.num_hidden_layers * 3 * self.cfg.num_attention_heads
        return calls * jnp.sum(visits), calls * jnp.sum(skipped)

    @nn.nowrap
    def prefill_rows(self, num_envs: int, prompt_len: int) -> Optional[int]:
        """Prompts that go through the whole-sequence form together; None = all of them at once."""
        at_once = attention_is_fused(self.cfg, prompt_len, self.dtype) or num_envs % PREFILL_GROUP
        return None if at_once else PREFILL_GROUP

    @nn.nowrap
    def init_cache(self, num_envs: int, context: int) -> Dict[str, Any]:
        """The latent cache of ``num_envs`` envs over ``context`` positions, one array a layer."""
        c = self.cfg
        rows = lambda width: tuple(jnp.zeros((num_envs, context, width), self.dtype) for _ in range(c.num_hidden_layers))  # noqa: E731
        return {"c": rows(c.kv_lora_rank), "kr": rows(c.qk_rope_head_dim)}

    @nn.nowrap
    def prefill_cache(self, cache: Dict[str, Any], kept: list, prompt_len: int, keep) -> Dict[str, Any]:
        """``cache`` with the first ``prompt_len`` rows filled from what the whole-sequence form kept, for the envs
        ``keep(new, old)`` takes the new leaf for."""
        fill = lambda new, old: keep(old.at[:, :prompt_len].set(new.astype(old.dtype)), old)  # noqa: E731
        return {"c": tuple(fill(c, old) for (c, _), old in zip(kept, cache["c"])),
                "kr": tuple(fill(kr, old) for (_, kr), old in zip(kept, cache["kr"]))}

    def decode(self, tokens: jax.Array, cache: Dict[str, Any], pos: jax.Array, start: jax.Array):
        """``tokens`` [E] at indices ``pos`` [E]; ``cache`` = {"c": L x [E, T, r], "kr": L x [E, T, dr]},
        one array a layer so that a donated cache is updated in place."""
        x = self.embed(tokens)
        cs, krs = [], []
        for i, layer in enumerate(self.layers):
            x, c_i, kr_i = layer.decode(x, cache["c"][i], cache["kr"][i], pos, start)
            cs.append(c_i)
            krs.append(kr_i)
        return x, {"c": tuple(cs), "kr": tuple(krs)}


def merge_moe_stats(stats: list) -> Optional[Dict[str, jax.Array]]:
    """The per-layer stats of one forward pass as arrays with a leading layer axis."""
    if not stats:
        return None
    return {k: jnp.stack([s[k] for s in stats]) for k in stats[0]}

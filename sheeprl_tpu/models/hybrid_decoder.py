"""Decoder blocks of the `phi4flash` family (flax.linen), TPU-first: the
decoder-hybrid-decoder of arXiv:2507.06607 with the differential attention of
arXiv:2410.05258, as `model_type: phi4flash` configurations publish it.

Every layer is ``h += Mixer(LN(h)); h += SwiGLU(LN(h))`` with LayerNorm (gain
and bias, float32 statistics); there is no positional embedding and no RoPE;
the head is the embedding, transposed. The mixer follows from the layer's
**published** index ``l`` of ``N`` (:meth:`HybridConfig.kind`):

- ``ssm`` (``l`` even, ``l <= N/2``): a Mamba-1 block. ``[x; z] = W_in u``, a
  causal depthwise convolution of ``d_conv`` taps and SiLU on ``x``,
  ``[d; B; C] = W_x x``, ``Delta = softplus(W_dt d + b_dt)``, the selective
  scan ``s_t = exp(Delta_t A) s_{t-1} + (Delta_t x_t) B_t``, ``y_t = s_t C_t
  + D x_t``, ``W_out (y silu(z))``. Layer ``N/2`` also hands on ``y`` (before
  the gate): the **memory** of the gated memory units.
- ``swa`` (``l`` odd, ``l < N/2``): differential grouped-query attention over
  the last ``sliding_window`` positions (the token itself included).
- ``full`` (``l = N/2 + 1``): the same over the whole context; its keys and
  values are kept for every ``cross`` layer.
- ``cross`` (``l`` odd, ``l >= N/2 + 3``): a query and an output projection
  only; keys and values are the ``full`` layer's, causal.
- ``gmu`` (``l`` even, ``l > N/2``): ``W_2 (m silu(W_1 u))`` over the memory.

**Differential attention**: query heads and key/value heads are taken in
adjacent pairs, the values of a pair concatenated; ``(softmax(q_1 k_1) -
lambda softmax(q_2 k_2)) [v_1; v_2]``, an RMSNorm over the concatenated width
with a gain, times ``1 - lambda_init``; ``lambda = exp(lq1.lk1) - exp(lq2.lk2)
+ lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``.

The model is *told which layers it holds* (``layers_held = (first, count)``
of the published indices, as ``experts_held`` tells an expert layer its
experts): one pipeline stage of a deployment. Kinds and ``lambda_init`` keep
following the published index; a held range with a ``cross`` or ``gmu`` layer
and without its source is refused.

Two forms over the same parameters. **Whole sequences** (left-padded: a pad
position is no key, feeds neither the convolution nor the state): the scan
runs as the Pallas kernels of `pallas_selective_scan` (the state in VMEM
across a chunk, the backward pass making a chunk's states once) where
`pallas_selective_scan.ineligible_reason` allows (a TPU, an inner width in
whole lane blocks), and otherwise (the CPU, micro sizes) as
:func:`selective_scan`, :data:`SCAN_CHUNK` positions at a time under
``jax.checkpoint``; either way forward and backward keep one state a chunk
and nothing of size ``[B, S, d_inner, d_state]``. The attention runs as the
fused Pallas kernels of `pallas_diff_attention` (online softmax, a pair's two
softmaxes in one pass, forward and backward: no block of scores leaves VMEM)
where `pallas_diff_attention.ineligible_reason` allows (a TPU, heads of 64, at
least one tile of positions), and otherwise (the CPU, micro sizes) as
:func:`blocked_differential`, query block by query block, a window block
reading the keys of its band only, rows :data:`ATTN_ROWS` at a time where
there are more. **One token** per env over the player's state, three kinds
side by side: a ring of ``sliding_window`` rows a window layer (slot = index
mod window), the ``full`` layer's keys and values of the whole context (read
by every ``cross`` layer), and ``(conv, ssm)`` a Mamba layer; the memory
units hold nothing. Everything but the whole-sequence attention and scan is
plain JAX.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from sheeprl_tpu.models import pallas_diff_attention, pallas_selective_scan
from sheeprl_tpu.models.transformer import MASKED, SwiGLU
from sheeprl_tpu.telemetry import scopes

Dtype = Any
# the plain path's alone (`selective_scan`; the kernels have their own, `pallas_selective_scan.CHUNK`):
SCAN_CHUNK = 64  # positions of one chunk of the selective scan: what its backward pass keeps states for
SCAN_UNROLL = 8  # positions of one trip of the chunk's loop
# the plain path's alone (`blocked_differential`; the fused kernels have their own tile, `pallas_diff_attention.BLOCK`):
FULL_BLOCK = 384  # most queries of one block of the whole-context attention (its float32 scores are [rows, heads, block, S])
ATTN_ROWS = 2  # rows that share one block of scores where a batch has more (and divides)
#: kind of layer -> the cache leaves a layer of that kind holds (the other kinds hold none)
CACHE_LEAVES = {"ssm": ("conv", "ssm"), "swa": ("win_k", "win_v"), "full": ("full_k", "full_v")}


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    """The published keys of a `phi4flash` config.json, the sizes the family's
    code fixes by convention, and what this chip holds (``layers_held``)."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    intermediate_size: int
    sliding_window: int
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None  # None = ceil(hidden_size / 16)
    initializer_range: float = 0.02
    layers_held: Optional[Tuple[int, int]] = None  # (first, count) of the published layers held here; None = all of them

    def __post_init__(self) -> None:
        if not self.tie_word_embeddings:
            raise ValueError("the phi4flash family ties its head to the embedding")
        if self.num_hidden_layers % 4 or self.mb_per_layer != 2:
            raise ValueError("the layer pattern is written for mb_per_layer 2 and a depth that is a multiple of 4")
        if self.num_attention_heads % 4 or self.num_attention_heads != 2 * self.num_key_value_heads:
            raise ValueError("differential attention pairs adjacent heads: query heads = 2 x key/value heads, in fours")
        if self.dt_rank is None:
            object.__setattr__(self, "dt_rank", -(-self.hidden_size // 16))
        held = self.layers_held or (0, 0)
        first, count = int(held[0]), int(held[1])
        if count <= 0:
            first, count = 0, int(self.num_hidden_layers)
        if first < 0 or first + count > self.num_hidden_layers:
            raise ValueError(f"layers_held {tuple(held)} does not lie inside the {self.num_hidden_layers} layers")
        object.__setattr__(self, "layers_held", (first, count))
        kinds = {self.kind(i): i for i in self.layers}
        if "cross" in kinds and self.kv_layer not in self.layers:
            raise ValueError(f"layers_held {self.layers_held} holds cross-attention layer {kinds['cross']} without layer "
                             f"{self.kv_layer}, whose keys and values it reads")
        if "gmu" in kinds and self.memory_layer not in self.layers:
            raise ValueError(f"layers_held {self.layers_held} holds gated memory unit {kinds['gmu']} without layer "
                             f"{self.memory_layer}, whose scan output is its memory")

    @classmethod
    def from_config(cls, model_cfg: Mapping[str, Any]) -> "HybridConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: (tuple(v) if k == "layers_held" else v) for k, v in dict(model_cfg).items()
                      if k in known and v is not None})

    @property
    def layers(self) -> range:
        """The published indices of the layers held here."""
        return range(self.layers_held[0], self.layers_held[0] + self.layers_held[1])

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def kv_layer(self) -> int:
        return self.num_hidden_layers // 2 + 1

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    def kind(self, index: int) -> str:
        """The mixer of published layer ``index``: ssm, swa, full, cross or gmu."""
        if index % self.mb_per_layer == 0:
            return "ssm" if index <= self.memory_layer else "gmu"
        if index < self.memory_layer:
            return "swa"
        return "full" if index == self.kv_layer else "cross"

    def window(self, index: int) -> Optional[int]:
        """The positions an attention layer's query sees back (itself included): ``sliding_window`` of a ``swa`` layer, else None."""
        return self.sliding_window if self.kind(index) == "swa" else None

    def held(self, kind: str) -> Tuple[int, ...]:
        return tuple(i for i in self.layers if self.kind(i) == kind)

    def lambda_init(self, index: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * index)

    def scan_chunks(self, seq: int, chunk: int = SCAN_CHUNK) -> int:
        """Chunks the selective scans of one whole-sequence pass over ``seq`` positions work through, all held layers,
        at ``chunk`` positions a chunk."""
        return len(self.held("ssm")) * -(-seq // min(chunk, seq))

    def backbone(self, dtype: Dtype, param_dtype: Dtype) -> "HybridDecoder":
        """The decoder of this config (unbound: what it says of the player's state needs no parameters)."""
        return HybridDecoder(self, dtype, param_dtype)


def _init(cfg: HybridConfig):
    return nn.initializers.normal(cfg.initializer_range)


class LayerNorm(nn.Module):
    epsilon: float = 1e-5
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), self.param_dtype)
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.epsilon)
        return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


class GainNorm(nn.Module):
    """RMSNorm with a gain over the last axis, float32 in and out (differential attention's sub-layer norm)."""

    epsilon: float = 1e-5
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), self.param_dtype)
        return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.epsilon) * scale.astype(jnp.float32)


# ------------------------------------------------------------ the selective scan
def scan_step(state: jax.Array, x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array):
    """One position: ``state`` [B, N, D] float32 (the inner width on the lanes),
    ``x``, ``delta`` [B, D], ``a`` [N, D] (negative), ``b``, ``c`` [B, N]."""
    delta = delta.astype(jnp.float32)
    decay = jnp.exp(delta[:, None, :] * a[None])
    state = decay * state + (delta * x.astype(jnp.float32))[:, None, :] * b.astype(jnp.float32)[:, :, None]
    return state, jnp.sum(state * c.astype(jnp.float32)[:, :, None], axis=1)


def selective_scan(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, chunk: int = SCAN_CHUNK):
    """``s_t = exp(delta_t a) s_{t-1} + (delta_t x_t) b_t``, ``y_t = s_t . c_t``
    from a zero state over whole sequences: ``x``, ``delta`` [B, S, D], ``a``
    [N, D], ``b``, ``c`` [B, S, N]. Returns ``y`` [B, S, D] float32 and the last
    state [B, N, D]. ``chunk`` positions at a time, each chunk under
    ``jax.checkpoint``: the backward pass keeps the state at every chunk's
    start and makes a chunk's states again when it gets there. The last chunk
    is padded with ``delta = 0`` positions, which leave the state as it is."""
    batch, seq, width = x.shape
    size = min(chunk, seq)
    count = -(-seq // size)

    def chunks(t):  # [B, S, F] -> [chunks, size, B, F]
        t = jnp.pad(jnp.swapaxes(t, 0, 1), ((0, count * size - seq), (0, 0), (0, 0)))
        return t.reshape(count, size, batch, t.shape[-1])

    def one(state, inputs):
        return jax.lax.scan(lambda s, at: scan_step(s, at[0], at[1], a, at[2], at[3]), state, inputs,
                            unroll=min(SCAN_UNROLL, size))

    state = jnp.zeros((batch, a.shape[0], width), jnp.float32)
    state, ys = jax.lax.scan(jax.checkpoint(one), state, (chunks(x), chunks(delta), chunks(b), chunks(c)))
    return jnp.swapaxes(ys.reshape(count * size, batch, width)[:seq], 0, 1), state


def scan_is_fused(cfg: HybridConfig, batch: int, seq: int, dtype: Dtype) -> bool:
    """Whether :meth:`Mamba.__call__` hands ``batch`` whole sequences of ``seq`` positions to the kernels where this is
    traced (`pallas_selective_scan.ineligible_reason`, the whole rule: a TPU and an eligible shape); else
    :func:`selective_scan` runs."""
    return pallas_selective_scan.ineligible_reason(batch, seq, cfg.d_inner, cfg.d_state, dtype) is None


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The family's: ``Delta`` log-uniform on 1e-3..1e-1 at initialisation, through softplus's inverse."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32) * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class Mamba(nn.Module):
    cfg: HybridConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        inner, state, rank = c.d_inner, c.d_state, c.dt_rank
        self.norm = LayerNorm(c.layer_norm_eps, self.param_dtype)
        self.w_in = self.param("w_in", _init(c), (c.hidden_size, 2 * inner), self.param_dtype)
        self.conv_w = self.param("conv_w", nn.initializers.normal(c.d_conv ** -0.5), (c.d_conv, inner), self.param_dtype)
        self.conv_b = self.param("conv_b", nn.initializers.zeros, (inner,), self.param_dtype)
        self.w_x = self.param("w_x", _init(c), (inner, rank + 2 * state), self.param_dtype)
        self.w_dt = self.param("w_dt", nn.initializers.normal(rank ** -0.5), (rank, inner), self.param_dtype)
        self.b_dt = self.param("b_dt", _dt_bias_init, (inner,), jnp.float32)
        self.A_log = self.param(
            "A_log", lambda key, shape, dtype: jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape),
            (inner, state), jnp.float32)
        self.D = self.param("D", nn.initializers.ones, (inner,), jnp.float32)
        self.w_out = self.param("w_out", _init(c), (inner, c.hidden_size), self.param_dtype)

    def _inputs(self, x: jax.Array):
        """``x`` [..., D] after the convolution: ``delta`` float32 [..., D], ``a`` [N, D], ``b``, ``c`` [..., N]."""
        c = self.cfg
        dbc = x @ self.w_x.astype(self.dtype)
        d, b, cc = dbc[..., :c.dt_rank], dbc[..., c.dt_rank:c.dt_rank + c.d_state], dbc[..., c.dt_rank + c.d_state:]
        delta = jax.nn.softplus(jnp.dot(d, self.w_dt.astype(self.dtype), preferred_element_type=jnp.float32) + self.b_dt)
        return delta, -jnp.exp(self.A_log.astype(jnp.float32)).T, b, cc

    def _gate(self, y: jax.Array, x: jax.Array, z: jax.Array):
        memory = (y + self.D * x.astype(jnp.float32)).astype(self.dtype)
        return (memory * nn.silu(z)) @ self.w_out.astype(self.dtype), memory

    def __call__(self, u: jax.Array, start: jax.Array):
        """``u`` [B, S, H] left-padded (row b's context begins at ``start[b]``).
        Returns the output, the memory ``y`` [B, S, D] and what a cache keeps:
        the last ``d_conv - 1`` inputs of the convolution and the last state."""
        c = self.cfg
        seq, taps = u.shape[1], c.d_conv
        xz = self.norm(u) @ self.w_in.astype(self.dtype)
        x, z = xz[..., :c.d_inner], xz[..., c.d_inner:]
        real = (jnp.arange(seq)[None, :] >= start[:, None])[..., None]
        x = jnp.pad(jnp.where(real, x, 0), ((0, 0), (taps - 1, 0), (0, 0)))
        tail = x[:, seq:]
        conv_w = self.conv_w.astype(self.dtype)
        x = sum(x[:, k:k + seq] * conv_w[k] for k in range(taps)) + self.conv_b.astype(self.dtype)
        x = jnp.where(real, nn.silu(x), 0)
        delta, a, b, cc = self._inputs(x)
        scan = pallas_selective_scan.selective_scan if scan_is_fused(c, *u.shape[:2], self.dtype) else selective_scan
        y, state = scan(x, delta, a, b, cc)
        out, memory = self._gate(y, x, z)
        return out, memory, (tail, state)

    def decode(self, u: jax.Array, conv: jax.Array, ssm: jax.Array):
        """One token per env: ``u`` [E, H], ``conv`` [E, d_conv - 1, D], ``ssm`` [E, N, D] float32."""
        c = self.cfg
        xz = self.norm(u) @ self.w_in.astype(self.dtype)
        x, z = xz[..., :c.d_inner], xz[..., c.d_inner:]
        taps = jnp.concatenate([conv, x[:, None].astype(conv.dtype)], axis=1)
        x = nn.silu(jnp.sum(taps * self.conv_w.astype(self.dtype)[None], axis=1) + self.conv_b.astype(self.dtype))
        delta, a, b, cc = self._inputs(x)
        ssm, y = scan_step(ssm, x, delta, a, b, cc)
        out, memory = self._gate(y, x, z)
        return out, memory, taps[:, 1:], ssm


# ------------------------------------------------------ differential attention
def _differential(q: jax.Array, k: jax.Array, v: jax.Array, valid: jax.Array, lam: jax.Array) -> jax.Array:
    """``q`` [B, Q, heads, d], ``k``, ``v`` [B, K, kv heads, d], ``valid``
    [B, Q, K]: adjacent heads in pairs, a pair's values side by side, ``P_1 v
    - lam P_2 v`` -> float32 [B, Q, heads / 2, 2 d]. Softmax in float32."""
    B, Q, heads, d = q.shape
    K, pairs = k.shape[1], k.shape[2] // 2
    q = q.reshape(B, Q, pairs, heads // (2 * pairs), 2, d)  # [.., kv pair, query pair of its group, member, d]
    k = k.reshape(B, K, pairs, 2, d)
    v = v.reshape(B, K, pairs, 2 * d)
    scores = jnp.einsum("bqjgid,bkjid->bjgiqk", q, k, preferred_element_type=jnp.float32) * d ** -0.5
    probs = jax.nn.softmax(jnp.where(valid[:, None, None, None], scores, MASKED), axis=-1)
    out = jnp.einsum("bjgiqk,bkjv->bqjgiv", probs.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return (out[..., 0, :] - lam * out[..., 1, :]).reshape(B, Q, heads // 2, 2 * d)


def blocked_differential(q: jax.Array, k: jax.Array, v: jax.Array, start: jax.Array, lam: jax.Array,
                         window: Optional[int]) -> jax.Array:
    """The causal differential attention of left-padded whole sequences in plain
    JAX, query block by query block, each under ``jax.checkpoint``: a block
    reads the keys up to its end, from its band's beginning on where there is a
    ``window`` (position t sees ``t - window + 1 .. t``), and only the keys from
    ``start[b]`` on are keys at all. What runs where `pallas_diff_attention`
    does not, and what it is held to."""
    seq = q.shape[1]

    def block(q, k, v, first, begin, start):  # queries [first, first + len) against the keys [begin, begin + K)
        at_q = first + jnp.arange(q.shape[1])
        at_k = begin + jnp.arange(k.shape[1])
        valid = at_k[None, :] <= at_q[:, None]
        if window is not None:
            valid = valid & (at_k[None, :] > at_q[:, None] - window)
        return _differential(q, k, v, valid[None] & (at_k[None, None, :] >= start[:, None, None]), lam)

    block = jax.checkpoint(block, static_argnums=(3, 4))

    def rows(q, k, v, start):
        size = min(window or FULL_BLOCK, -(-seq // 4))
        outs = []
        for first in range(0, seq, size):
            end = min(first + size, seq)
            begin = 0 if window is None else max(0, first - window + 1)
            outs.append(block(q[:, first:end], k[:, begin:end], v[:, begin:end], first, begin, start))
        return jnp.concatenate(outs, axis=1)

    batch = q.shape[0]
    if batch <= ATTN_ROWS or batch % ATTN_ROWS:
        return rows(q, k, v, start)
    grouped = lambda t: t.reshape(batch // ATTN_ROWS, ATTN_ROWS, *t.shape[1:])  # noqa: E731
    out = jax.lax.map(lambda args: rows(*args), (grouped(q), grouped(k), grouped(v), grouped(start)))
    return out.reshape(batch, *out.shape[2:])


def attention_is_fused(cfg: HybridConfig, seq: int, window: Optional[int], dtype: Dtype) -> bool:
    """Whether :meth:`DiffAttention.__call__` hands whole sequences of ``seq``
    positions to the fused kernels where this is traced (`pallas_diff_attention.ineligible_reason`,
    the whole rule: a TPU and an eligible shape); else :func:`blocked_differential` runs."""
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    return pallas_diff_attention.ineligible_reason(seq, cfg.head_dim, window, dtype, group) is None


class DiffAttention(nn.Module):
    """Differential grouped-query attention of published layer ``index``:
    with its own keys and values (``swa``, ``full``) or another layer's (``cross``)."""

    cfg: HybridConfig
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.kind = c.kind(self.index)
        width = c.num_attention_heads * c.head_dim
        own = 0 if self.kind == "cross" else 2 * c.num_key_value_heads * c.head_dim
        self.norm = LayerNorm(c.layer_norm_eps, self.param_dtype)
        name = "wq" if self.kind == "cross" else "wqkv"
        self.w_in = self.param(name, _init(c), (c.hidden_size, width + own), self.param_dtype)
        self.b_in = self.param(name.replace("w", "b", 1), nn.initializers.zeros, (width + own,), self.param_dtype)
        self.wo = self.param("wo", _init(c), (width, c.hidden_size), self.param_dtype)
        self.bo = self.param("bo", nn.initializers.zeros, (c.hidden_size,), self.param_dtype)
        vector = nn.initializers.normal(0.1)
        self.lq1 = self.param("lq1", vector, (c.head_dim,), jnp.float32)
        self.lk1 = self.param("lk1", vector, (c.head_dim,), jnp.float32)
        self.lq2 = self.param("lq2", vector, (c.head_dim,), jnp.float32)
        self.lk2 = self.param("lk2", vector, (c.head_dim,), jnp.float32)
        self.subln = GainNorm(c.layer_norm_eps, self.param_dtype)

    def _lambda(self) -> jax.Array:
        return jnp.exp(jnp.sum(self.lq1 * self.lk1)) - jnp.exp(jnp.sum(self.lq2 * self.lk2)) + self.cfg.lambda_init(self.index)

    def project(self, u: jax.Array):
        """``u`` [..., H]: the queries [..., heads, d] and, of a layer with its own, keys and values [..., kv heads, d]."""
        c = self.cfg
        qkv = self.norm(u) @ self.w_in.astype(self.dtype) + self.b_in.astype(self.dtype)
        width, kv = c.num_attention_heads * c.head_dim, c.num_key_value_heads * c.head_dim
        q = qkv[..., :width].reshape(*u.shape[:-1], c.num_attention_heads, c.head_dim)
        if self.kind == "cross":
            return q, None, None
        heads = lambda t: t.reshape(*u.shape[:-1], c.num_key_value_heads, c.head_dim)  # noqa: E731
        return q, heads(qkv[..., width:width + kv]), heads(qkv[..., width + kv:])

    def _out(self, mixed: jax.Array) -> jax.Array:
        """The pairs' float32 results [..., heads / 2, 2 d] through the sub-layer norm and the output projection."""
        mixed = self.subln(mixed) * (1.0 - self.cfg.lambda_init(self.index))
        mixed = mixed.reshape(*mixed.shape[:-2], -1).astype(self.dtype)
        return mixed @ self.wo.astype(self.dtype) + self.bo.astype(self.dtype)

    def __call__(self, u: jax.Array, start: jax.Array, shared: Optional[Tuple[jax.Array, jax.Array]] = None):
        """Whole sequences ``u`` [B, S, H]; ``shared`` = the ``full`` layer's (k, v) for a ``cross`` layer. Returns the output and (k, v)."""
        q, k, v = self.project(u)
        if self.kind == "cross":
            k, v = shared
        window = self.cfg.window(self.index)
        fused = attention_is_fused(self.cfg, u.shape[1], window, self.dtype)
        attention = pallas_diff_attention.diff_attention if fused else blocked_differential
        return self._out(attention(q, k, v, start, self._lambda(), window)), (k, v)

    def attend(self, q: jax.Array, k: jax.Array, v: jax.Array, valid: jax.Array) -> jax.Array:
        """One query per env, ``q`` [E, heads, d], over cached ``k``, ``v`` [E, K, kv heads, d] with ``valid`` [E, K]."""
        return self._out(_differential(q[:, None], k, v, valid[:, None], self._lambda())[:, 0])



class GatedMemoryUnit(nn.Module):
    cfg: HybridConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array, memory: jax.Array) -> jax.Array:
        c = self.cfg
        w1 = self.param("w1", _init(c), (c.hidden_size, c.d_inner), self.param_dtype).astype(self.dtype)
        w2 = self.param("w2", _init(c), (c.d_inner, c.hidden_size), self.param_dtype).astype(self.dtype)
        gate = LayerNorm(c.layer_norm_eps, self.param_dtype, name="norm")(u) @ w1
        return (memory.astype(self.dtype) * nn.silu(gate)) @ w2


# ------------------------------------------------------------------ the blocks
_SCOPES = {"ssm": scopes.LM_SSM, "swa": scopes.LM_SWA, "full": scopes.LM_FULL_ATTN, "cross": scopes.LM_CROSS_ATTN,
           "gmu": scopes.LM_GMU}


def _write(rows: jax.Array, new: jax.Array, at: jax.Array) -> jax.Array:
    """``new`` [E, ...] written into ``rows`` [E, T, ...] at index ``at`` [E] of each env's row."""
    put = lambda row, value, i: jax.lax.dynamic_update_slice(row, value[None], (i,) + (0,) * value.ndim)  # noqa: E731
    return jax.vmap(put)(rows, new.astype(rows.dtype), at)


class HybridLayer(nn.Module):
    """Published layer ``index``: its mixer, then the SwiGLU. ``carried`` =
    ``(memory, (k, v))``: what layers ``N/2`` and ``N/2 + 1`` hand to those after them."""

    cfg: HybridConfig
    index: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.kind = c.kind(self.index)
        # the mixer's parameters sit under its kind: layer_<index>/<ssm|swa|full|cross|gmu>/...
        if self.kind == "ssm":
            self.mixer = Mamba(c, self.dtype, self.param_dtype, name="ssm")
        elif self.kind == "gmu":
            self.mixer = GatedMemoryUnit(c, self.dtype, self.param_dtype, name="gmu")
        else:
            self.mixer = DiffAttention(c, self.index, self.dtype, self.param_dtype, name=self.kind)
        self.mlp_norm = LayerNorm(c.layer_norm_eps, self.param_dtype)
        self.mlp = SwiGLU(c, c.intermediate_size, self.dtype, self.param_dtype)

    def _mlp(self, x: jax.Array) -> jax.Array:
        with scopes.scope(scopes.LM_DENSE_MLP):
            return x + self.mlp(self.mlp_norm(x))

    def __call__(self, x: jax.Array, start: jax.Array, carried):
        """Whole sequences. Returns ``x``, what a cache keeps of this layer (None for a layer that keeps nothing) and ``carried``."""
        memory, shared = carried
        kept = None
        with scopes.scope(_SCOPES[self.kind]):
            if self.kind == "ssm":
                out, y, kept = self.mixer(x, start)
                memory = y if self.index == self.cfg.memory_layer else memory
            elif self.kind == "gmu":
                out = self.mixer(x, memory)
            else:
                out, own = self.mixer(x, start, shared)
                if self.kind == "swa":
                    kept = own
                elif self.kind == "full":
                    kept = shared = own
            x = x + out
        return self._mlp(x), kept, (memory, shared)

    def decode(self, x: jax.Array, state, pos: jax.Array, start: jax.Array, carried):
        """One token per env at index ``pos`` [E]; ``state`` = this layer's cache leaves (None where it has none)."""
        memory, shared = carried
        if self.kind == "ssm":
            out, y, conv, ssm = self.mixer.decode(x, *state)
            state = (conv, ssm)
            memory = y if self.index == self.cfg.memory_layer else memory
        elif self.kind == "gmu":
            out = self.mixer(x, memory)
        else:
            q, k, v = self.mixer.project(x)
            if self.kind == "swa":
                window = state[0].shape[1]
                slot = jnp.arange(window)[None, :]
                held = pos[:, None] - jnp.mod(pos[:, None] - slot, window)  # the index a slot holds once `pos` is written
                state = (_write(state[0], k, jnp.mod(pos, window)), _write(state[1], v, jnp.mod(pos, window)))
                valid = (held >= start[:, None]) & (held >= 0)
                keys = state
            else:
                if self.kind == "full":
                    state = shared = (_write(state[0], k, pos), _write(state[1], v, pos))
                at = jnp.arange(shared[0].shape[1])[None, :]
                valid = (at >= start[:, None]) & (at <= pos[:, None])
                keys = shared
            out = self.mixer.attend(q, *keys, valid)
        return self._mlp(x + out), state, (memory, shared)


class HybridDecoder(nn.Module):
    """Embedding, the held layers, the final norm. Heads are the caller's (the vocabulary head is the embedding)."""

    cfg: HybridConfig
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    def setup(self) -> None:
        c = self.cfg
        self.embedding = self.param("embedding", _init(c), (c.vocab_size, c.hidden_size), self.param_dtype)
        layer = nn.remat(HybridLayer)  # a layer's internals are made again in the backward pass, its input alone is kept
        self.layers = [layer(c, i, self.dtype, self.param_dtype, name=f"layer_{i}") for i in c.layers]
        self.final_norm = LayerNorm(c.layer_norm_eps, self.param_dtype)

    def embed(self, tokens: jax.Array) -> jax.Array:
        with scopes.scope(scopes.LM_EMBED):
            return jnp.take(self.embedding, tokens, axis=0).astype(self.dtype)

    def __call__(self, tokens: jax.Array, start: jax.Array):
        """``tokens`` [B, S] left-padded: row b's context begins at index
        ``start[b]``. Returns the hidden states before the final norm, per held
        layer what a cache keeps (None where nothing), and no stats."""
        x = self.embed(tokens)
        kept, carried = [], (None, None)
        for layer in self.layers:
            x, layer_kept, carried = layer(x, start, carried)
            kept.append(layer_kept)
        return x, kept, []

    # ------------------------------------------------------------ the player's state (no parameters, no scope of its own)
    #: cache leaf -> the kind of player state it is (`player/cache_bytes/<kind>`)
    cache_kinds = {"win_k": "window", "win_v": "window", "full_k": "full", "full_v": "full", "conv": "state", "ssm": "state"}

    @nn.nowrap
    def scan_chunks(self, seq: int) -> int:
        """Chunks the scans of one whole-sequence pass work through, at the chunk of the path that runs where this is asked."""
        fused = scan_is_fused(self.cfg, 1, seq, self.dtype)
        return self.cfg.scan_chunks(seq, pallas_selective_scan.CHUNK if fused else SCAN_CHUNK)

    @nn.nowrap
    def fused_scan_layers(self, seq: int) -> int:
        """Held Mamba layers whose whole-sequence scan over ``seq`` positions runs as kernels where this is asked."""
        return len(self.cfg.held("ssm")) * scan_is_fused(self.cfg, 1, seq, self.dtype)

    @nn.nowrap
    def fused_attention_layers(self, seq: int) -> int:
        """Held attention layers whose whole-sequence form over ``seq`` positions runs as fused kernels where this is asked."""
        c = self.cfg
        return sum(attention_is_fused(c, seq, c.window(i), self.dtype) for i in c.layers if c.kind(i) in ("swa", "full", "cross"))

    @nn.nowrap
    def attention_tile_visits(self, start: jax.Array, seq: int) -> None:
        """No tile count is kept for the differential kernels (None): they walk from a row's first tile, padding or not."""
        return None

    @nn.nowrap
    def prefill_rows(self, num_envs: int, prompt_len: int) -> Optional[int]:
        """Prompts that go through the whole-sequence form together: all of them (None). The scans are one position
        after another whatever the rows; the fused attention takes a row a grid step, and the plain path makes its
        blocks of scores :data:`ATTN_ROWS` rows at a time."""
        return None

    @nn.nowrap
    def init_cache(self, num_envs: int, context: int) -> Dict[str, Any]:
        c = self.cfg
        rows = {"win_k": c.sliding_window, "win_v": c.sliding_window, "full_k": context, "full_v": context}
        shapes = {name: ((num_envs, rows[name], c.num_key_value_heads, c.head_dim), self.dtype) for name in rows}
        shapes["conv"] = ((num_envs, c.d_conv - 1, c.d_inner), self.dtype)
        shapes["ssm"] = ((num_envs, c.d_state, c.d_inner), jnp.float32)
        return {name: tuple(jnp.zeros(*shapes[name]) for _ in c.held(kind)) for kind, names in CACHE_LEAVES.items() for name in names}

    @nn.nowrap
    def prefill_cache(self, cache: Dict[str, Any], kept: list, prompt_len: int, keep) -> Dict[str, Any]:
        """``cache`` filled from what the whole-sequence form kept of prompts of ``prompt_len`` positions, for the envs
        ``keep(new, old)`` takes the new leaf for: the ring from the last ``sliding_window`` of them, each at its slot."""
        c = self.cfg
        window = c.sliding_window
        olds = {name: iter(leaves) for name, leaves in cache.items()}
        new: Dict[str, list] = {name: [] for name in cache}
        for index, layer_kept in zip(c.layers, kept):
            kind = c.kind(index)
            for name, value in zip(CACHE_LEAVES.get(kind, ()), layer_kept or ()):
                old = next(olds[name])
                value = value.astype(old.dtype)
                if kind == "swa" and prompt_len > window:  # index i sits at slot i mod window
                    value = jnp.roll(value[:, prompt_len - window:], prompt_len % window, axis=1)
                elif kind != "ssm":
                    value = old.at[:, :prompt_len].set(value)
                new[name].append(keep(value, old))
        return {name: tuple(leaves) for name, leaves in new.items()}

    def decode(self, tokens: jax.Array, cache: Dict[str, Any], pos: jax.Array, start: jax.Array):
        """``tokens`` [E] at indices ``pos`` [E]; ``cache`` as :meth:`init_cache` made it, one array a layer and leaf."""
        c = self.cfg
        x = self.embed(tokens)
        olds = {name: iter(leaves) for name, leaves in cache.items()}
        new: Dict[str, list] = {name: [] for name in cache}
        carried = (None, None)
        for index, layer in zip(c.layers, self.layers):
            own = CACHE_LEAVES.get(c.kind(index))
            state = tuple(next(olds[name]) for name in own) if own else None
            x, state, carried = layer.decode(x, state, pos, start, carried)
            for name, leaf in zip(own or (), state or ()):
                new[name].append(leaf)
        return x, {name: tuple(leaves) for name, leaves in new.items()}

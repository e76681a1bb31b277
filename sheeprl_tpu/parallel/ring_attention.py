"""Ring attention + Ulysses all-to-all: sequence parallelism over the mesh.

Ring attention (https://arxiv.org/abs/2310.01889, public algorithm): every
device holds one contiguous shard of the sequence; queries stay put while the
K/V shards travel around the device ring (`lax.ppermute` over ICI), and each
arriving block folds into the local attention output with the online-softmax
(flash-style) update. Peak memory is O(T/N) per device and the N-step ring
overlaps compute with neighbor transfers.

Ulysses-style `seq_all_to_all` is the alternative CP scheme: an all-to-all
that re-shards [seq-sharded, all heads] <-> [all seq, head-sharded] so a
standard attention kernel runs on full sequences with 1/N of the heads.

Both run inside `shard_map` over a named mesh axis; causal masking uses
global positions derived from the device's ring index.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P


def _block_attention(q, k, v, q_pos, k_pos, scale, causal):
    """One (q-shard, k-block) partial: returns (unnormalized out, row max,
    row sumexp) for the online-softmax merge. Shapes: q [B, Tq, H, D],
    k/v [B, Tk, H, D]."""
    # Precision pinned HIGHEST: the ambient default can be bf16-grade, and
    # softmax noise compounds across the N-block online merge.
    logits = (
        jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=jax.lax.Precision.HIGHEST) * scale
    )  # [B, H, Tq, Tk]
    if causal:
        mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
        logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)  # [B, H, Tq]
    # Fully-masked rows produce -inf maxima; exp(-inf - -inf) traps — guard.
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(logits - m_safe[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    l = p.sum(-1)  # [B, H, Tq]
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=jax.lax.Precision.HIGHEST)
    return out, m_safe, l


def _ring_attention_local(q, k, v, *, axis_name: str, n: int, causal: bool, scale: float):
    """Per-device body under shard_map: q/k/v are the LOCAL sequence shards
    [B, Tl, H, D]. ``n`` is the static mesh axis size, passed from the
    wrapper: `lax.axis_size` only exists in newer jax, and the ring loop
    needs a Python int to unroll at trace time anyway."""
    my = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    q_pos = my * t_local + jnp.arange(t_local)

    # Receive from the next rank: after i steps we hold the block that
    # started on rank (my + i) % n.
    perm = [(j, (j - 1) % n) for j in range(n)]

    out = jnp.zeros_like(q)
    # Derive the accumulators from q so they carry the same varying manual
    # axes as the loop outputs (a plain jnp.zeros would be axis-invariant and
    # trip shard_map's carry type check).
    zeros_bht = jnp.zeros_like(q[..., 0]).transpose(0, 2, 1)  # [B, H, Tl]
    m = zeros_bht - jnp.inf
    l = zeros_bht

    # The mesh axis size is static, so the ring unrolls at trace time; the
    # last block is folded WITHOUT a trailing permute (its result would be
    # discarded — n-1 neighbor transfers suffice for n blocks).
    k_blk, v_blk = k, v
    for i in range(n):
        src = (my + i) % n
        k_pos = src * t_local + jnp.arange(t_local)
        blk_out, blk_m, blk_l = _block_attention(q, k_blk, v_blk, q_pos, k_pos, scale, causal)
        new_m = jnp.maximum(m, blk_m)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(blk_m - new_m)
        out = out * alpha.transpose(0, 2, 1)[..., None] + blk_out * beta.transpose(0, 2, 1)[..., None]
        l = l * alpha + blk_l * beta
        m = new_m
        if i < n - 1:
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
    # Rows with zero mass (fully masked) stay zero.
    denom = jnp.where(l == 0.0, 1.0, l)
    return out / denom.transpose(0, 2, 1)[..., None]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Sequence-parallel attention over ``mesh``'s ``axis_name``.

    q/k/v: GLOBAL [B, T, H, D] arrays whose T axis is (or will be) sharded
    over ``axis_name``; returns the attention output with the same sharding.
    T must divide evenly by the axis size.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local,
            axis_name=axis_name,
            n=mesh.shape[axis_name],
            causal=causal,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def _seq_all_to_all_local(x, *, axis_name: str, to_heads: bool):
    if to_heads:
        # [B, Tl, H, D] -> [B, T, H/n, D]: each rank keeps head-chunk `rank`
        # over the FULL sequence (tiled all_to_all splits heads, concats time).
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    # [B, T, H/n, D] -> [B, Tl, H, D]
    return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)


def seq_all_to_all(
    x: jax.Array, mesh: Mesh, axis_name: str, to_heads: bool = True
) -> jax.Array:
    """Ulysses-style exchange: re-shard [B, T(sharded), H, D] into
    [B, T, H(sharded), D] (``to_heads=True``) or back. H (or T) must divide
    by the axis size."""
    in_spec = P(None, axis_name, None, None) if to_heads else P(None, None, axis_name, None)
    out_spec = P(None, None, axis_name, None) if to_heads else P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_seq_all_to_all_local, axis_name=axis_name, to_heads=to_heads),
        mesh=mesh,
        in_specs=(in_spec,),
        out_specs=out_spec,
    )
    return fn(x)

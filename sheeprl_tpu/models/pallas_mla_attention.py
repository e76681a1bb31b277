"""Whole-sequence latent attention as fused Pallas TPU kernels (the flash form).

What `models.transformer.MLA.__call__` needs between its projections and its
output product: the causal softmax of left-padded sequences over keys that
are ``qk_nope_head_dim`` wide per head plus one rotated ``k_rope`` vector a
position, shared by every head. In plain XLA each block of float32 scores
``[B, heads, q, k]`` goes to HBM and comes back about ten times (two score
products added, scale, mask, softmax, cast; the same again backward). Here
no block of scores leaves VMEM:

- **forward** (:func:`_fwd_kernel`, ``mla_attention_fwd``): one grid step per
  (sequence, head, query tile). The head's keys and values stay in VMEM while
  the step walks the key tiles up to the diagonal with the running maximum,
  the running sum and the output accumulator (online softmax), and writes
  the output tile and the rows' log-sum-exp.
- **backward** (:func:`_bwd_kernel`, ``mla_attention_bwd``): one grid step
  per (sequence, head, key tile). It walks the query tiles from the diagonal
  down, makes the probabilities again from the saved log-sum-exp (transposed:
  keys on sublanes, queries on lanes, so the per-query log-sum-exp and
  ``delta = sum(out * d_out)``, which the head's first step makes, are rows)
  and forms dV, dK and dQ; dQ of the whole sequence stays in VMEM across the
  head's key tiles. ``k_rope``'s gradient leaves per head and is summed over
  the heads outside. The forward's output reaches it in float32.

Precision is the configuration's: the operands of every product in the dtype
they come in (bfloat16 under ``bf16-mixed``), float32 accumulation; scores,
scale, mask, maximum, sums, log-sum-exp and the accumulators in float32; the
probabilities are cast only as the operand of their products.

Masks: causal, and keys valid from ``start[b]`` on. Key tiles wholly above
the diagonal are never visited, nor is a tile wholly inside a row's left
padding: a row's walk starts at :func:`first_tile`, the first tile that holds
a valid key. A query tile before it writes the zero output and the
no-key log-sum-exp at once; a key tile before it writes zero gradients at
once. What is skipped adds exactly nothing (a score there is ``MASKED``: its
probability is exactly 0 once a row has met a valid key, and the row's sums
restart from it exactly), so every result is the one a walk from tile 0
gives, to the bit. :func:`tile_visits` counts the walk by the same rule. A
query row with no valid key (a position inside the left padding) gives a
zero output and sends no gradient anywhere.

The sequence is padded to the tile on the right inside the wrapper (causality
keeps real queries off the padded keys) and ``qk_rope_head_dim`` to the
128-lane tile, so every block is lane-aligned and nothing is transposed on
the way in: the arrays go in as ``[B, S, heads * width]`` and a block is one
head's columns. Keys and values are expanded from the (padded) latent by the
wrapper itself, one product whose result ``[B, S, heads * 256]`` holds each
head's keys beside its values: the kernels read it, and write its gradient,
as it lies.

Dispatch: :func:`ineligible_reason` is the whole rule. The kernels run when
the backend is a TPU and the shape is eligible ("eligible" implies
"compiles": tests/test_utils/test_tpu_aot_compiles.py asks the TPU compiler);
otherwise the caller's blocked plain-JAX path runs (CPU tests, micro sizes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = -1e30  # a masked score: finite, so the running maximum of a row with no valid key stays finite
NO_KEY_LSE = 1e30  # the log-sum-exp written for such a row: exp(score - it) is 0, so the row sends no gradient
LANES = 128
#: Queries and keys of one (square) tile. On the chip (PERF.md, PR 29) the time
#: per score falls with the tile up to 512: a tile's rows are what one load of
#: the MXU's weights is spread over, its columns what the per-row running
#: maximum, sum and rescaling are spread over; 2080 positions pad to 2560.
BLOCK = 512
#: What one grid step may hold in VMEM (the kernels ask the compiler for this
#: much; a v5e core has 128 MiB) and how much of it the blocks may fill.
_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_VMEM_BLOCKS_BYTES = 48 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _padded_len(seq: int) -> int:
    return seq + (-seq) % BLOCK


def _vmem_bytes(seq: int, itemsize: int) -> int:
    """Upper bound on the VMEM one grid step of the backward kernel holds (the
    forward holds less): the whole-sequence blocks of one head double-buffered
    (q_nope, q_rope, d_out and the float32 out in; dq_nope, dq_rope out), the two float32 dQ
    accumulators, the per-tile blocks (keys and values, k_rope and their
    gradients) and the score-sized temporaries."""
    sp = _padded_len(seq)
    whole = sp * LANES
    tile = BLOCK * LANES
    rows = 3 * 8 * sp * 4  # the log-sum-exp (double-buffered) and delta, sublane-padded
    blocks = 2 * (5 * whole * itemsize + whole * 4) + 2 * whole * 4 + rows  # `out` comes in float32
    tiles = 2 * (3 * tile * itemsize + 2 * tile * itemsize + tile * 4) + 3 * tile * 4
    return blocks + tiles + 8 * BLOCK * BLOCK * 4


def ineligible_reason(seq: int, nope_dim: int, rope_dim: int, v_dim: int, dtype) -> Optional[str]:
    """Why the kernels cannot take whole sequences of this shape here, or None when they can."""
    # where what is traced now will run: the `jax.default_device` in force (a player acting from the host), else the default backend
    device = jax.config.jax_default_device
    backend = getattr(device, "platform", device) or jax.default_backend()
    if backend != "tpu":
        return f"the backend is {backend}, not a TPU"
    return shape_ineligible_reason(seq, nope_dim, rope_dim, v_dim, dtype)


def shape_ineligible_reason(seq: int, nope_dim: int, rope_dim: int, v_dim: int, dtype) -> Optional[str]:
    """The part of the rule that is about the shape alone (what the ahead-of-time compiles hold to the compiler)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"operands of {dtype.name} (the kernels take bfloat16 or float32)"
    if nope_dim != LANES or v_dim != LANES:
        return f"head widths {nope_dim} (keys) and {v_dim} (values) are not the {LANES}-lane tile"
    if not 0 < rope_dim <= LANES:
        return f"a rotated width of {rope_dim} does not fit one {LANES}-lane tile"
    if seq < BLOCK:
        return f"{seq} positions are fewer than one tile of {BLOCK}"
    need = _vmem_bytes(seq, dtype.itemsize)
    if need > _VMEM_BLOCKS_BYTES:
        return (
            f"one head's blocks of {seq} positions need {need / 2**20:.1f} MiB of VMEM per grid step, over the "
            f"{_VMEM_BLOCKS_BYTES / 2**20:.0f} MiB the kernels keep for them"
        )
    return None


def first_tile(start):
    """The first tile of a row whose keys begin at ``start`` that holds a valid
    key: the tiles before it lie wholly in the left padding, and the kernels'
    walks start here."""
    return start // BLOCK


def tile_visits(start: jax.Array, seq: int):
    """The (query tile, key tile) pairs one kernel call over ``seq`` positions
    visits for each row of ``start`` [B] and each head, and those it skips as
    wholly left padding: ``(visits [B], skipped [B])``. The forward's query
    tile ``i`` visits the key tiles ``first .. i``, the backward's key tile
    ``j`` the query tiles ``j ..`` to the end: both walk the pairs on or below
    the diagonal from :func:`first_tile` on."""
    tiles = _padded_len(seq) // BLOCK
    kept = tiles - jnp.minimum(first_tile(start), tiles)
    visits = kept * (kept + 1) // 2
    return visits, tiles * (tiles + 1) // 2 - visits


def _tile_iota(axis: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), axis)


# ------------------------------------------------------------------ forward
def _fwd_kernel(start_ref, qn_ref, qr_ref, kv_ref, kr_ref, out_ref, lse_ref, *, scale: float):
    b, i = pl.program_id(0), pl.program_id(2)
    start = start_ref[b]
    first = first_tile(start)

    @pl.when(i < first)
    def _all_padding():  # no row of the tile has a valid key: what such a row gets, with no key tile visited
        out_ref[0] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)
        lse_ref[0, 0] = jnp.full(lse_ref.shape[2:], NO_KEY_LSE, jnp.float32)

    @pl.when(i >= first)
    def _walk():
        qn, qr = qn_ref[0], qr_ref[0]  # [BLOCK, 128]
        key_at = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)

        def tile(j, carry, diagonal: bool):
            m, l, acc = carry
            at = pl.multiple_of(j * BLOCK, BLOCK)
            kn, v, kr = kv_ref[0, pl.ds(at, BLOCK), :LANES], kv_ref[0, pl.ds(at, BLOCK), LANES:], kr_ref[0, pl.ds(at, BLOCK), :]
            s = jax.lax.dot_general(qn, kn, _NT, preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(qr, kr, _NT, preferred_element_type=jnp.float32)
            s = s * scale + jnp.where(at + key_at >= start, 0.0, MASKED)  # a key inside the left padding
            if diagonal:  # tiles are square: on the diagonal tile key k is visible to query q where k <= q, tile-locally
                s = jnp.where(_tile_iota(1) <= _tile_iota(0), s, MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return m_new, l, acc

        init = (
            jnp.full((BLOCK, 1), MASKED, jnp.float32),
            jnp.zeros((BLOCK, 1), jnp.float32),
            jnp.zeros((BLOCK, LANES), jnp.float32),
        )
        # the key tiles below the diagonal need no causal mask; tiles above it, and before the first, are never visited
        m, l, acc = tile(i, jax.lax.fori_loop(first, i, functools.partial(tile, diagonal=False), init), diagonal=True)
        seen = m > 0.5 * MASKED  # the row met a valid key
        out_ref[0] = jnp.where(seen, acc / l, 0.0).astype(out_ref.dtype)
        lse = jnp.where(seen, m + jnp.log(l), NO_KEY_LSE)
        # the rows' log-sum-exp leaves as a row (queries on lanes), the way the backward kernel reads it
        lse_ref[0, 0] = jnp.broadcast_to(lse, (BLOCK, LANES)).T[:1]


def _forward(qn, qr, kv, kr, start, scale: float, interpret: bool, out_dtype=None):
    """The padded forms: ``qn``, ``qr`` [B, Sp, h*128], ``kv`` [B, Sp, h*256]
    (a head's keys, then its values), ``kr`` [B, Sp, 128], ``start`` [B].
    Returns out [B, Sp, h*128] (in ``out_dtype``, the queries' by default) and
    the rows' log-sum-exp [B, h, 1, Sp] (float32)."""
    batch, sp, width = qn.shape
    heads = width // LANES
    tile = pl.BlockSpec((1, BLOCK, LANES), lambda b, h, i, start: (b, i, h))
    whole = pl.BlockSpec((1, sp, 2 * LANES), lambda b, h, i, start: (b, 0, h))
    shared = pl.BlockSpec((1, sp, LANES), lambda b, h, i, start: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, sp // BLOCK),
            in_specs=[tile, tile, whole, shared],
            out_specs=[tile, pl.BlockSpec((1, 1, 1, BLOCK), lambda b, h, i, start: (b, h, 0, i))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qn.shape, out_dtype or qn.dtype),
            jax.ShapeDtypeStruct((batch, heads, 1, sp), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="mla_attention_fwd",
    )(start, qn, qr, kv, kr)


# ------------------------------------------------------------------ backward
def _bwd_kernel(start_ref, qn_ref, qr_ref, kv_ref, kr_ref, out_ref, do_ref, lse_ref,
                dqn_ref, dqr_ref, dkv_ref, dkr_ref, dqn_acc, dqr_acc, delta_ref, *, scale: float):
    b, j = pl.program_id(0), pl.program_id(2)
    tiles = pl.num_programs(2)
    start = start_ref[b]
    first = first_tile(start)

    @pl.when(j == 0)
    def _first_key_tile_of_the_head():
        dqn_acc[...] = jnp.zeros_like(dqn_acc)
        dqr_acc[...] = jnp.zeros_like(dqr_acc)

        def delta(i, _):  # sum(out * d_out) of every query, as rows like the log-sum-exp
            at = pl.multiple_of(i * BLOCK, BLOCK)
            product = out_ref[0, pl.ds(at, BLOCK), :].astype(jnp.float32) * do_ref[0, pl.ds(at, BLOCK), :].astype(jnp.float32)
            delta_ref[pl.ds(i, 1), :] = jnp.broadcast_to(jnp.sum(product, axis=1, keepdims=True), (BLOCK, LANES)).T[:1]
            return 0

        jax.lax.fori_loop(0, tiles, delta, 0)

    @pl.when(j < first)
    def _all_padding():  # no key of the tile is valid: no gradient reaches it, and no query tile is visited
        dkv_ref[...] = jnp.zeros(dkv_ref.shape, dkv_ref.dtype)
        dkr_ref[...] = jnp.zeros(dkr_ref.shape, dkr_ref.dtype)

    @pl.when(j >= first)
    def _walk():
        kn, v, kr = kv_ref[0, :, :LANES], kv_ref[0, :, LANES:], kr_ref[0]  # [BLOCK, 128]
        # scores transposed: keys on sublanes, queries on lanes
        key_at = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
        padding = jnp.where(key_at >= start, 0.0, MASKED)  # [BLOCK, 1]: a key inside the left padding

        def tile(i, carry, diagonal: bool):
            dkn, dkr, dv = carry
            at = pl.multiple_of(i * BLOCK, BLOCK)
            qn, qr, do = qn_ref[0, pl.ds(at, BLOCK), :], qr_ref[0, pl.ds(at, BLOCK), :], do_ref[0, pl.ds(at, BLOCK), :]
            lse, delta = lse_ref[0, 0, pl.ds(i, 1), :], delta_ref[pl.ds(i, 1), :]  # [1, BLOCK]
            s = jax.lax.dot_general(kn, qn, _NT, preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(kr, qr, _NT, preferred_element_type=jnp.float32)
            s = s * scale + padding
            if diagonal:
                s = jnp.where(_tile_iota(0) <= _tile_iota(1), s, MASKED)
            p = jnp.exp(s - lse)
            dv = dv + jnp.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(qn.dtype)
            dkn = dkn + jnp.dot(ds, qn, preferred_element_type=jnp.float32)
            dkr = dkr + jnp.dot(ds, qr, preferred_element_type=jnp.float32)
            dqn_acc[pl.ds(at, BLOCK), :] += jax.lax.dot_general(ds, kn, _TN, preferred_element_type=jnp.float32)
            dqr_acc[pl.ds(at, BLOCK), :] += jax.lax.dot_general(ds, kr, _TN, preferred_element_type=jnp.float32)
            return dkn, dkr, dv

        zero = jnp.zeros((BLOCK, LANES), jnp.float32)
        # the diagonal tile, then the query tiles below it (no causal mask there)
        diagonal = tile(j, (zero, zero, zero), diagonal=True)
        dkn, dkr, dv = jax.lax.fori_loop(j + 1, tiles, functools.partial(tile, diagonal=False), diagonal)
        dkv_ref[0, :, :LANES] = dkn.astype(dkv_ref.dtype)
        dkv_ref[0, :, LANES:] = dv.astype(dkv_ref.dtype)
        dkr_ref[0, 0] = dkr

    @pl.when(j == tiles - 1)
    def _write():
        dqn_ref[0] = dqn_acc[...].astype(dqn_ref.dtype)
        dqr_ref[0] = dqr_acc[...].astype(dqr_ref.dtype)


def _backward(qn, qr, kv, kr, start, out, lse, d_out, scale: float, interpret: bool):
    batch, sp, width = qn.shape
    heads, tiles = width // LANES, sp // BLOCK
    lse = lse.reshape(batch, heads, tiles, BLOCK)
    whole = pl.BlockSpec((1, sp, LANES), lambda b, h, j, start: (b, 0, h))
    tile = pl.BlockSpec((1, BLOCK, 2 * LANES), lambda b, h, j, start: (b, j, h))
    shared = pl.BlockSpec((1, BLOCK, LANES), lambda b, h, j, start: (b, j, 0))
    rows = pl.BlockSpec((1, 1, tiles, BLOCK), lambda b, h, j, start: (b, h, 0, 0))
    head_major = pl.BlockSpec((1, 1, BLOCK, LANES), lambda b, h, j, start: (b, h, j, 0))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    dqn, dqr, dkv, dkr = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, heads, tiles),
            in_specs=[whole, whole, tile, shared, whole, whole, rows],
            out_specs=[whole, whole, tile, head_major],
            scratch_shapes=[pltpu.VMEM((sp, LANES), jnp.float32), pltpu.VMEM((sp, LANES), jnp.float32),
                            pltpu.VMEM((tiles, BLOCK), jnp.float32)],
        ),
        out_shape=[like(qn), like(qr), like(kv), jax.ShapeDtypeStruct((batch, heads, sp, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="mla_attention_bwd",
    )(start, qn, qr, kv, kr, out, d_out, lse)
    # k_rope is one vector a position for every head: its gradient is the heads' sum (head-major, so the sum moves no layout)
    return dqn, dqr, dkv, jnp.sum(dkr, axis=1).astype(kr.dtype)


# ------------------------------------------------------------------ the differentiable whole
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attention(qn, qr, kv, kr, start, scale, interpret):
    return _forward(qn, qr, kv, kr, start, scale, interpret)[0]


def _attention_fwd(qn, qr, kv, kr, start, scale, interpret):
    # The backward's delta = sum(out * d_out) stands for sum(p * dp), and each score's gradient is p * (dp - delta): an
    # output rounded to bfloat16 first leaves every key of a row the same error. Where the values share a large common
    # part (this model's do) the gradients to q and k then stand 0.015 from float32's, the blocked path's 0.013, and
    # 0.010 with the output kept in float32 (CPU interpreter, PERF.md section 6). So the residual is float32.
    out, lse = _forward(qn, qr, kv, kr, start, scale, interpret, out_dtype=jnp.float32)
    return out.astype(qn.dtype), (qn, qr, kv, kr, start, out, lse)


def _attention_bwd(scale, interpret, residuals, d_out):
    qn, qr, kv, kr, start, out, lse = residuals
    return (*_backward(qn, qr, kv, kr, start, out, lse, d_out, scale, interpret), None)


_attention.defvjp(_attention_fwd, _attention_bwd)


def mla_attention(q_nope: jax.Array, q_rope: jax.Array, latent: jax.Array, w_kv: jax.Array, k_rope: jax.Array,
                  start: jax.Array, scale: float, interpret: bool = False) -> jax.Array:
    """``softmax(mask(scale * (q_nope . k_nope + q_rope . k_rope))) v`` per head,
    with the keys and values expanded from the latent here: ``q_nope``
    [B, S, h, 128], ``q_rope`` [B, S, h, dr], ``latent`` [B, S, r], ``w_kv``
    [r, h * 256] (per head its 128 key columns, then its 128 value columns),
    ``k_rope`` [B, S, dr]; query s of row b sees the keys ``start[b] .. s``.
    Returns [B, S, h, 128]. The latent is padded to the tile before its
    expansion, so keys and values reach the kernels in the layout their product
    leaves them in, and no copy of them is made. ``interpret`` runs the kernels
    in the Pallas interpreter (the CPU tests)."""
    batch, seq, heads, _ = q_nope.shape
    grow = ((0, 0), (0, _padded_len(seq) - seq))
    lanes = (0, LANES - q_rope.shape[-1])
    qn = jnp.pad(q_nope.reshape(batch, seq, heads * LANES), (*grow, (0, 0)))
    qr = jnp.pad(q_rope, (*grow, (0, 0), lanes)).reshape(batch, -1, heads * LANES)
    kv = jnp.pad(latent, (*grow, (0, 0))) @ w_kv
    kr = jnp.pad(k_rope, (*grow, lanes))
    out = _attention(qn, qr, kv, kr, start.astype(jnp.int32), float(scale), interpret)
    return out[:, :seq].reshape(batch, seq, heads, LANES)

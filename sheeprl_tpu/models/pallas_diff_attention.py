"""Whole-sequence differential attention as fused Pallas TPU kernels (the flash form).

What `models.hybrid_decoder.DiffAttention.__call__` needs between its
projections and its sub-layer norm: query heads and key/value heads in
adjacent pairs, a pair's values side by side, and per query pair
``softmax(q_1 k_1^T / sqrt(d)) [v_1; v_2] - lambda softmax(q_2 k_2^T / sqrt(d))
[v_1; v_2]`` over the keys ``max(start[b], t - window + 1) .. t`` of
left-padded sequences. In plain XLA (`hybrid_decoder.blocked_differential`)
each block of float32 scores ``[B, heads, q, k]`` goes to HBM and comes back
about ten times, forward and backward. Here no block of scores leaves VMEM.

**A pair is one 128-lane tile** on every operand: the arrays go in as
``[B, S, pairs * 128]`` (``q_1 | q_2``, ``k_1 | k_2``, ``v_1 | v_2``), as the
projections leave them, and a block is one pair's columns. A member's scores
are the product of the pair's tile with the other member's half zeroed: the
contraction runs over 128 lanes, which is what the MXU takes in one pass
anyway, and no tile is cut at lane 64.

- **forward** (:func:`_fwd_kernel`, ``diff_attention_fwd``): one grid step
  per (sequence, query pair, query tile). The two members' queries stand on
  top of each other (``[2 BLOCK, 128]``), so one online softmax (running
  maximum, running sum, accumulator) serves both while the step walks the key
  tiles of its key/value pair, which stay in VMEM, up to the diagonal; it
  writes ``o_1 - lambda o_2``, the rows' two log-sum-exps and, for a backward
  pass, ``o_2`` (all float32).
- **backward** (:func:`_bwd_kernel`, ``diff_attention_bwd``): one grid step
  per (sequence, key/value pair, key tile). For each query pair of the group
  it walks the query tiles from the diagonal down, makes both members'
  probabilities again from the saved log-sum-exps (transposed: keys on
  sublanes, queries on lanes, so the per-query rows broadcast) and forms dV,
  dK (summed over the group in the step) and dQ, which stays in VMEM across
  the pair's key tiles. The members share ``dP = V dO^T`` and one product for
  dV, ``(P_1 - lambda P_2)^T dO``. The rows' ``delta_i = sum(o_i * d_out)``
  are made outside from the float32 residuals, where ``dL/dlambda = -sum
  delta_2`` falls out of them.

Precision is the configuration's: the operands of every product in the dtype
the queries, keys and values come in (bfloat16 under ``bf16-mixed``), float32
accumulation; scores, masks, maxima, sums, log-sum-exps, deltas and the
accumulators in float32; probabilities (and the cotangent, which arrives in
float32) are cast only as the operand of their products. ``1 / sqrt(d)`` is a
power of two at the one eligible head width, so it goes onto the ``[BLOCK,
128]`` operand exactly, not onto every score.

Masks: causal, the band ``k > t - window`` where there is a window, and keys
valid from ``start[b]`` on. Key tiles above the diagonal and below the band
are never visited; tiles wholly inside a row's left padding are (as in
`pallas_mla_attention`). A query with no valid key (a position inside the
left padding) gives zero and sends no gradient anywhere.

Dispatch: :func:`ineligible_reason` is the whole rule. The kernels run when
the backend is a TPU and the shape is eligible ("eligible" implies
"compiles": tests/test_utils/test_tpu_aot_compiles.py asks the TPU compiler);
otherwise `blocked_differential` runs (CPU tests, micro sizes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sheeprl_tpu.models.pallas_mla_attention import (
    _NT,
    _TN,
    _VMEM_BLOCKS_BYTES,
    _VMEM_LIMIT_BYTES,
    LANES,
    MASKED,
    NO_KEY_LSE,
)

#: Queries and keys of one (square) tile; two members' queries make a step's
#: scores ``[2 BLOCK, BLOCK]``. Chosen on the chip by layer time and build
#: time (PERF.md section 6, PR 34).
BLOCK = 512
SCALE = (LANES // 2) ** -0.5  # 1 / sqrt(d) at the one eligible head width, 64: a power of two


def _padded_len(seq: int) -> int:
    return seq + (-seq) % BLOCK


def _reach(window: int) -> int:
    """Key tiles before its own that a query tile's band reaches into."""
    return (window + BLOCK - 2) // BLOCK


def _vmem_bytes(seq: int, itemsize: int, group: int) -> int:
    """Upper bound on the VMEM one grid step of the backward kernel holds (the
    forward holds less): the whole-sequence blocks of a group's query pairs
    double-buffered (q and d_out in, dq out), the float32 dQ accumulator, the
    rows (log-sum-exps and deltas), the tile's keys, values and their gradients
    and the score-sized temporaries."""
    sp = _padded_len(seq)
    whole = sp * group * LANES
    tile = BLOCK * LANES
    rows = 2 * 2 * group * 2 * (sp + 8 * BLOCK) * 4  # two arrays, double-buffered, two members, sublane-padded
    return 2 * 3 * whole * itemsize + whole * 4 + rows + 2 * 4 * tile * itemsize + 8 * tile * 4 + 8 * BLOCK * BLOCK * 4


def traced_backend() -> str:
    """Where what is traced now will run: the `jax.default_device` in force (a player acting from the host), else the
    default backend."""
    device = jax.config.jax_default_device
    return getattr(device, "platform", device) or jax.default_backend()


def ineligible_reason(seq: int, head_dim: int, window: Optional[int], dtype, group: int = 2) -> Optional[str]:
    """Why the kernels cannot take whole sequences of this shape here, or None when they can."""
    backend = traced_backend()
    if backend != "tpu":
        return f"the backend is {backend}, not a TPU"
    return shape_ineligible_reason(seq, head_dim, window, dtype, group)


def shape_ineligible_reason(seq: int, head_dim: int, window: Optional[int], dtype, group: int = 2) -> Optional[str]:
    """The part of the rule that is about the shape alone (what the ahead-of-time compiles hold to the compiler):
    ``seq`` positions, heads of ``head_dim``, ``group`` query pairs to a key/value pair."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"operands of {dtype.name} (the kernels take bfloat16 or float32)"
    if 2 * head_dim != LANES:
        return f"a pair of heads of {head_dim} is not the {LANES}-lane tile"
    if window is not None and window < 1:
        return f"a window of {window} positions holds no key"
    if seq < BLOCK:
        return f"{seq} positions are fewer than one tile of {BLOCK}"
    need = _vmem_bytes(seq, dtype.itemsize, group)
    if need > _VMEM_BLOCKS_BYTES:
        return (
            f"a group's blocks of {seq} positions need {need / 2**20:.1f} MiB of VMEM per grid step, over the "
            f"{_VMEM_BLOCKS_BYTES / 2**20:.0f} MiB the kernels keep for them"
        )
    return None


def _members(tile: jax.Array):
    """A pair's tile ``[BLOCK, 128]`` (``x_1 | x_2``) times ``1 / sqrt(d)``, as two tiles with the other member's half zeroed."""
    first = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) < LANES // 2
    tile = tile * SCALE  # a power of two: exact in the tile's dtype
    zero = jnp.zeros_like(tile)
    return jnp.where(first, tile, zero), jnp.where(first, zero, tile)


# ------------------------------------------------------------------ forward
def _fwd_kernel(start_ref, lam_ref, q_ref, k_ref, v_ref, out_ref, lse_ref, *second_ref, window: Optional[int]):
    b, i = pl.program_id(0), pl.program_id(2)
    start, lam = start_ref[b], lam_ref[0]
    q = jnp.concatenate(_members(q_ref[0]), axis=0)  # [2 BLOCK, 128]: member 1's queries, then member 2's
    key_at = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)
    ahead = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1) - jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    ahead = jnp.concatenate([ahead, ahead], axis=0)  # key - query, tile-locally, of both members' rows

    def tile(j, carry, diagonal: bool, band: bool):
        m, l, acc = carry
        at = pl.multiple_of(j * BLOCK, BLOCK)
        k, v = k_ref[0, pl.ds(at, BLOCK), :], v_ref[0, pl.ds(at, BLOCK), :]
        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        s = s + jnp.where(at + key_at >= start, 0.0, MASKED)  # a key inside the left padding
        if diagonal:  # tiles are square: on the diagonal tile key k is visible to query t where k <= t, tile-locally
            s = jnp.where(ahead <= 0, s, MASKED)
        if band:  # key k is visible to query t where k > t - window
            s = jnp.where(ahead > (i - j) * BLOCK - window, s, MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (
        jnp.full((2 * BLOCK, 1), MASKED, jnp.float32),
        jnp.zeros((2 * BLOCK, 1), jnp.float32),
        jnp.zeros((2 * BLOCK, LANES), jnp.float32),
    )
    # tiles above the diagonal and below the band are never visited; between them only a band's tiles need a mask
    if window is None:
        below = jax.lax.fori_loop(0, i, functools.partial(tile, diagonal=False, band=False), init)
    else:
        below = jax.lax.fori_loop(jnp.maximum(i - _reach(window), 0), i, functools.partial(tile, diagonal=False, band=True), init)
    m, l, acc = tile(i, below, diagonal=True, band=window is not None and window < BLOCK)
    seen = m > 0.5 * MASKED  # the row met a valid key
    out = jnp.where(seen, acc / l, 0.0)
    out_ref[0] = out[:BLOCK] - lam * out[BLOCK:]
    if second_ref:
        second_ref[0][0] = out[BLOCK:]
    lse = jnp.where(seen, m + jnp.log(l), NO_KEY_LSE)
    # the rows' log-sum-exps leave as a row (queries on lanes, member 1's then member 2's), the way the backward kernel reads them
    lse_ref[0, 0] = jnp.broadcast_to(lse, (2 * BLOCK, LANES)).T[:1]


def _forward(q, k, v, start, lam, window: Optional[int], interpret: bool, keep_second: bool):
    """The padded forms: ``q`` [B, Sp, query pairs * 128], ``k``, ``v`` [B, Sp,
    key/value pairs * 128], ``start`` [B], ``lam`` [1]. Returns ``o_1 - lam
    o_2`` [B, Sp, query pairs * 128] and the rows' log-sum-exps [B, query
    pairs, 1, tiles * 2 BLOCK] (a tile's first members, then its second), and
    ``o_2`` after them where ``keep_second``: all float32."""
    batch, sp, width = q.shape
    pairs, group = width // LANES, width // k.shape[-1]
    tile = pl.BlockSpec((1, BLOCK, LANES), lambda b, p, i, *_: (b, i, p))
    whole = pl.BlockSpec((1, sp, LANES), lambda b, p, i, *_: (b, 0, p // group))
    rows = pl.BlockSpec((1, 1, 1, 2 * BLOCK), lambda b, p, i, *_: (b, p, 0, i))
    like_q = jax.ShapeDtypeStruct(q.shape, jnp.float32)
    second = [like_q] if keep_second else []
    return pl.pallas_call(
        functools.partial(_fwd_kernel, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, pairs, sp // BLOCK),
            in_specs=[tile, whole, whole],
            out_specs=[tile, rows] + [tile] * len(second),
        ),
        out_shape=[like_q, jax.ShapeDtypeStruct((batch, pairs, 1, 2 * sp), jnp.float32)] + second,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="diff_attention_fwd",
    )(start, lam, q, k, v)


# ------------------------------------------------------------------ backward
def _bwd_kernel(start_ref, lam_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                *, window: Optional[int], group: int):
    b, j = pl.program_id(0), pl.program_id(2)
    tiles = pl.num_programs(2)
    start, lam = start_ref[b], lam_ref[0]

    @pl.when(j == 0)
    def _first_key_tile_of_the_pair():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    v = v_ref[0]  # [BLOCK, 128]
    k1, k2 = _members(k_ref[0])
    # scores transposed: keys on sublanes, queries on lanes
    key_at = j * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
    padding = jnp.where(key_at >= start, 0.0, MASKED)  # [BLOCK, 1]: a key inside the left padding
    ahead = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0) - jax.lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)

    def tile(i, carry, pair: int, diagonal: bool, band: bool):
        dk1, dk2, dv = carry
        at = pl.multiple_of(i * BLOCK, BLOCK)
        lanes = slice(pair * LANES, (pair + 1) * LANES)
        q, do = q_ref[0, pl.ds(at, BLOCK), lanes], do_ref[0, pl.ds(at, BLOCK), lanes]
        row = lambda ref, member: ref[0, pair, pl.ds(i, 1), member * BLOCK:(member + 1) * BLOCK]  # noqa: E731 - [1, BLOCK]
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)  # both members'

        def member(km, index):
            s = jax.lax.dot_general(km, q, _NT, preferred_element_type=jnp.float32) + padding
            if diagonal:
                s = jnp.where(ahead <= 0, s, MASKED)
            if band:
                s = jnp.where(ahead > (i - j) * BLOCK - window, s, MASKED)
            p = jnp.exp(s - row(lse_ref, index))
            return p, p * (dp - row(delta_ref, index))

        (p1, ds1), (p2, ds2) = member(k1, 0), member(k2, 1)
        ds1, ds2 = ds1.astype(q.dtype), (-lam * ds2).astype(q.dtype)
        dv = dv + jnp.dot((p1 - lam * p2).astype(do.dtype), do, preferred_element_type=jnp.float32)
        # a member's gradient is right in its own half of the lanes; the halves are put together at the end
        dk1 = dk1 + jnp.dot(ds1, q, preferred_element_type=jnp.float32)
        dk2 = dk2 + jnp.dot(ds2, q, preferred_element_type=jnp.float32)
        dq_acc[pl.ds(at, BLOCK), lanes] += (jax.lax.dot_general(ds1, k1, _TN, preferred_element_type=jnp.float32)
                                            + jax.lax.dot_general(ds2, k2, _TN, preferred_element_type=jnp.float32))
        return dk1, dk2, dv

    zero = jnp.zeros((BLOCK, LANES), jnp.float32)
    carry = (zero, zero, zero)
    for pair in range(group):  # the query pairs this key/value pair serves: dK and dV are their sum
        # the diagonal tile, then the query tiles below it, to the sequence's end or the band's
        carry = tile(j, carry, pair, diagonal=True, band=window is not None and window < BLOCK)
        if window is None:
            carry = jax.lax.fori_loop(j + 1, tiles, functools.partial(tile, pair=pair, diagonal=False, band=False), carry)
        else:
            last = jnp.minimum(j + _reach(window), tiles - 1)
            carry = jax.lax.fori_loop(j + 1, last + 1, functools.partial(tile, pair=pair, diagonal=False, band=True), carry)
    dk1, dk2, dv = carry
    first = jax.lax.broadcasted_iota(jnp.int32, (BLOCK, LANES), 1) < LANES // 2
    dk_ref[0] = (jnp.where(first, dk1, dk2) * SCALE).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == tiles - 1)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _backward(q, k, v, start, lam, lse, delta, d_out, window: Optional[int], interpret: bool):
    """``d_out`` in the operands' dtype; ``lse`` and ``delta`` [B, query pairs, tiles, 2 BLOCK]. Returns dq, dk, dv."""
    batch, sp, width = q.shape
    group, tiles = width // k.shape[-1], sp // BLOCK
    whole = pl.BlockSpec((1, sp, group * LANES), lambda b, h, j, *_: (b, 0, h))
    tile = pl.BlockSpec((1, BLOCK, LANES), lambda b, h, j, *_: (b, j, h))
    rows = pl.BlockSpec((1, group, tiles, 2 * BLOCK), lambda b, h, j, *_: (b, h, 0, 0))
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_bwd_kernel, window=window, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, k.shape[-1] // LANES, tiles),
            in_specs=[whole, tile, tile, whole, rows, rows],
            out_specs=[whole, tile, tile],
            scratch_shapes=[pltpu.VMEM((sp, group * LANES), jnp.float32)],
        ),
        out_shape=[like(q), like(k), like(v)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="diff_attention_bwd",
    )(start, lam, q, k, v, d_out, lse, delta)


# ------------------------------------------------------------------ the differentiable whole
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _attention(q, k, v, lam, start, window, interpret):
    return _forward(q, k, v, start, lam.reshape(1), window, interpret, keep_second=False)[0]


def _attention_fwd(q, k, v, lam, start, window, interpret):
    # The outputs stay float32 as residuals: the backward's delta_i = sum(o_i * d_out) stands for sum(p_i * dp), and a
    # rounded output leaves every key of a row the same error (pallas_mla_attention._attention_fwd); here the sub-layer
    # norm after it renormalises a difference of two softmaxes and amplifies what is left.
    out, lse, second = _forward(q, k, v, start, lam.reshape(1), window, interpret, keep_second=True)
    return out, (q, k, v, lam, start, out, second, lse)


def _attention_bwd(window, interpret, residuals, d_out):
    q, k, v, lam, start, out, second, lse = residuals
    batch, sp, width = q.shape
    pairs, tiles = width // LANES, sp // BLOCK
    by_pair = lambda x: x.reshape(batch, sp, pairs, LANES)  # noqa: E731
    delta2 = jnp.sum(by_pair(d_out) * by_pair(second), axis=-1)  # [B, Sp, pairs]
    delta1 = jnp.sum(by_pair(d_out) * by_pair(out), axis=-1) + lam * delta2  # o_1 = out + lam o_2
    # as the log-sum-exps lie: per pair and tile a row of the first members' queries, then the second's
    delta = jnp.stack([delta1, delta2], axis=-1).reshape(batch, tiles, BLOCK, pairs, 2)
    delta = delta.transpose(0, 3, 1, 4, 2).reshape(batch, pairs, tiles, 2 * BLOCK)
    lse = lse.reshape(batch, pairs, tiles, 2 * BLOCK)
    dq, dk, dv = _backward(q, k, v, start, lam.reshape(1), lse, delta, d_out.astype(q.dtype), window, interpret)
    return dq, dk, dv, -jnp.sum(delta2).astype(lam.dtype), None


_attention.defvjp(_attention_fwd, _attention_bwd)


def diff_attention(q: jax.Array, k: jax.Array, v: jax.Array, start: jax.Array, lam: jax.Array, window: Optional[int],
                   interpret: bool = False) -> jax.Array:
    """`hybrid_decoder.blocked_differential` as fused kernels: ``q`` [B, S,
    heads, d], ``k``, ``v`` [B, S, kv heads, d] with ``2 d = 128``, ``lam`` a
    float32 scalar; query t of row b sees the keys ``max(start[b], t - window
    + 1) .. t``. Returns float32 [B, S, heads / 2, 2 d]. The sequence is padded
    to the tile on the right (causality keeps real queries off the padded
    keys). ``interpret`` runs the kernels in the Pallas interpreter (the CPU tests)."""
    batch, seq, heads, d = q.shape
    grow = ((0, 0), (0, _padded_len(seq) - seq), (0, 0))
    flat = lambda t: jnp.pad(t.reshape(batch, seq, -1), grow)  # noqa: E731 - a pair's heads are adjacent: [B, Sp, pairs * 128]
    out = _attention(flat(q), flat(k), flat(v), jnp.asarray(lam, jnp.float32), start.astype(jnp.int32), window, interpret)
    return out[:, :seq].reshape(batch, seq, heads // 2, 2 * d)

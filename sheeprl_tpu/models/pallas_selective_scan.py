"""The selective scan of a Mamba layer as Pallas TPU kernels.

What `models.hybrid_decoder.Mamba.__call__` needs between its input
projections and its gate: from a zero state over whole sequences,
``s_t = exp(delta_t a) s_{t-1} + (delta_t x_t) b_t``, ``y_t = s_t . c_t``,
with ``x``, ``delta`` ``[B, S, D]``, ``a`` ``[N, D]``, ``b``, ``c`` ``[B, S,
N]``. In plain XLA (`hybrid_decoder.selective_scan`) the state ``[B, N, D]``
goes to HBM and back between the trips of a loop over positions, the inputs
are transposed and padded into chunks and ``y`` transposed back, and the
backward pass makes every chunk's states twice (the layer's rematerialised
forward, then the chunk's `jax.checkpoint`). Here the state never leaves
VMEM inside a chunk of :data:`CHUNK` positions:

- **forward** (:func:`_fwd_kernel`, ``selective_scan_fwd``): one grid step
  per (sequence, lane block of the inner width, chunk), the chunks in order.
  The state ``[N, LANE_BLOCK]`` float32 (``d_state`` on the sublanes, the
  inner width on the lanes, as `hybrid_decoder.scan_step` lays it out) sits in
  a VMEM scratch that the first chunk zeroes and every chunk carries on. It
  writes ``y`` float32, the last state (the player's cache) and, for a
  backward pass, the state at each chunk's start: the only residual.
- **backward** (:func:`_bwd_kernel`, ``selective_scan_bwd``): the same grid,
  the chunks in reverse. A step makes its chunk's states once, in VMEM
  (``[CHUNK + 1, N, LANE_BLOCK]`` float32), from the saved start state, then
  runs back through them with ``dL/ds`` carried in VMEM from chunk to chunk.
  It writes ``dx`` and ``d delta`` per position, and partials that are summed
  outside: ``db``, ``dc`` per lane block ``[B, D / LANE_BLOCK, S, N]``, ``da``
  per sequence ``[B, N, D]`` (accumulated over the chunks in VMEM).

``b`` and ``c`` are read as they lie (``[CHUNK, N]`` a step) and turned once a
chunk; a position's column of them is a masked sum over the chunk's lanes,
and a position's ``db``, ``dc`` are put into the chunk's columns by a select,
so nothing is indexed on the lanes at run time.

Precision is the plain path's: float32 state, float32 ``exp``, the inputs
cast to float32 where they are read, in the dtype they arrive in. Positions
past the sequence's end inside the last chunk are given ``delta = 0`` and
``x = b = c = 0`` (whatever the partial block holds there): they leave the
state as it is and write nothing that is kept.

Dispatch: :func:`ineligible_reason` is the whole rule. The kernels run when
the backend is a TPU and the shape is eligible ("eligible" implies
"compiles": tests/test_utils/test_tpu_aot_compiles.py asks the TPU compiler);
otherwise `hybrid_decoder.selective_scan` runs (CPU tests, micro sizes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sheeprl_tpu.models.pallas_diff_attention import traced_backend
from sheeprl_tpu.models.pallas_mla_attention import _VMEM_BLOCKS_BYTES, _VMEM_LIMIT_BYTES

#: Inner-width lanes of one grid step (the state a step carries is ``[N, LANE_BLOCK]``: 8 vregs at 16 states) and
#: the positions of one chunk (what the backward pass makes states for at once). Chosen by the TPU compiler's VMEM
#: bound and by arithmetic, not yet by chip time (PERF.md section 6).
LANE_BLOCK = 512
CHUNK = 128
UNROLL = 8  # positions of one trip of a chunk's loop


def _vmem_bytes(state: int, itemsize: int) -> int:
    """Upper bound on the VMEM one grid step of the backward kernel holds (the
    forward holds less): the chunk's states, the double-buffered blocks in and
    out, the float32 rows of the chunk and the ``[N, LANE_BLOCK]`` carries."""
    rows = CHUNK * LANE_BLOCK
    square = state * LANE_BLOCK * 4
    narrow = CHUNK * 128 * 4  # a [CHUNK, N] block of b, c, db or dc, lane-padded
    blocks = 2 * (2 * rows * itemsize + 3 * rows * 4 + 3 * square + 4 * narrow)  # x, dx; delta, dy, d delta; a, start, da; b, c, db, dc
    return (CHUNK + 1) * square + blocks + 6 * rows * 4 + 4 * square


def ineligible_reason(batch: int, seq: int, width: int, state: int, dtype) -> Optional[str]:
    """Why the kernels cannot scan ``batch`` sequences of ``seq`` positions, an inner width ``width`` and ``state``
    states a lane here, or None when they can."""
    backend = traced_backend()
    if backend != "tpu":
        return f"the backend is {backend}, not a TPU"
    return shape_ineligible_reason(batch, seq, width, state, dtype)


def shape_ineligible_reason(batch: int, seq: int, width: int, state: int, dtype) -> Optional[str]:
    """The part of the rule that is about the shape alone (what the ahead-of-time compiles hold to the compiler)."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"inputs of {dtype.name} (the kernels take bfloat16 or float32)"
    if batch < 1 or seq < 1:
        return f"[{batch}, {seq}] holds no position to scan"
    if width % LANE_BLOCK:
        return f"an inner width of {width} is not a multiple of the {LANE_BLOCK}-lane block"
    if state % 8:
        return f"{state} states are not a multiple of the 8 sublanes"
    need = _vmem_bytes(state, dtype.itemsize)
    if need > _VMEM_BLOCKS_BYTES:
        return (
            f"a chunk's states and blocks at {state} states need {need / 2**20:.1f} MiB of VMEM per grid step, over the "
            f"{_VMEM_BLOCKS_BYTES / 2**20:.0f} MiB the kernels keep for them"
        )
    return None


def _chunk_rows(chunk, seq: int, x_ref, delta_ref, b_ref, c_ref):
    """Which of the chunk's rows are positions of the sequence [CHUNK, 1], and its ``delta``, ``x`` [CHUNK,
    LANE_BLOCK] and ``b``, ``c`` turned to [N, CHUNK], float32, with the positions past the sequence's end zeroed."""
    first = chunk * CHUNK
    real = first + jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0) < seq
    delta = jnp.where(real, delta_ref[0].astype(jnp.float32), 0.0)
    x = jnp.where(real, x_ref[0].astype(jnp.float32), 0.0)
    real_lanes = first + jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1) < seq
    turned = lambda ref: jnp.where(real_lanes, ref[0].astype(jnp.float32).T, 0.0)  # noqa: E731
    return real, delta, x, turned(b_ref), turned(c_ref)


def _walk(position, carry, reverse: bool = False):
    """``carry = position(t, carry)`` for the chunk's positions ``t`` in order (or in reverse), :data:`UNROLL` of
    them written out in one trip of the loop (Mosaic unrolls a loop wholly or not at all)."""

    def trip(i, carry):
        for j in range(UNROLL):
            at = i * UNROLL + j
            carry = position(CHUNK - 1 - at if reverse else at, carry)
        return carry

    return jax.lax.fori_loop(0, CHUNK // UNROLL, trip, carry)


def _column(at: jax.Array, turned: jax.Array) -> jax.Array:
    """Position ``t``'s column [N, 1] of a chunk's ``[N, CHUNK]`` (``at``: the lanes that are ``t``)."""
    return jnp.sum(jnp.where(at, turned, 0.0), axis=1, keepdims=True)


# ------------------------------------------------------------------ forward
def _fwd_kernel(x_ref, delta_ref, a_ref, b_ref, c_ref, y_ref, last_ref, *refs, seq: int, keep_starts: bool):
    starts_ref = refs[0] if keep_starts else None
    state_ref, delta_rows, du_rows = refs[-3:]
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _first_chunk():
        state_ref[...] = jnp.zeros_like(state_ref)

    if keep_starts:
        starts_ref[0, 0] = state_ref[...]
    _, delta, x, b, c = _chunk_rows(k, seq, x_ref, delta_ref, b_ref, c_ref)
    delta_rows[...] = delta
    du_rows[...] = delta * x
    a = a_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)

    def position(t, s):
        at = lane == t
        d = delta_rows[pl.ds(t, 1), :]
        s = jnp.exp(d * a) * s + du_rows[pl.ds(t, 1), :] * _column(at, b)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(s * _column(at, c), axis=0, keepdims=True)
        return s

    s = _walk(position, state_ref[...])
    state_ref[...] = s

    @pl.when(k == pl.num_programs(2) - 1)
    def _last_chunk():
        last_ref[0] = s


def _forward(x, delta, a, b, c, interpret: bool, keep_starts: bool):
    """Returns ``y`` [B, S, D] float32, the last state [B, N, D] and, where ``keep_starts``, the state at each chunk's
    start [B, chunks, N, D]."""
    batch, seq, width = x.shape
    state = a.shape[0]
    chunks = pl.cdiv(seq, CHUNK)
    rows = pl.BlockSpec((1, CHUNK, LANE_BLOCK), lambda r, j, k: (r, k, j))
    narrow = pl.BlockSpec((1, CHUNK, state), lambda r, j, k: (r, k, 0))
    square = pl.BlockSpec((1, state, LANE_BLOCK), lambda r, j, k: (r, 0, j))
    starts = pl.BlockSpec((1, 1, state, LANE_BLOCK), lambda r, j, k: (r, k, 0, j))
    out_specs = [rows, square] + ([starts] if keep_starts else [])
    out_shape = [jax.ShapeDtypeStruct((batch, seq, width), jnp.float32), jax.ShapeDtypeStruct((batch, state, width), jnp.float32)]
    if keep_starts:
        out_shape.append(jax.ShapeDtypeStruct((batch, chunks, state, width), jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, seq=seq, keep_starts=keep_starts),
        grid=(batch, width // LANE_BLOCK, chunks),
        in_specs=[rows, rows, pl.BlockSpec((state, LANE_BLOCK), lambda r, j, k: (0, j)), narrow, narrow],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((state, LANE_BLOCK), jnp.float32),
            pltpu.VMEM((CHUNK, LANE_BLOCK), jnp.float32),
            pltpu.VMEM((CHUNK, LANE_BLOCK), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, delta, a, b, c)


# ------------------------------------------------------------------ backward
def _bwd_kernel(x_ref, delta_ref, a_ref, b_ref, c_ref, start_ref, dy_ref, dlast_ref,
                dx_ref, ddelta_ref, da_ref, db_ref, dc_ref,
                ds_ref, da_acc, states, delta_rows, x_rows, dy_rows, dx_rows, ddelta_rows, *, seq: int):
    k = pl.program_id(2)
    chunks = pl.num_programs(2)

    @pl.when(k == 0)
    def _last_chunk_first():
        ds_ref[...] = dlast_ref[0]
        da_acc[...] = jnp.zeros_like(da_acc)

    real, delta, x, b, c = _chunk_rows(chunks - 1 - k, seq, x_ref, delta_ref, b_ref, c_ref)
    delta_rows[...] = delta
    x_rows[...] = x
    dy_rows[...] = jnp.where(real, dy_ref[0], 0.0)
    a = a_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    states[0] = start_ref[0, 0]

    def remake(t, s):
        d = delta_rows[pl.ds(t, 1), :]
        s = jnp.exp(d * a) * s + (d * x_rows[pl.ds(t, 1), :]) * _column(lane == t, b)
        states[t + 1] = s
        return s

    _walk(remake, states[0])

    def back(t, carry):
        ds, da, db, dc = carry
        at = lane == t
        d, xt, dy = delta_rows[pl.ds(t, 1), :], x_rows[pl.ds(t, 1), :], dy_rows[pl.ds(t, 1), :]
        dc = jnp.where(at, jnp.sum(states[t + 1] * dy, axis=1, keepdims=True), dc)
        g = ds + _column(at, c) * dy  # dL/ds_t
        decay = jnp.exp(d * a)
        dlog = g * states[t] * decay  # dL/d(delta_t a)
        db = jnp.where(at, jnp.sum(g * (d * xt), axis=1, keepdims=True), db)
        ddu = jnp.sum(g * _column(at, b), axis=0, keepdims=True)  # dL/d(delta_t x_t)
        dx_rows[pl.ds(t, 1), :] = ddu * d
        ddelta_rows[pl.ds(t, 1), :] = jnp.sum(dlog * a, axis=0, keepdims=True) + ddu * xt
        return decay * g, da + dlog * d, db, dc

    zero = jnp.zeros(b.shape, jnp.float32)
    ds, da, db, dc = _walk(back, (ds_ref[...], da_acc[...], zero, zero), reverse=True)
    ds_ref[...] = ds
    da_acc[...] = da
    dx_ref[0] = dx_rows[...].astype(dx_ref.dtype)
    ddelta_ref[0] = ddelta_rows[...].astype(ddelta_ref.dtype)
    db_ref[0, 0] = db.T
    dc_ref[0, 0] = dc.T

    @pl.when(k == chunks - 1)
    def _first_chunk_last():
        da_ref[0] = da


def _backward(x, delta, a, b, c, starts, dy, dlast, interpret: bool):
    """Returns ``dx`` (``x``'s dtype), ``d delta`` (``delta``'s), and the float32 partials ``da`` [B, N, D], ``db``,
    ``dc`` [B, D / LANE_BLOCK, S, N]."""
    batch, seq, width = x.shape
    state, chunks, blocks = a.shape[0], starts.shape[1], width // LANE_BLOCK
    back = lambda k: chunks - 1 - k  # noqa: E731 - the grid walks the chunks in reverse
    rows = pl.BlockSpec((1, CHUNK, LANE_BLOCK), lambda r, j, k: (r, back(k), j))
    narrow = pl.BlockSpec((1, CHUNK, state), lambda r, j, k: (r, back(k), 0))
    square = pl.BlockSpec((1, state, LANE_BLOCK), lambda r, j, k: (r, 0, j))
    start = pl.BlockSpec((1, 1, state, LANE_BLOCK), lambda r, j, k: (r, back(k), 0, j))
    partial = pl.BlockSpec((1, 1, CHUNK, state), lambda r, j, k: (r, j, back(k), 0))
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)  # noqa: E731
    partials = jax.ShapeDtypeStruct((batch, blocks, seq, state), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, seq=seq),
        grid=(batch, blocks, chunks),
        in_specs=[rows, rows, pl.BlockSpec((state, LANE_BLOCK), lambda r, j, k: (0, j)), narrow, narrow, start, rows, square],
        out_specs=[rows, rows, square, partial, partial],
        out_shape=[like(x), like(delta), jax.ShapeDtypeStruct((batch, state, width), jnp.float32), partials, partials],
        scratch_shapes=[
            pltpu.VMEM((state, LANE_BLOCK), jnp.float32),
            pltpu.VMEM((state, LANE_BLOCK), jnp.float32),
            pltpu.VMEM((CHUNK + 1, state, LANE_BLOCK), jnp.float32),
        ] + [pltpu.VMEM((CHUNK, LANE_BLOCK), jnp.float32)] * 5,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, delta, a, b, c, starts, dy, dlast)


# ------------------------------------------------------------------ the differentiable whole
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, delta, a, b, c, interpret):
    return tuple(_forward(x, delta, a, b, c, interpret, keep_starts=False))


def _scan_fwd(x, delta, a, b, c, interpret):
    y, last, starts = _forward(x, delta, a, b, c, interpret, keep_starts=True)
    return (y, last), (x, delta, a, b, c, starts)


def _scan_bwd(interpret, residuals, cotangents):
    x, delta, a, b, c, starts = residuals
    dy, dlast = cotangents
    dx, ddelta, da, db, dc = _backward(x, delta, a, b, c, starts, dy.astype(jnp.float32), dlast.astype(jnp.float32), interpret)
    return dx, ddelta, jnp.sum(da, axis=0).astype(a.dtype), jnp.sum(db, axis=1).astype(b.dtype), jnp.sum(dc, axis=1).astype(c.dtype)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x: jax.Array, delta: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, interpret: bool = False):
    """`hybrid_decoder.selective_scan` as kernels: ``x``, ``delta`` [B, S, D], ``a`` [N, D], ``b``, ``c`` [B, S, N].
    Returns ``y`` [B, S, D] float32 and the last state [B, N, D] float32. ``interpret`` runs the kernels in the Pallas
    interpreter (the CPU tests)."""
    return _scan(x, delta, a, b, c, interpret)

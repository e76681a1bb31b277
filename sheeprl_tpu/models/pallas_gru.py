"""Fused LayerNorm-GRU cell step as a Pallas TPU kernel.

The Hafner GRU cell (models.LayerNormGRUCell) is the per-step body of every
Dreamer RSSM scan — the hottest recurrent op in the framework. Unfused, XLA
materializes the projection z = [h, x] @ W (+b) to HBM, then reads it back
for LayerNorm and again for the gate math. This kernel keeps each [B_tile,
3H] row of z in VMEM: the matmul accumulates over D tiles on the MXU and the
epilogue (LayerNorm over the full 3H row + sigmoid/tanh gates + the convex
h-update) runs on the VPU before anything returns to HBM.

Gradients: `fused_ln_gru` carries a custom VJP. The forward kernel ALSO
emits the biased pre-LN projection z as a residual, so the backward never
recomputes the forward matmul — it differentiates the cheap elementwise
z -> out tail with plain jax and forms the three matmul gradients
(dz @ W^T, inp^T @ dz, sum dz) directly. Same FLOPs as XLA's unfused
backward, minus the fused forward's saved HBM traffic.

Dispatch: the kernel runs on TPU when the shape is eligible — H a multiple
of 128 and the per-grid-step VMEM footprint (:func:`_vmem_bytes`, the sum
the compiler itself refuses past its scoped limit) inside
:data:`_VMEM_LIMIT_BYTES`; "eligible" implies "compiles"
(tests/test_utils/test_tpu_aot_compiles.py asks the TPU compiler). Off TPU (CPU tests)
the identical plain-jax computation runs; on TPU an ineligible shape also
takes the plain path, with a warning that names the shape and the bound.
Whether the cell routes here at all is decided by ONE knob in
models.LayerNormGRUCell: the `fused` flag, whose auto default reads
SHEEPRL_TPU_FUSED_GRU (default off).
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LN_EPS = 1e-5  # models.LayerNorm default
_B_TILE = 256
_D_TILE = 512
# Per-grid-step VMEM budget for the W tile (f32): D_TILE * 3H * 4 bytes
_W_TILE_BUDGET = 8 * 1024 * 1024
# Mosaic's default scoped-VMEM limit on a TPU v5e (the kernel sets no compiler
# params): a kernel whose blocks sum past it is refused at compile time.
_VMEM_LIMIT_BYTES = 16 * 1024 * 1024


def _tiles(batch: int, d: int, hidden: int) -> Tuple[int, int, int, int]:
    """(padded batch, padded D, batch tile, D tile) the kernel runs with."""
    bp = batch + (-batch) % 8
    dp = d + (-d) % 128
    b_tile = min(_B_TILE, bp)
    # Adapt the D tile to the VMEM budget: wide hidden states (L/XL configs,
    # 3H up to 12k) shrink the K-tile instead of losing the kernel.
    d_tile = min(_D_TILE, dp)
    while d_tile > 128 and d_tile * 3 * hidden * 4 > _W_TILE_BUDGET:
        d_tile //= 2
    return bp, dp, b_tile, d_tile


def _vmem_bytes(batch: int, d: int, hidden: int, itemsize: int) -> int:
    """Upper bound on the VMEM one grid step holds, counted as the compiler
    counts it: every input and output block double-buffered (inp, W, the
    three [1, 3H] vectors, h; out and the f32 z), plus the f32 accumulator.
    Within a few percent above the compiler's own total at every Dreamer
    size (S..XL, train and imagination batch), and never below it."""
    _, _, b_tile, d_tile = _tiles(batch, d, hidden)
    h3 = 3 * hidden
    inputs = (b_tile * d_tile + d_tile * h3 + b_tile * hidden) * itemsize + h3 * (itemsize + 4 + 4)
    outputs = b_tile * hidden * itemsize + b_tile * h3 * 4
    return 2 * (inputs + outputs) + b_tile * h3 * 4


def ineligible_reason(batch: int, d: int, hidden: int, itemsize: int) -> Optional[str]:
    """Why the kernel cannot take this shape on a TPU, or None when it can."""
    if hidden % 128 != 0:
        return f"hidden size {hidden} is not a multiple of the 128-lane tile"
    need = _vmem_bytes(batch, d, hidden, itemsize)
    if need > _VMEM_LIMIT_BYTES:
        return (
            f"its blocks need {need / 2**20:.1f} MiB of VMEM per grid step, over the "
            f"{_VMEM_LIMIT_BYTES / 2**20:.0f} MiB scoped limit"
        )
    return None


def _gates_from_z(z, scale, ln_bias, h):
    """The elementwise tail: biased pre-LN z [B, 3H] -> new state [B, H].
    Differentiated in the custom backward; must match the kernel epilogue."""
    zf = z.astype(jnp.float32)
    mu = zf.mean(-1, keepdims=True)
    var = ((zf - mu) ** 2).mean(-1, keepdims=True)
    zf = (zf - mu) * jax.lax.rsqrt(var + _LN_EPS)
    zf = zf * scale + ln_bias
    hidden = h.shape[-1]
    reset = jax.nn.sigmoid(zf[..., :hidden])
    cand = jnp.tanh(reset * zf[..., hidden : 2 * hidden])
    update = jax.nn.sigmoid(zf[..., 2 * hidden :] - 1)
    hf = h.astype(jnp.float32)
    return (update * cand + (1 - update) * hf).astype(h.dtype)


def _plain_ln_gru(inp, w, b, scale, ln_bias, h):
    """Reference computation (identical math to models.LayerNormGRUCell)."""
    z = (inp @ w + b).astype(jnp.float32)
    return _gates_from_z(z, scale, ln_bias, h), z


def _kernel(inp_ref, w_ref, b_ref, scale_ref, lnb_ref, h_ref, out_ref, z_ref, acc_ref, *, hidden: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(
        inp_ref[:].astype(jnp.float32),
        w_ref[:].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(1) - 1)
    def _epilogue():
        z = acc_ref[:] + b_ref[:].astype(jnp.float32)
        z_ref[:] = z
        mu = z.mean(-1, keepdims=True)
        var = ((z - mu) ** 2).mean(-1, keepdims=True)
        z = (z - mu) * jax.lax.rsqrt(var + _LN_EPS)
        z = z * scale_ref[:].astype(jnp.float32) + lnb_ref[:].astype(jnp.float32)
        reset = jax.nn.sigmoid(z[:, :hidden])
        cand = jnp.tanh(reset * z[:, hidden : 2 * hidden])
        update = jax.nn.sigmoid(z[:, 2 * hidden :] - 1)
        h = h_ref[:].astype(jnp.float32)
        out_ref[:] = (update * cand + (1 - update) * h).astype(out_ref.dtype)


def _pallas_ln_gru(inp, w, b, scale, ln_bias, h, *, interpret: bool = False):
    """Returns (new_state [B, H], biased pre-LN z [B, 3H] f32)."""
    batch, d = inp.shape
    hidden = h.shape[-1]
    h3 = 3 * hidden

    # Pad batch to the f32 sublane tile and D to the lane tile; zero rows and
    # zero K-columns do not perturb the matmul.
    bp, dp, b_tile, d_tile = _tiles(batch, d, hidden)
    if bp != batch:
        inp = jnp.pad(inp, ((0, bp - batch), (0, 0)))
        h = jnp.pad(h, ((0, bp - batch), (0, 0)))
    if dp != d:
        inp = jnp.pad(inp, ((0, 0), (0, dp - d)))
        w = jnp.pad(w, ((0, dp - d), (0, 0)))
    grid = (pl.cdiv(bp, b_tile), pl.cdiv(dp, d_tile))

    out, z = pl.pallas_call(
        functools.partial(_kernel, hidden=hidden),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_tile, d_tile), lambda i, k: (i, k)),
            pl.BlockSpec((d_tile, h3), lambda i, k: (k, 0)),
            pl.BlockSpec((1, h3), lambda i, k: (0, 0)),
            pl.BlockSpec((1, h3), lambda i, k: (0, 0)),
            pl.BlockSpec((1, h3), lambda i, k: (0, 0)),
            pl.BlockSpec((b_tile, hidden), lambda i, k: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((b_tile, hidden), lambda i, k: (i, 0)),
            pl.BlockSpec((b_tile, h3), lambda i, k: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, hidden), h.dtype),
            jax.ShapeDtypeStruct((bp, h3), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((b_tile, h3), jnp.float32)],
        interpret=interpret,
    )(inp, w, b.reshape(1, -1), scale.reshape(1, -1), ln_bias.reshape(1, -1), h)
    return out[:batch], z[:batch]


def _eligible(inp, w, h) -> bool:
    if jax.default_backend() != "tpu":
        return False
    batch, d = inp.shape
    reason = ineligible_reason(batch, d, h.shape[-1], jnp.dtype(inp.dtype).itemsize)
    if reason is not None:
        # The caller asked for the fused cell (fused=True or the env knob):
        # say that it is not getting it. The warnings registry shows each
        # distinct message, hence each shape, once.
        warnings.warn(
            f"fused LN-GRU kernel skipped for inp[{batch}, {d}] x W[{d}, {w.shape[-1]}] "
            f"({jnp.dtype(inp.dtype).name}): {reason}; running the plain-JAX cell"
        )
    return reason is None


@jax.custom_vjp
def fused_ln_gru(inp, w, b, scale, ln_bias, h):
    """One LN-GRU cell step: inp [B, D] (already concat of [h, x]), dense
    kernel w [D, 3H] + bias b [3H], LayerNorm scale/bias [3H], state h [B, H]
    -> new state [B, H]."""
    if _eligible(inp, w, h):
        return _pallas_ln_gru(inp, w, b, scale, ln_bias, h)[0]
    return _plain_ln_gru(inp, w, b, scale, ln_bias, h)[0]


def _fwd(inp, w, b, scale, ln_bias, h):
    if _eligible(inp, w, h):
        out, z = _pallas_ln_gru(inp, w, b, scale, ln_bias, h)
    else:
        out, z = _plain_ln_gru(inp, w, b, scale, ln_bias, h)
    return out, (inp, w, b, scale, ln_bias, h, z)


def _bwd(residuals, g):
    inp, w, b, scale, ln_bias, h, z = residuals
    # Elementwise tail gradient from the saved projection — no matmul
    # recompute.
    _, tail_vjp = jax.vjp(_gates_from_z, z, scale, ln_bias, h)
    dz, dscale, dln_bias, dh_tail = tail_vjp(g)
    dz = dz.astype(jnp.float32)
    dinp = (dz @ w.astype(jnp.float32).T).astype(inp.dtype)
    dw = (inp.astype(jnp.float32).T @ dz).astype(w.dtype)
    db = dz.sum(0).astype(b.dtype)
    return dinp, dw, db, dscale, dln_bias, dh_tail


fused_ln_gru.defvjp(_fwd, _bwd)

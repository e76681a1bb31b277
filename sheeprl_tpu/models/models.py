"""NN building blocks (flax.linen), TPU-first.

Capability parity with the reference model library
(sheeprl/models/models.py:16-525 and sheeprl/utils/model.py:34-223), designed
for XLA:TPU rather than translated from torch:

- Convolutions use **NHWC** layout — the TPU-native format (the reference is
  NCHW; here pixels stay channel-last from env to loss, so XLA never inserts
  transposes in front of the MXU).
- Per-layer dropout/norm/activation configurability is kept (reference
  `miniblock`, sheeprl/utils/model.py:34-88; order: layer → dropout → norm →
  activation), but layers are declared inline in `nn.compact` — shape
  inference removes the reference's input-size bookkeeping and dummy-forward
  probing (e.g. NatureCNN's probe at sheeprl/models/models.py:312-314).
- All blocks take a `dtype` (compute) / `param_dtype` pair wired from the
  precision policy; LayerNorm always computes statistics in fp32 and returns
  the input dtype (parity with the dtype-preserving LayerNorm,
  sheeprl/models/models.py:521-525 — and the right call on TPU where bf16
  accumulation of variance is lossy).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import os

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any
ActivationLike = Union[None, str, Callable[[jax.Array], jax.Array]]


_ACTIVATIONS: Dict[str, Callable[[jax.Array], jax.Array]] = {
    "relu": nn.relu,
    "tanh": jnp.tanh,
    "silu": nn.silu,
    "swish": nn.silu,
    "gelu": nn.gelu,
    "elu": nn.elu,
    "leaky_relu": nn.leaky_relu,
    "sigmoid": nn.sigmoid,
    "identity": lambda x: x,
    "none": lambda x: x,
}


def get_activation(act: ActivationLike) -> Callable[[jax.Array], jax.Array]:
    """Resolve an activation given by name (config-friendly) or callable."""
    if act is None:
        return _ACTIVATIONS["identity"]
    if callable(act):
        return act
    try:
        return _ACTIVATIONS[str(act).lower()]
    except KeyError:
        raise ValueError(f"Unknown activation '{act}'. Valid: {sorted(_ACTIVATIONS)}") from None


def _per_layer(spec: Any, num_layers: int, what: str) -> Sequence[Any]:
    """Broadcast a single spec to `num_layers`, or validate a per-layer list
    (reference `create_layers`, sheeprl/utils/model.py:91-139)."""
    if isinstance(spec, (list, tuple)):
        if len(spec) != num_layers:
            raise ValueError(f"Got {len(spec)} {what} specs for {num_layers} layers")
        return list(spec)
    return [spec] * num_layers


class LayerNorm(nn.Module):
    """LayerNorm computing statistics in fp32, returning the input dtype.

    Parity: dtype-preserving LayerNorm (sheeprl/models/models.py:521-525).
    On TPU this keeps the reduction out of bf16 while leaving the surrounding
    matmuls in the compute dtype.
    """

    epsilon: float = 1e-5
    use_scale: bool = True
    use_bias: bool = True
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        input_dtype = x.dtype
        out = nn.LayerNorm(
            epsilon=self.epsilon,
            use_scale=self.use_scale,
            use_bias=self.use_bias,
            dtype=jnp.float32,
            param_dtype=self.param_dtype,
        )(x.astype(jnp.float32))
        return out.astype(input_dtype)


# Channel-last is the native layout here, so the reference's
# LayerNormChannelLast (sheeprl/models/models.py:507-518) — a permute/LN/
# permute sandwich around NCHW — degenerates to plain LayerNorm over the
# trailing channel dim. Exported under the same name for config parity.
LayerNormChannelLast = LayerNorm


_NORMS: Dict[str, Callable[..., nn.Module]] = {
    "layer_norm": LayerNorm,
    "layer_norm_channel_last": LayerNormChannelLast,
    "rms_norm": nn.RMSNorm,
}


def make_norm(norm: Union[None, str, Callable[..., nn.Module]], args: Optional[dict]) -> Optional[nn.Module]:
    if norm is None:
        return None
    args = dict(args or {})
    # torch LayerNorm configs carry normalized_shape; flax infers it.
    args.pop("normalized_shape", None)
    # torch spells the epsilon kwarg "eps".
    if "eps" in args:
        args["epsilon"] = args.pop("eps")
    if callable(norm) and not isinstance(norm, str):
        return norm(**args)
    try:
        return _NORMS[str(norm).lower()](**args)
    except KeyError:
        raise ValueError(f"Unknown norm layer '{norm}'. Valid: {sorted(_NORMS)}") from None


def _apply_block(
    x: jax.Array,
    *,
    dropout: Optional[float],
    norm: Union[None, str, Callable[..., nn.Module]],
    norm_args: Optional[dict],
    activation: ActivationLike,
    deterministic: bool,
) -> jax.Array:
    """Post-layer stack in reference miniblock order: dropout → norm → act
    (sheeprl/utils/model.py:80-88)."""
    if dropout:
        x = nn.Dropout(rate=float(dropout), deterministic=deterministic)(x)
    norm_mod = make_norm(norm, norm_args)
    if norm_mod is not None:
        x = norm_mod(x)
    return get_activation(activation)(x)


class MLP(nn.Module):
    """Configurable MLP backbone (reference: sheeprl/models/models.py:16-119).

    `hidden_sizes` hidden blocks of Dense → [dropout] → [norm] → activation,
    plus an optional bare `output_dim` Dense head. `flatten_dim` flattens the
    input starting at that axis (negative axes supported), matching the
    reference's `obs.flatten(self._flatten_dim)`.

    Any of `activation`, `norm_layer`, `norm_args`, `dropout`, `layer_args`
    may be a per-layer list of length `len(hidden_sizes)`.
    """

    hidden_sizes: Sequence[int] = ()
    output_dim: Optional[int] = None
    activation: Union[ActivationLike, Sequence[ActivationLike]] = "relu"
    norm_layer: Any = None
    norm_args: Any = None
    dropout: Union[None, float, Sequence[Optional[float]]] = None
    layer_args: Any = None
    flatten_dim: Optional[int] = None
    kernel_init: Optional[Callable] = None
    output_kernel_init: Optional[Callable] = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        if len(self.hidden_sizes) < 1 and self.output_dim is None:
            raise ValueError("The number of layers should be at least 1.")
        if self.flatten_dim is not None:
            start = self.flatten_dim % x.ndim
            x = x.reshape(*x.shape[:start], -1)
        n = len(self.hidden_sizes)
        acts = _per_layer(self.activation, n, "activation")
        norms = _per_layer(self.norm_layer, n, "norm")
        norm_args = _per_layer(self.norm_args, n, "norm_args")
        drops = _per_layer(self.dropout, n, "dropout")
        largs = _per_layer(self.layer_args, n, "layer_args")
        x = x.astype(self.dtype)
        for i, size in enumerate(self.hidden_sizes):
            kw = dict(largs[i] or {})
            init_kw = {"kernel_init": self.kernel_init} if self.kernel_init is not None else {}
            x = nn.Dense(
                size,
                use_bias=kw.get("bias", True),
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"dense_{i}",
                **init_kw,
            )(x)
            x = _apply_block(
                x,
                dropout=drops[i],
                norm=norms[i],
                norm_args=norm_args[i],
                activation=acts[i],
                deterministic=deterministic,
            )
        if self.output_dim is not None:
            out_init = self.output_kernel_init or self.kernel_init
            init_kw = {"kernel_init": out_init} if out_init is not None else {}
            x = nn.Dense(
                self.output_dim, dtype=self.dtype, param_dtype=self.param_dtype, name="output", **init_kw
            )(x)
        return x


def _pair(v: Union[int, Sequence[int]]) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    return tuple(v)  # type: ignore[return-value]


class CNN(nn.Module):
    """Configurable conv stack, NHWC (reference: sheeprl/models/models.py:122-202).

    `layer_args` per layer supports torch-style keys {kernel_size, stride,
    padding, bias} so algorithm configs stay portable; padding ints are
    symmetric pads (torch semantics), strings pass through to XLA ("SAME",
    "VALID").
    """

    hidden_channels: Sequence[int]
    activation: Union[ActivationLike, Sequence[ActivationLike]] = "relu"
    norm_layer: Any = None
    norm_args: Any = None
    dropout: Union[None, float, Sequence[Optional[float]]] = None
    layer_args: Any = None
    kernel_init: Optional[Callable] = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        n = len(self.hidden_channels)
        if n < 1:
            raise ValueError("The number of layers should be at least 1.")
        acts = _per_layer(self.activation, n, "activation")
        norms = _per_layer(self.norm_layer, n, "norm")
        norm_args = _per_layer(self.norm_args, n, "norm_args")
        drops = _per_layer(self.dropout, n, "dropout")
        largs = _per_layer(self.layer_args, n, "layer_args")
        init_kw = {"kernel_init": self.kernel_init} if self.kernel_init is not None else {}
        x = x.astype(self.dtype)
        for i, ch in enumerate(self.hidden_channels):
            kw = dict(largs[i] or {})
            kernel = _pair(kw.get("kernel_size", 3))
            stride = _pair(kw.get("stride", 1))
            pad = kw.get("padding", 0)
            padding = [(p, p) for p in _pair(pad)] if not isinstance(pad, str) else pad
            x = nn.Conv(
                ch,
                kernel_size=kernel,
                strides=stride,
                padding=padding,
                use_bias=kw.get("bias", True),
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"conv_{i}",
                **init_kw,
            )(x)
            x = _apply_block(
                x,
                dropout=drops[i],
                norm=norms[i],
                norm_args=norm_args[i],
                activation=acts[i],
                deterministic=deterministic,
            )
        return x


class DeCNN(nn.Module):
    """Configurable transposed-conv stack, NHWC (reference: models.py:205-285).

    torch ConvTranspose2d-style layer_args {kernel_size, stride, padding,
    output_padding, bias} are mapped onto lax.conv_transpose padding so a
    torch-shaped decoder config produces identical output spatial sizes:
    out = (in-1)*stride - 2*pad + kernel + output_padding.
    """

    hidden_channels: Sequence[int]
    activation: Union[ActivationLike, Sequence[ActivationLike]] = "relu"
    norm_layer: Any = None
    norm_args: Any = None
    dropout: Union[None, float, Sequence[Optional[float]]] = None
    layer_args: Any = None
    kernel_init: Any = None
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True) -> jax.Array:
        n = len(self.hidden_channels)
        if n < 1:
            raise ValueError("The number of layers should be at least 1.")
        acts = _per_layer(self.activation, n, "activation")
        norms = _per_layer(self.norm_layer, n, "norm")
        norm_args = _per_layer(self.norm_args, n, "norm_args")
        drops = _per_layer(self.dropout, n, "dropout")
        largs = _per_layer(self.layer_args, n, "layer_args")
        kernel_inits = _per_layer(self.kernel_init, n, "kernel_init")
        x = x.astype(self.dtype)
        for i, ch in enumerate(self.hidden_channels):
            kw = dict(largs[i] or {})
            kernel = _pair(kw.get("kernel_size", 3))
            stride = _pair(kw.get("stride", 1))
            pad = _pair(kw.get("padding", 0))
            out_pad = _pair(kw.get("output_padding", 0))
            # torch transposed-conv output size, expressed as lax.conv_transpose
            # explicit padding: lax pads (k-1-p) on each side of the dilated
            # input; output_padding extends the high side.
            padding = [
                (kernel[0] - 1 - pad[0], kernel[0] - 1 - pad[0] + out_pad[0]),
                (kernel[1] - 1 - pad[1], kernel[1] - 1 - pad[1] + out_pad[1]),
            ]
            init_kw = {"kernel_init": kernel_inits[i]} if kernel_inits[i] is not None else {}
            x = nn.ConvTranspose(
                ch,
                kernel_size=kernel,
                strides=stride,
                padding=padding,
                use_bias=kw.get("bias", True),
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"deconv_{i}",
                **init_kw,
            )(x)
            x = _apply_block(
                x,
                dropout=drops[i],
                norm=norms[i],
                norm_args=norm_args[i],
                activation=acts[i],
                deterministic=deterministic,
            )
        return x


class NatureCNN(nn.Module):
    """DQN Nature trunk + dense head (reference: models.py:288-328).

    Conv 32/64/64 with (8,4)/(4,2)/(3,1) kernels/strides, flatten, Dense to
    `features_dim`, ReLU. Input NHWC. No dummy-forward probing needed: flax
    infers the flattened dim at init.
    """

    features_dim: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = CNN(
            hidden_channels=(32, 64, 64),
            layer_args=[
                {"kernel_size": 8, "stride": 4},
                {"kernel_size": 4, "stride": 2},
                {"kernel_size": 3, "stride": 1},
            ],
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="cnn",
        )(x)
        x = x.reshape(*x.shape[:-3], -1)
        x = nn.Dense(self.features_dim, dtype=self.dtype, param_dtype=self.param_dtype, name="fc")(x)
        return nn.relu(x)


class LayerNormGRUCell(nn.Module):
    """Hafner GRU cell: LN after the fused input projection, `update-1` bias,
    tanh candidate gated by reset (reference: sheeprl/models/models.py:331-410,
    itself from danijar/dreamerv2 nets.py).

        x = LN(W [h, x])                (single fused matmul — MXU-friendly)
        reset, cand, update = split(x, 3)
        cand = tanh(sigmoid(reset) * cand)
        update = sigmoid(update - 1)
        h' = update * cand + (1 - update) * h

    This is the per-step body of every Dreamer RSSM; the sequence loop lives
    in the caller as `lax.scan` (never a Python loop — SURVEY §7.2). With
    ``fused=None`` (auto) the standard bias+LN configuration routes through
    the Pallas kernel (models/pallas_gru.py) on TPU — same math, same param
    tree, one VMEM-resident epilogue instead of an HBM round-trip of z.
    """

    hidden_size: int
    bias: bool = True
    layer_norm: bool = True
    fused: Optional[bool] = None  # None = auto (TPU + bias + LN)
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, h: jax.Array, x: jax.Array) -> jax.Array:
        inp = jnp.concatenate([h.astype(self.dtype), x.astype(self.dtype)], axis=-1)
        # Auto default is OFF: the round-3 A/B (CHANGES.md, "Round-3 profile";
        # old code, not re-measured) showed the fused kernel winning at the
        # cell level forward (1.45x at B=1024) but neutral-to-slightly-
        # negative inside the full DV3 train step, where convs dominate and
        # the custom-VJP boundary blocks XLA cross-fusion.
        # ONE knob: opt in per-module (fused=True) or globally via
        # SHEEPRL_TPU_FUSED_GRU=1 (read only here).
        use_fused = (
            self.fused
            if self.fused is not None
            else os.environ.get("SHEEPRL_TPU_FUSED_GRU", "0") == "1"
        )
        if use_fused and self.layer_norm:
            from sheeprl_tpu.models.pallas_gru import fused_ln_gru

            # Raw params declared under the SAME tree as the unfused path
            # (linear/{kernel[,bias]}, norm/LayerNorm_0/{scale,bias}) so
            # checkpoints and the `fused` flag are interchangeable. The
            # Dreamer RSSM config (bias=False: LN provides the shift) feeds
            # the kernel a constant zero bias.
            kernel, dense_bias = _DenseParams(
                3 * self.hidden_size, self.bias, self.param_dtype, name="linear"
            )(inp.shape[-1])
            if dense_bias is None:
                dense_bias = jnp.zeros((3 * self.hidden_size,), self.dtype)
            scale, ln_bias = _LayerNormParams(self.param_dtype, name="norm")(3 * self.hidden_size)
            return fused_ln_gru(
                inp,
                kernel.astype(self.dtype),
                dense_bias.astype(self.dtype),
                scale,
                ln_bias,
                h.astype(self.dtype),
            )
        z = nn.Dense(
            3 * self.hidden_size,
            use_bias=self.bias,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="linear",
        )(inp)
        if self.layer_norm:
            z = LayerNorm(param_dtype=self.param_dtype, name="norm")(z)
        reset, cand, update = jnp.split(z, 3, axis=-1)
        reset = nn.sigmoid(reset)
        cand = jnp.tanh(reset * cand)
        update = nn.sigmoid(update - 1)
        return update * cand + (1 - update) * h.astype(self.dtype)


class _DenseParams(nn.Module):
    """Param-holder mirroring nn.Dense's tree ({kernel, bias})."""

    features: int
    use_bias: bool
    param_dtype: Dtype

    @nn.compact
    def __call__(self, in_dim: int):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (in_dim, self.features), self.param_dtype
        )
        bias = (
            self.param("bias", nn.initializers.zeros_init(), (self.features,), self.param_dtype)
            if self.use_bias
            else None
        )
        return kernel, bias


class _LayerNormInnerParams(nn.Module):
    param_dtype: Dtype

    @nn.compact
    def __call__(self, dim: int):
        scale = self.param("scale", nn.initializers.ones_init(), (dim,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros_init(), (dim,), self.param_dtype)
        return scale, bias


class _LayerNormParams(nn.Module):
    """Param-holder mirroring models.LayerNorm's tree
    (norm/LayerNorm_0/{scale, bias})."""

    param_dtype: Dtype

    @nn.compact
    def __call__(self, dim: int):
        return _LayerNormInnerParams(self.param_dtype, name="LayerNorm_0")(dim)


class MultiEncoder(nn.Module):
    """Dict-obs fusion: concat CNN features with MLP features
    (reference: sheeprl/models/models.py:413-475).

    `cnn_encoder` / `mlp_encoder` are submodules taking the obs dict and
    returning a feature vector; at least one must be set.
    """

    cnn_encoder: Optional[nn.Module] = None
    mlp_encoder: Optional[nn.Module] = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.cnn_encoder is None and self.mlp_encoder is None:
            raise ValueError("There must be at least one encoder, both cnn and mlp encoders are None")

    def __call__(self, obs: Dict[str, jax.Array], *args: Any, **kwargs: Any) -> jax.Array:
        outs = []
        if self.cnn_encoder is not None:
            outs.append(self.cnn_encoder(obs, *args, **kwargs))
        if self.mlp_encoder is not None:
            outs.append(self.mlp_encoder(obs, *args, **kwargs))
        if len(outs) == 2:
            return jnp.concatenate(outs, axis=-1)
        return outs[0]


class MultiDecoder(nn.Module):
    """Latent → dict of per-key reconstructions
    (reference: sheeprl/models/models.py:478-504)."""

    cnn_decoder: Optional[nn.Module] = None
    mlp_decoder: Optional[nn.Module] = None

    def __post_init__(self) -> None:
        super().__post_init__()
        if self.cnn_decoder is None and self.mlp_decoder is None:
            raise ValueError("There must be a decoder, both cnn and mlp decoders are None")

    def __call__(self, x: jax.Array) -> Dict[str, jax.Array]:
        out: Dict[str, jax.Array] = {}
        if self.cnn_decoder is not None:
            out.update(self.cnn_decoder(x))
        if self.mlp_decoder is not None:
            out.update(self.mlp_decoder(x))
        return out

"""Plain reference for one PPO gradient step over a `phi4flash` token policy
(the decoder-hybrid-decoder of arXiv:2507.06607 with the differential
attention of arXiv:2410.05258, as the published config keys of
Phi-4-mini-flash-reasoning select them), written in straightforward float32
``jax.numpy``. It imports nothing of the program.

The model: residual stream ``h``; published depth ``N``, layer index ``l``
from 0; every layer ``h += Mixer_l(LN(h)); h += MLP(LN(h))``. ``LN`` is
LayerNorm with gain and bias (eps ``layer_norm_eps``); ``MLP(x) =
W_down(silu(W_gate x) * W_up x)``, no bias. After the last layer a final
LayerNorm; ``logits = LN(h) E^T`` with ``E`` the embedding (tied). No
positional embedding, no RoPE. The mixer by ``l``:

- **Mamba** (``l`` even, ``l <= N/2``): ``[x; z] = W_in u``; ``x =
  silu(conv(x))``, ``conv`` causal, depthwise, ``d_conv`` taps, with bias;
  ``[d; B; C] = W_x x``; ``Delta = softplus(W_dt d + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(Delta_t A) * s_{t-1} + (Delta_t x_t) B_t^T``;
  ``y_t = s_t C_t + D * x_t``; ``out = W_out(y * silu(z))``. One sequential
  `lax.scan` over the positions. Layer ``N/2`` hands on ``m = y``: the memory.
- **Attention** (``l`` odd): query heads and key/value heads of ``d`` =
  hidden / heads, taken in adjacent pairs, the values of a pair concatenated:
  ``A_i = softmax(q_i k_i^T / sqrt(d))``, ``o = (A_1 - lambda A_2) [v_1;
  v_2]``, ``o = RMSNorm_2d(o) (1 - lambda_init)`` (with a gain, eps
  ``layer_norm_eps``), ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; projections with
  biases. ``l < N/2``: position t sees keys ``t - window + 1 .. t``. ``l =
  N/2 + 1``: full causal; its ``K, V`` are kept. ``l >= N/2 + 3``: query and
  output projection only, keys and values are layer ``N/2 + 1``'s, causal.
- **Gated memory unit** (``l`` even, ``l > N/2``): ``W_2(m * silu(W_1 u))``.

Left padding: the context of a sequence begins at ``start``; a pad position
is no key, and feeds neither the convolution nor the state (``x`` is zeroed
before the convolution and after its SiLU).

Departures from the published model, each because the configuration states
it: **the value head** (PPO's, on the final normed state); **the held
layers** (``layers_held = [first, count]``: one pipeline stage; kinds and
``lambda_init`` follow the published index); **the vocabulary slice**.

What memory forces, and nothing else (the same numbers either way): each layer
under `jax.checkpoint`; the scan's positions in runs of 512 under
`jax.checkpoint` (still one position after another, in order); the masked
softmax 1024 queries at a time (each against all the keys it may see); the
gradient accumulated sequence by sequence; and, because four float32 trees of
697 M parameters are 11.2 GB on a 16 GB device and a host of 40 GiB holds ten
such trees at most beside the harness's own, Adam's moments are streamed leaf
group by leaf group: they rest on the device after a step (no gradient is in
the way then), go to the host while the next step's gradient is made, and
come back leaf by leaf as the update is made; the first step hands its
gradient back on the host (`Reference.step`).
The surrogate's clip is an operand (`adapters/ppo_lm.py`: `ASKED_CLIP`).

Naming of the weights: ``embed_head/{embed,final_norm_scale,final_norm_bias}``,
``ssm/l<l>/{norm_scale,norm_bias,w_in,conv_w,conv_b,w_x,w_dt,b_dt,A_log,D,w_out}``,
``window_attn/l<l>/`` and ``full_attn/l<l>/{norm_scale,norm_bias,wqkv,bqkv,wo,bo,lq1,lk1,lq2,lk2,subln}``,
``cross_attn/l<l>/{norm_scale,norm_bias,wq,bq,wo,bo,lq1,lk1,lq2,lk2,subln}``,
``gmu/l<l>/{norm_scale,norm_bias,w1,w2}``, ``mlp/l<l>/{norm_scale,norm_bias,w_gate,w_up,w_down}``,
``value/w``; ``<l>`` is the published index. ``conv_w`` is [taps, inner],
``A_log`` [inner, state].

``precision`` is ``"highest"`` (the reference proper) or a lower one for the
control, ``"bf16"`` or ``"fp8"``: both operands and the result of every matrix
product the configuration makes in bfloat16, and the activations between
them, are rounded to the lower type (fp8 with one scale per tensor), the
gradient passed straight through. Scan state, Delta, A, softmax, lambda, norm
statistics, the logits' accumulation, the value head, the losses and the
optimizer stay float32. Rounding is `lax.reduce_precision`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, jax.Array]
HI = lax.Precision.HIGHEST
MASKED = -1e30
SCAN_RUN = 512
QUERY_RUN = 1024
KIND_PREFIX = {"ssm": "ssm", "swa": "window_attn", "full": "full_attn", "cross": "cross_attn", "gmu": "gmu"}


def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "highest":
        return x
    if precision == "bf16":
        rounded = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        rounded = lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    return x + lax.stop_gradient(rounded - x)


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def kind_of(index: int, depth: int) -> str:
    """The mixer of published layer ``index`` of ``depth``."""
    if index % 2 == 0:
        return "ssm" if index <= depth // 2 else "gmu"
    if index < depth // 2:
        return "swa"
    return "full" if index == depth // 2 + 1 else "cross"


class Net:
    """The layer equations over a flat dict of weights, one sequence at a time."""

    def __init__(self, model: Dict[str, Any], precision: str = "highest") -> None:
        self.m = model
        self.precision = precision
        self.depth = int(model["published"]["num_hidden_layers"])
        first, count = model["layers_held"]
        self.held = list(range(first, first + count))

    def r(self, x):
        return _round(x, self.precision)

    def mm(self, x, w):
        return self.r(jnp.matmul(self.r(x), self.r(w), precision=HI))

    def norm(self, p: Params, pre: str, x):
        return self.r(layer_norm(x, p[pre + "norm_scale"], p[pre + "norm_bias"], self.m["layer_norm_eps"]))

    # ---------------------------------------------------------------- Mamba
    def mamba(self, p: Params, pre: str, u, real):
        """``u`` [S, H], ``real`` [S]: the output and the memory ``y`` [S, inner]."""
        m = self.m
        inner, state, rank, taps = m["expand"] * m["hidden_size"], m["d_state"], m["dt_rank"], m["d_conv"]
        S = u.shape[0]
        xz = self.mm(self.norm(p, pre, u), p[pre + "w_in"])
        x, z = xz[:, :inner], xz[:, inner:]
        x = jnp.where(real[:, None], x, 0.0)
        padded = jnp.concatenate([jnp.zeros((taps - 1, inner), x.dtype), x], axis=0)
        conv = sum(padded[k:k + S] * self.r(p[pre + "conv_w"])[k] for k in range(taps)) + self.r(p[pre + "conv_b"])
        x = self.r(jnp.where(real[:, None], silu(self.r(conv)), 0.0))
        dbc = self.mm(x, p[pre + "w_x"])
        d, B, C = dbc[:, :rank], dbc[:, rank:rank + state], dbc[:, rank + state:]
        delta = jax.nn.softplus(jnp.matmul(self.r(d), self.r(p[pre + "w_dt"]), precision=HI) + p[pre + "b_dt"])
        A = -jnp.exp(p[pre + "A_log"])  # [inner, state]

        def position(s, at):
            delta_t, x_t, B_t, C_t = at
            s = jnp.exp(delta_t[:, None] * A) * s + (delta_t * x_t)[:, None] * B_t[None, :]
            return s, jnp.matmul(s, C_t, precision=HI)

        @jax.checkpoint
        def run(s, at):  # the same positions in the same order: only what the backward pass keeps differs
            return lax.scan(position, s, at)

        s = jnp.zeros((inner, state), jnp.float32)
        ys = []
        for begin in range(0, S, SCAN_RUN):
            cut = slice(begin, min(begin + SCAN_RUN, S))
            s, y = run(s, (delta[cut], x[cut], B[cut], C[cut]))
            ys.append(y)
        y = self.r(jnp.concatenate(ys, axis=0) + p[pre + "D"] * x)
        return self.mm(self.r(y * silu(z)), p[pre + "w_out"]), y

    # ----------------------------------------------------------- attention
    def attention(self, p: Params, pre: str, index: int, u, start, shared):
        """``u`` [S, H]; ``shared`` = the full layer's (K, V) for a cross layer. Returns the output and (K, V)."""
        m = self.m
        heads, kv_heads, d = m["num_attention_heads"], m["num_key_value_heads"], m["hidden_size"] // m["num_attention_heads"]
        kind = kind_of(index, self.depth)
        S = u.shape[0]
        un = self.norm(p, pre, u)
        if kind == "cross":
            q = self.r(self.mm(un, p[pre + "wq"]) + self.r(p[pre + "bq"])).reshape(S, heads, d)
            K, V = shared
        else:
            qkv = self.r(self.mm(un, p[pre + "wqkv"]) + self.r(p[pre + "bqkv"]))
            q = qkv[:, :heads * d].reshape(S, heads, d)
            K = qkv[:, heads * d:(heads + kv_heads) * d].reshape(S, kv_heads, d)
            V = qkv[:, (heads + kv_heads) * d:].reshape(S, kv_heads, d)
        lambda_init = 0.8 - 0.6 * math.exp(-0.3 * index)
        lam = jnp.exp(jnp.sum(p[pre + "lq1"] * p[pre + "lk1"])) - jnp.exp(jnp.sum(p[pre + "lq2"] * p[pre + "lk2"])) + lambda_init
        pairs = kv_heads // 2  # key/value pairs; each serves heads / (2 kv_heads) x 2 query pairs' worth of query heads
        group = heads // kv_heads  # query pairs of one key/value pair
        # query head 2 * (group * j + g) + i is member i of query pair g of key/value pair j; key head 2 j + i its key
        k2 = K.reshape(S, pairs, 2, d)
        v2 = V.reshape(S, pairs, 2 * d)  # the pair's values side by side
        window = m["sliding_window"] if kind == "swa" else None
        at_k = jnp.arange(S)

        @jax.checkpoint
        def queries(q_run, at_q):
            q2 = q_run.reshape(q_run.shape[0], pairs, group, 2, d)
            scores = jnp.einsum("qjgid,kjid->jgiqk", self.r(q2), self.r(k2), precision=HI) / math.sqrt(d)
            valid = (at_k[None, :] <= at_q[:, None]) & (at_k[None, :] >= start)
            if window is not None:
                valid = valid & (at_k[None, :] > at_q[:, None] - window)
            probs = jax.nn.softmax(jnp.where(valid, scores, MASKED), axis=-1)
            each = self.r(jnp.einsum("jgiqk,kjv->qjgiv", self.r(probs), self.r(v2), precision=HI))
            return each[:, :, :, 0] - lam * each[:, :, :, 1]  # [Q, pairs, group, 2 d]

        mixed = jnp.concatenate([queries(q[b:b + QUERY_RUN], at_k[b:b + QUERY_RUN]) for b in range(0, S, QUERY_RUN)], axis=0)
        mixed = mixed * lax.rsqrt(jnp.mean(jnp.square(mixed), axis=-1, keepdims=True) + m["layer_norm_eps"]) * p[pre + "subln"]
        mixed = self.r(mixed * (1.0 - lambda_init)).reshape(S, heads * d)
        return self.r(self.mm(mixed, p[pre + "wo"]) + self.r(p[pre + "bo"])), (K, V)

    # --------------------------------------------------------------- layers
    def layer(self, index: int, p: Params, x, start, memory, shared):
        kind = kind_of(index, self.depth)
        pre = f"{KIND_PREFIX[kind]}/l{index}/"
        real = jnp.arange(x.shape[0]) >= start
        if kind == "ssm":
            out, y = self.mamba(p, pre, x, real)
            memory = y if index == self.depth // 2 else memory
        elif kind == "gmu":
            out = self.mm(self.r(memory * silu(self.mm(self.norm(p, pre, x), p[pre + "w1"]))), p[pre + "w2"])
        else:
            out, own = self.attention(p, pre, index, x, start, shared)
            shared = own if kind == "full" else shared
        x = self.r(x + out)
        pre = f"mlp/l{index}/"
        xn = self.norm(p, pre, x)
        hidden = self.r(silu(self.mm(xn, p[pre + "w_gate"])) * self.mm(xn, p[pre + "w_up"]))
        return self.r(x + self.mm(hidden, p[pre + "w_down"])), memory, shared

    def hidden(self, p: Params, tokens, start):
        """``tokens`` [S] whose context begins at ``start``: the residual stream after the last held layer."""
        x = self.r(p["embed_head/embed"][tokens])
        memory, shared = None, None
        for index in self.held:
            x, memory, shared = jax.checkpoint(partial(self.layer, index))(p, x, start, memory, shared)
        return x

    def heads(self, p: Params, x):
        normed = self.r(layer_norm(x, p["embed_head/final_norm_scale"], p["embed_head/final_norm_bias"], self.m["layer_norm_eps"]))
        logits = jnp.matmul(self.r(normed), self.r(p["embed_head/embed"]).T, precision=HI)
        values = jnp.matmul(normed, p["value/w"], precision=HI)[..., 0]
        return logits, values

    def response_outputs(self, p: Params, tokens, start):
        """Logits [R, V] and values [R] at the positions the response tokens were drawn from."""
        P, R = self.m["prompt_len"], self.m["rollout_steps"]
        return self.heads(p, self.hidden(p, tokens, start)[P - 1:P - 1 + R])


def sequence_losses(net: Net, p: Params, seq: Dict[str, jax.Array], loss_tokens, clip_coef):
    """One sequence's share of the minibatch's three token means."""
    m = net.m
    P = m["prompt_len"]
    logits, values = net.response_outputs(p, seq["tokens"], seq["start"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    new_logprobs = jnp.take_along_axis(logp, seq["tokens"][P:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    ratio = jnp.exp(new_logprobs - seq["logprobs"])
    adv = seq["advantages"]
    surrogate = -jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - clip_coef, 1 + clip_coef))
    mean = lambda x: jnp.sum(x * seq["mask"]) / loss_tokens  # noqa: E731
    policy, value, ent = mean(surrogate), mean(jnp.square(values - seq["returns"])), mean(-entropy)
    total = policy + m["vf_coef"] * value + m["ent_coef"] * ent
    return total, {"policy": policy, "value": value, "entropy": ent}


@partial(jax.jit, donate_argnums=(0,))
def _add(a: Params, b: Params) -> Params:
    return {k: a[k] + b[k] for k in a}


@jax.jit
def _global_norm(grads: Params):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))


@partial(jax.jit, static_argnames=("lr", "eps", "b1", "b2"))
def _adam_leaf(p, g, mu, nu, count, lr, eps, b1=0.9, b2=0.999):
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    step = (mu / (1 - b1 ** count)) / (jnp.sqrt(nu / (1 - b2 ** count)) + eps)
    return p - lr * step, mu, nu


class Reference:
    def __init__(self, model: Dict[str, Any], precision: str = "highest") -> None:
        self.m = model
        self.net = Net(model, precision)
        self._grad = jax.jit(jax.value_and_grad(partial(sequence_losses, self.net), has_aux=True))
        self._outputs = jax.jit(self.net.response_outputs)

    def init(self, params: Params) -> Dict[str, Any]:
        return {"params": dict(params), "mu": None, "nu": None, "count": 0}  # no moments yet: zeros, made leaf by leaf when first needed

    def gradient(self, params: Params, batch: Dict[str, Any], clip_coef=None) -> Tuple[Params, Dict[str, jax.Array]]:
        """The minibatch's gradient (before clipping by its norm) and its three losses; ``clip_coef`` is the
        surrogate's clip, the configuration's unless given (an operand: nothing compiles again)."""
        clip_coef = jnp.float32(self.m["clip_coef"] if clip_coef is None else clip_coef)
        loss_tokens = jnp.maximum(jnp.sum(jnp.asarray(batch["mask"], jnp.float32)), 1.0)
        grads, losses = None, None
        for b in range(len(batch["tokens"])):
            seq = {k: jnp.asarray(v[b]) for k, v in batch.items()}
            (_, seq_losses), seq_grads = self._grad(params, seq, loss_tokens, clip_coef)
            grads = seq_grads if grads is None else _add(grads, seq_grads)
            losses = seq_losses if losses is None else {k: losses[k] + seq_losses[k] for k in losses}
            del seq_grads
        return grads, losses

    def clipped(self, grads: Params) -> Params:
        norm = _global_norm(grads)
        scale = jnp.minimum(1.0, self.m["optim"]["clip"] / jnp.maximum(norm, 1e-30))
        return {k: g * scale for k, g in grads.items()}

    def first_gradient(self, state: Dict[str, Any], batch: Dict[str, Any], clip_coef=None) -> Dict[str, np.ndarray]:
        """The gradient as the optimizer gets it (clipped by its norm), on the host; nothing updated."""
        grads = self.clipped(self.gradient(state["params"], batch, clip_coef)[0])
        return {k: np.asarray(grads.pop(k)) for k in list(grads)}

    def step(self, state: Dict[str, Any], batch: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """One Adam step, leaf by leaf. Where the moments are at any time is a matter of room, not of numbers: a step
        leaves them on the device (beside the parameters, with no gradient in the way); the next step moves them to
        the host before its gradient, which needs the device, and brings each back as its leaf is updated. So between
        steps the host holds none of them, and whoever holds the last state holds no host copy either. The first
        step's gradient (as the optimizer gets it) goes to the host, ``out["grads"]``; later steps hand back none."""
        opt = self.m["optim"]
        moments = {}
        for name in ("mu", "nu"):  # off the device, leaf by leaf, letting each device copy go (the caller's state keeps them on the host now)
            if state[name] is not None:
                state[name] = moments[name] = {k: np.asarray(state[name].pop(k)) for k in list(state[name])}
        grads, losses = self.gradient(state["params"], batch)
        grads = self.clipped(grads)
        count = state["count"] + 1
        params, mu, nu, host = {}, {}, {}, {}
        for k in list(grads):
            g = grads.pop(k)
            before = [jnp.zeros_like(g) if name not in moments else jnp.asarray(moments[name][k]) for name in ("mu", "nu")]
            params[k], mu[k], nu[k] = _adam_leaf(state["params"][k], g, *before, float(count), lr=float(opt["lr"]), eps=float(opt["eps"]))
            if count == 1:
                host[k] = np.asarray(g)
        return {"params": params, "mu": mu, "nu": nu, "count": count}, {"losses": losses, "grads": host}

    def logits(self, params: Params, tokens, start) -> np.ndarray:
        """The full forward pass's logits [R, V] at the response positions of one sequence."""
        return np.asarray(self._outputs(params, jnp.asarray(tokens), jnp.asarray(start))[0])

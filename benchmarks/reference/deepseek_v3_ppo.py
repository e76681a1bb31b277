"""Plain reference for one PPO gradient step over a `deepseek_v3` token policy
(DeepSeek-V3 technical report, arXiv:2412.19437, sections 2.1.1-2.1.2, and the
published `modeling_deepseek_v3` equations behind the config keys), written in
straightforward float32 ``jax.numpy``. It imports nothing of the program.

The model, per layer (``x`` the residual stream, no biases anywhere):

- RMSNorm: ``x / sqrt(mean(x^2) + eps) * g``.
- Latent attention without query compression: ``q = x^ W_q`` (heads x 192,
  split nope 128 | rope 64); ``x^ W_kva`` -> 512 + 64: ``c = RMSNorm(first
  512)``, ``k_rope`` the last 64, one for all heads; ``c W_kvb`` -> heads x
  (128 | 128) = ``k_nope | v``. RoPE (theta 1e6) on ``q_rope`` and ``k_rope``
  with the channels paired (2i, 2i+1). Causal ``softmax(q.k / sqrt(192))``,
  ``concat_heads(P v) W_o``. Whole sequences, one ``[heads, S, S]`` block of
  scores a sequence, no cache.
- Layer 0: SwiGLU of width 6144. Layers 1..: ``s = sigmoid(x^ W_r)`` over 128
  experts in float32; the top 6 of ``s + b`` (``b`` selects only); weights
  ``s_sel / sum(s_sel) * 2.448``; ``y = sum_e w_e SwiGLU_e(x^) + SwiGLU_shared(x^)``.
  Every held expert is applied to every token and masked: no grouping, no
  capacity, no token dropped.
- Final RMSNorm; head 2048 -> vocabulary; value head 2048 -> 1.

Departures from the published model, each because the configuration states
it: **the value head** (PPO's, on the final normed state); **the held
experts** (``experts_held = [first, count]``: the router scores all
``n_routed_experts`` and selects among all of them, and only the held ones
add to the result: one chip's share of an expert-parallel deployment; what the
absent experts would add is left out); **the vocabulary slice** (embedding,
head, softmax and loss over ``vocab_size`` rows). Sequences are left-padded:
row ``b``'s context begins at ``start[b]``, positions count from there, keys
before it are masked.

The step: PPO's clipped surrogate, squared-error value loss and entropy over
the active response positions of ``batch`` whole sequences ``[prompt |
response]``, each a token mean over the minibatch (the denominators are sums
over the minibatch, computed first); the gradient is accumulated sequence by
sequence, each layer under `jax.checkpoint` (the same numbers, less memory),
so that the step fits beside the weights at the cell's size; clip by global
norm; Adam. Adam's moments live on the host between steps, and a step hands
its gradient back on the host. The surrogate's clip is an operand of the
gradient: the adapter asks for the altered minibatch's with the clip open
(`adapters/ppo_lm.py`: `ASKED_CLIP`), the steps themselves use the recipe's.

Naming of the weights: ``embed_head/{embed,head,final_norm}``,
``attention/l<i>/{norm,wq,wkv_a,kv_norm,wkv_b,wo}``,
``dense/l<i>/{norm,w_gate,w_up,w_down}``,
``router/l<i>/{norm,w,bias}``, ``experts/l<i>/{w_gate,w_up,w_down}`` ([held, in, out]),
``shared/l<i>/{w_gate,w_up,w_down}``, ``value/w``.

``precision`` is ``"highest"`` (the reference proper) or a lower one for the
control, ``"bf16"`` or ``"fp8"``: everything the configuration computes in its
compute type is then rounded to the lower type instead (both operands and the
result of every matrix product the configuration makes in bfloat16, and the
activations between them; fp8 with one scale per tensor), the gradient passed
straight through. The router, the softmax, the norms' statistics, the logits'
accumulation, the value head, the losses and the optimizer stay float32, as
the configuration keeps them. Rounding is `lax.reduce_precision`, never a
cast there and back, which the TPU compiler may drop.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, jax.Array]
HI = lax.Precision.HIGHEST
MASKED = -1e30


def _round(x: jax.Array, precision: str) -> jax.Array:
    if precision == "highest":
        return x
    if precision == "bf16":
        rounded = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    else:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        rounded = lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    return x + lax.stop_gradient(rounded - x)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, positions, theta):
    """Rotate the channel pairs (2i, 2i+1) of the last axis by the positions' angles; pairs stay in place."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # [S, dim/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if x.ndim == 3:  # [S, heads, dim]
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


class Net:
    """The layer equations over a flat dict of weights, one sequence at a time."""

    def __init__(self, model: Dict[str, Any], precision: str = "highest") -> None:
        self.m = model
        self.precision = precision

    def r(self, x):
        return _round(x, self.precision)

    def mm(self, x, w):
        return self.r(jnp.matmul(self.r(x), self.r(w), precision=HI))

    def swiglu(self, x, p: Params, prefix: str):
        return self.mm(self.r(silu(self.mm(x, p[prefix + "w_gate"])) * self.mm(x, p[prefix + "w_up"])), p[prefix + "w_down"])

    def attention(self, p: Params, i: int, x, positions, start):
        m = self.m
        heads, dn, dr, dv, rank = (m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                                   m["v_head_dim"], m["kv_lora_rank"])
        pre = f"attention/l{i}/"
        S = x.shape[0]
        xn = self.r(rms_norm(x, p[pre + "norm"], m["rms_norm_eps"]))
        q = self.mm(xn, p[pre + "wq"]).reshape(S, heads, dn + dr)
        kv = self.mm(xn, p[pre + "wkv_a"])
        latent = self.r(rms_norm(kv[:, :rank], p[pre + "kv_norm"], m["rms_norm_eps"]))
        k_rope = self.r(rope(kv[:, rank:], positions, m["rope_theta"]))
        q_nope, q_rope = q[..., :dn], self.r(rope(q[..., dn:], positions, m["rope_theta"]))
        kvb = self.mm(latent, p[pre + "wkv_b"]).reshape(S, heads, dn + dv)
        k_nope, v = kvb[..., :dn], kvb[..., dn:]
        scores = jnp.einsum("qhd,khd->hqk", self.r(q_nope), self.r(k_nope), precision=HI)
        scores = scores + jnp.einsum("qhd,kd->hqk", self.r(q_rope), self.r(k_rope), precision=HI)
        at = jnp.arange(S)
        valid = (at[None, :] <= at[:, None]) & (at[None, :] >= start)
        probs = jax.nn.softmax(jnp.where(valid[None], scores * (dn + dr) ** -0.5, MASKED), axis=-1)
        out = self.r(jnp.einsum("hqk,khd->qhd", self.r(probs), self.r(v), precision=HI)).reshape(S, heads * dv)
        return self.mm(out, p[pre + "wo"])

    def experts(self, p: Params, i: int, x):
        """The expert layer's output for ``x`` [S, H] and the experts every token chose [S, k]."""
        m = self.m
        first, held = m["experts_held"]
        xn = self.r(rms_norm(x, p[f"router/l{i}/norm"], m["rms_norm_eps"]))
        scores = jax.nn.sigmoid(jnp.matmul(xn, p[f"router/l{i}/w"], precision=HI))
        _, chosen = lax.top_k(scores + lax.stop_gradient(p[f"router/l{i}/bias"]), m["num_experts_per_tok"])
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if m["norm_topk_prob"]:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        weights = weights * m["routed_scaling_factor"]
        # [S, held]: the weight a token gives each held expert (0 where it did not choose it)
        per_expert = jnp.sum(jnp.where(chosen[..., None] == first + jnp.arange(held), weights[..., None], 0.0), axis=1)
        pre = f"experts/l{i}/"
        # every held expert over every token, [held, S, ...], then masked by the weights
        gate = self.r(jnp.einsum("sd,edf->esf", self.r(xn), self.r(p[pre + "w_gate"]), precision=HI))
        up = self.r(jnp.einsum("sd,edf->esf", self.r(xn), self.r(p[pre + "w_up"]), precision=HI))
        each = self.r(jnp.einsum("esf,efd->esd", self.r(silu(gate) * up), self.r(p[pre + "w_down"]), precision=HI))
        routed = jnp.sum(self.r(each * per_expert.T[:, :, None]), axis=0)
        return self.r(routed + self.swiglu(xn, p, f"shared/l{i}/")), chosen

    def hidden(self, p: Params, tokens, start):
        """``tokens`` [S] whose context begins at ``start``: the residual stream after the last layer, and the routes."""
        m = self.m
        positions = jnp.maximum(jnp.arange(tokens.shape[0]) - start, 0)
        x = self.r(p["embed_head/embed"][tokens])
        routes = []
        for i in range(m["num_hidden_layers"]):
            # the same numbers with or without the checkpoint: at the cell's size one
            # sequence's backward pass then fits beside the weights and the gradient
            x, chosen = jax.checkpoint(partial(self.layer, i))(p, x, positions, start)
            if chosen is not None:
                routes.append(chosen)
        return x, routes

    def layer(self, i: int, p: Params, x, positions, start):
        m = self.m
        x = self.r(x + self.attention(p, i, x, positions, start))
        if i < m["first_k_dense_replace"]:
            xn = self.r(rms_norm(x, p[f"dense/l{i}/norm"], m["rms_norm_eps"]))
            return self.r(x + self.swiglu(xn, p, f"dense/l{i}/")), None
        y, chosen = self.experts(p, i, x)
        return self.r(x + y), chosen

    def heads(self, p: Params, x):
        normed = self.r(rms_norm(x, p["embed_head/final_norm"], self.m["rms_norm_eps"]))
        logits = jnp.matmul(self.r(normed), self.r(p["embed_head/head"]), precision=HI)
        values = jnp.matmul(normed, p["value/w"], precision=HI)[..., 0]
        return logits, values

    def response_outputs(self, p: Params, tokens, start):
        """Logits [R, V] and values [R] at the positions the response tokens were drawn from."""
        P, R = self.m["prompt_len"], self.m["rollout_steps"]
        x, routes = self.hidden(p, tokens, start)
        logits, values = self.heads(p, x[P - 1:P - 1 + R])
        return logits, values, routes


def sequence_losses(net: Net, p: Params, seq: Dict[str, jax.Array], loss_tokens, clip_coef):
    """One sequence's share of the minibatch's three token means, and its routes."""
    m = net.m
    P = m["prompt_len"]
    logits, values, routes = net.response_outputs(p, seq["tokens"], seq["start"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    new_logprobs = jnp.take_along_axis(logp, seq["tokens"][P:, None], axis=-1)[:, 0]
    entropy = -jnp.sum(jnp.exp(logp) * logp, axis=-1)
    ratio = jnp.exp(new_logprobs - seq["logprobs"])
    adv = seq["advantages"]
    surrogate = -jnp.minimum(adv * ratio, adv * jnp.clip(ratio, 1 - clip_coef, 1 + clip_coef))
    mean = lambda x: jnp.sum(x * seq["mask"]) / loss_tokens  # noqa: E731
    policy, value, ent = mean(surrogate), mean(jnp.square(values - seq["returns"])), mean(-entropy)
    total = policy + m["vf_coef"] * value + m["ent_coef"] * ent
    return total, ({"policy": policy, "value": value, "entropy": ent}, routes)


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
        zeros = lambda: {k: np.zeros(v.shape, np.float32) for k, v in params.items()}  # noqa: E731
        return {"params": dict(params), "mu": zeros(), "nu": zeros(), "count": 0}

    def gradient(self, params: Params, batch: Dict[str, Any], clip_coef=None) -> Tuple[Params, Dict[str, jax.Array], list]:
        """The minibatch's gradient (before clipping by its norm), its three losses, and the routes [L, B, S, k];
        ``clip_coef`` is the surrogate's clip, the configuration's unless given (an operand: nothing compiles again)."""
        clip_coef = jnp.float32(self.m["clip_coef"] if clip_coef is None else clip_coef)
        loss_tokens = jnp.maximum(jnp.sum(jnp.asarray(batch["mask"], jnp.float32)), 1.0)
        grads, losses, routes = None, None, []
        for b in range(len(batch["tokens"])):
            seq = {k: jnp.asarray(v[b]) for k, v in batch.items()}
            (_, (seq_losses, seq_routes)), seq_grads = self._grad(params, seq, loss_tokens, clip_coef)
            grads = seq_grads if grads is None else _add(grads, seq_grads)
            losses = seq_losses if losses is None else {k: losses[k] + seq_losses[k] for k in losses}
            routes.append(np.stack([np.asarray(r) for r in seq_routes]) if seq_routes else np.zeros((0,), np.int32))
            del seq_grads
        return grads, losses, np.stack(routes, axis=1) if routes[0].size else None

    def clipped(self, grads: Params) -> Params:
        norm = _global_norm(grads)
        scale = jnp.minimum(1.0, self.m["optim"]["clip"] / jnp.maximum(norm, 1e-30))
        return {k: g * scale for k, g in grads.items()}

    def first_gradient(self, state: Dict[str, Any], batch: Dict[str, Any], clip_coef=None) -> Dict[str, np.ndarray]:
        """The gradient as the optimizer gets it (clipped by its norm), on the host; nothing updated."""
        return {k: np.asarray(g) for k, g in self.clipped(self.gradient(state["params"], batch, clip_coef)[0]).items()}

    def step(self, state: Dict[str, Any], batch: Dict[str, Any]) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        opt = self.m["optim"]
        grads, losses, routes = self.gradient(state["params"], batch)
        grads = self.clipped(grads)
        count = state["count"] + 1
        params, mu, nu = {}, {}, {}
        for k, g in grads.items():
            p, m_k, n_k = _adam_leaf(state["params"][k], g, state["mu"][k], state["nu"][k], float(count),
                                     lr=float(opt["lr"]), eps=float(opt["eps"]))
            params[k], mu[k], nu[k] = p, np.asarray(m_k), np.asarray(n_k)
        out = {"losses": losses, "grads": {k: np.asarray(g) for k, g in grads.items()}, "routes": routes}
        return {"params": params, "mu": mu, "nu": nu, "count": count}, out

    def logits(self, params: Params, tokens, start) -> np.ndarray:
        """The full forward pass's logits [R, V] at the response positions of one sequence."""
        return np.asarray(self._outputs(params, jnp.asarray(tokens), jnp.asarray(start))[0])

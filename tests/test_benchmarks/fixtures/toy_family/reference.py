"""Plain reference for one gradient step of the toy policy: a two-layer
network, the REINFORCE loss on given actions and returns, plain gradient
descent; the backward pass written out by hand. Imports nothing of the
program. ``precision``: ``"highest"``, or ``"bf16"`` for the control (every
operand and result of a matrix product and the activation rounded).

Weights: ``pi/l0/{w,b}``, ``pi/l1/{w,b}``.
"""

import jax
import jax.numpy as jnp
from jax import lax


class Reference:
    def __init__(self, model, precision="highest"):
        self.model = model
        self.precision = precision

    def _r(self, x):
        return x if self.precision == "highest" else lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def _dot(self, a, b):
        return self._r(jnp.dot(self._r(a), self._r(b), precision=lax.Precision.HIGHEST))

    def init(self, params):
        return {"params": {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}}

    def step(self, state, batch):
        p = state["params"]
        x, actions, returns = batch["obs"], batch["actions"], batch["returns"]
        rows = x.shape[0]
        h = self._r(jnp.tanh(self._dot(x, p["pi/l0/w"]) + p["pi/l0/b"]))
        logits = self._dot(h, p["pi/l1/w"]) + p["pi/l1/b"]
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
        chosen = jax.nn.one_hot(actions, logits.shape[-1])
        loss = -jnp.mean(returns * jnp.sum(chosen * logp, -1))
        d_logits = -(returns[:, None] / rows) * (chosen - jnp.exp(logp))
        d_h = self._dot(d_logits, p["pi/l1/w"].T)
        d_pre = d_h * (1.0 - h * h)
        grads = {
            "pi/l1/w": self._dot(h.T, d_logits), "pi/l1/b": jnp.sum(d_logits, 0),
            "pi/l0/w": self._dot(x.T, d_pre), "pi/l0/b": jnp.sum(d_pre, 0),
        }
        after = {"params": {k: v - self.model["lr"] * grads[k] for k, v in p.items()}}
        return after, {"losses": {"policy": loss}, "grads": grads}

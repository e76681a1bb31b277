"""Plain reference for one DreamerV3 gradient step (Hafner et al. 2023,
"Mastering Diverse Domains through World Models", arXiv:2301.04104), written
from the published description in straightforward float32 ``jax.numpy``.

It imports nothing of the program under test. It is handed, by the harness:
the model's sizes (a plain dict, from the configuration's own file), the
weights (made by the benchmark from the seed, as a flat ``{name: array}``
dict in THIS file's naming), the batch the step trained on, the step's
sampled noise as Gumbel arrays, and the target-critic coefficient ``tau``.

One step is: world-model loss and gradient -> clip -> Adam; imagination from
every posterior with the UPDATED world model; actor loss (REINFORCE with the
percentile-normalised lambda-return advantage, entropy bonus) -> clip -> Adam;
critic loss (two-hot log-likelihood of the lambda-returns plus the target
critic's regulariser) -> clip -> Adam; target critic EMA by ``tau``.

Naming of the weights (``l<i>`` = hidden layer i; ``w`` kernel, ``b`` bias,
``g``/``beta`` the layer norm's gain and bias):

- ``wm/enc_cnn/l<i>/{w,g,beta}``, ``wm/enc_mlp/l<i>/{w,g,beta}`` (if vector keys)
- ``wm/rec_in/l0/{w,g,beta}``, ``wm/gru/{w,g,beta}``, ``wm/h0``
- ``wm/prior/l0/{w,g,beta}``, ``wm/prior/out/{w,b}``; same for ``wm/post``
- ``wm/dec_fc/{w,b}``, ``wm/dec_cnn/l<i>/{w,g,beta}`` and the last ``/{w,b}``
- ``wm/dec_mlp/l<i>/...``, ``wm/dec_mlp/head<j>/{w,b}`` (if vector keys)
- ``wm/reward/l<i>/...``, ``wm/reward/out/{w,b}``; same for ``wm/cont``
- ``actor/l<i>/...``, ``actor/head0/{w,b}``; ``critic/l<i>/...``, ``critic/out/{w,b}``;
  ``target_critic/...`` as the critic.

Departures from the paper, made because the system under test makes them and
the comparison is of one step on the same numbers: the continue predictor's
imagined output is its mode (p > 0.5), not its mean; the layer norm inside the
GRU uses eps 1e-5 and the others 1e-3; the GRU's update gate carries a -1 bias.

``precision`` is ``"highest"`` (the reference proper) or a lower one for the
control, ``"bf16"`` or ``"fp8"``: everything the configuration computes in its
compute type is then rounded to the lower type instead: both operands and the
result of every matrix product and convolution, every activation, the logits
and the Gumbel sum a sample is taken from (fp8 with one scale per tensor).
The gradient passes a rounding straight through: a variant that rounded the
gradient on its way back too crashed the TPU compiler at the XL widths
(memory-space assignment, PR 24), and at S widths on the CPU read as this one.
Rounding is `lax.reduce_precision`, never a cast there and back: the TPU
compiler drops such a pair, and the control then equals the reference.
Layer-norm statistics, losses, returns and the optimizer stay float32, as the
configuration keeps them.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, jax.Array]
HI = lax.Precision.HIGHEST


# ------------------------------------------------------------------ primitives
def _round(x: jax.Array, precision: str) -> jax.Array:
    """``x`` rounded to the control's type, with the gradient passed straight
    through (the backward products then see the rounded operands too)."""
    if precision == "highest":
        return x
    if precision == "bf16":
        # not a cast there and back: the TPU compiler may drop such a pair
        # (excess precision is allowed), and the control then equals the reference
        rounded = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    else:
        # fp8 (4 exponent and 3 mantissa bits, largest finite 240) with one
        # scale per tensor, as a system that computes in fp8 would carry.
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
        rounded = lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    return x + lax.stop_gradient(rounded - x)


def symlog(x):
    return jnp.sign(x) * jnp.log1p(jnp.abs(x))


def symexp(x):
    return jnp.sign(x) * jnp.expm1(jnp.abs(x))


def layer_norm(x, g, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * g + beta


def silu(x):
    return x * jax.nn.sigmoid(x)


class Net:
    """The layer equations, over a flat dict of weights."""

    def __init__(self, model: Dict[str, Any], precision: str = "highest") -> None:
        self.m = model
        self.precision = precision
        self.eps = float(model.get("ln_eps", 1e-3))
        self.decode_dtype = jnp.float32

    # -- products
    def r(self, x):
        return _round(x, self.precision)

    def mm(self, x, w):
        return self.r(jnp.matmul(self.r(x), self.r(w), precision=HI))

    def conv(self, x, w):
        return self.r(lax.conv_general_dilated(
            self.r(x), self.r(w), (2, 2), [(1, 1), (1, 1)], dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        ))

    def deconv(self, x, w):
        # transposed convolution k4 s2 p1: dilate the input by the stride, pad by k-1-p
        return self.r(lax.conv_general_dilated(
            self.r(x), self.r(w), (1, 1), [(2, 2), (2, 2)], lhs_dilation=(2, 2),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        ))

    # -- blocks
    def hidden(self, p: Params, prefix: str, x, n_layers: int):
        for i in range(n_layers):
            q = f"{prefix}/l{i}"
            x = self.r(silu(layer_norm(self.mm(x, p[f"{q}/w"]), p[f"{q}/g"], p[f"{q}/beta"], self.eps)))
        return x

    def mlp_out(self, p: Params, prefix: str, x, n_layers: int, out: str = "out"):
        x = self.hidden(p, prefix, x, n_layers)
        return self.r(self.mm(x, p[f"{prefix}/{out}/w"]) + p[f"{prefix}/{out}/b"])

    def encode(self, p: Params, batch: Dict[str, jax.Array]):
        m = self.m
        outs = []
        if m["cnn_keys"]:
            x = jnp.concatenate([batch[k].astype(jnp.float32) / 255.0 - 0.5 for k in m["cnn_keys"]], -1)
            lead = x.shape[:-3]
            x = x.reshape((-1,) + x.shape[-3:])
            for i in range(m["cnn_stages"]):
                q = f"wm/enc_cnn/l{i}"
                x = self.r(silu(layer_norm(self.conv(x, p[f"{q}/w"]), p[f"{q}/g"], p[f"{q}/beta"], self.eps)))
            outs.append(x.reshape(lead + (-1,)))
        if m["mlp_keys"]:
            x = jnp.concatenate([symlog(batch[k].astype(jnp.float32)) for k in m["mlp_keys"]], -1)
            outs.append(self.hidden(p, "wm/enc_mlp", x, m["mlp_layers"]))
        return jnp.concatenate(outs, -1)

    def decode_cnn(self, p: Params, latent):
        m = self.m
        lead = latent.shape[:-1]
        x = self.r(self.mm(latent, p["wm/dec_fc/w"]) + p["wm/dec_fc/b"])
        x = x.reshape((-1, 4, 4, x.shape[-1] // 16))
        for i in range(m["cnn_stages"] - 1):
            q = f"wm/dec_cnn/l{i}"
            x = self.r(silu(layer_norm(self.deconv(x, p[f"{q}/w"]), p[f"{q}/g"], p[f"{q}/beta"], self.eps)))
        q = f"wm/dec_cnn/l{m['cnn_stages'] - 1}"
        x = self.r(self.deconv(x, p[f"{q}/w"]) + p[f"{q}/b"])
        return x.reshape(lead + x.shape[1:])

    def unimix(self, logits):
        m = self.m
        logits = logits.reshape(logits.shape[:-1] + (m["stoch"], m["discrete"]))
        probs = (1 - m["unimix"]) * jax.nn.softmax(logits, -1) + m["unimix"] / m["discrete"]
        return self.r(jnp.log(probs))  # [..., stoch, discrete], normalised

    def gru(self, p: Params, x, h):
        n = h.shape[-1]
        z = layer_norm(self.mm(jnp.concatenate([h, x], -1), p["wm/gru/w"]), p["wm/gru/g"], p["wm/gru/beta"], 1e-5)
        reset, cand, update = z[..., :n], z[..., n : 2 * n], z[..., 2 * n :]
        cand = jnp.tanh(jax.nn.sigmoid(reset) * cand)
        update = jax.nn.sigmoid(update - 1)
        return self.r(update * cand + (1 - update) * h)

    def recurrent(self, p: Params, z, a, h):
        return self.gru(p, self.hidden(p, "wm/rec_in", jnp.concatenate([z, a], -1), 1), h)

    def prior_logits(self, p: Params, h):
        return self.unimix(self.mlp_out(p, "wm/prior", h, 1))

    def post_logits(self, p: Params, h, emb):
        return self.unimix(self.mlp_out(p, "wm/post", jnp.concatenate([h, emb], -1), 1))

    def actor_logits(self, p: Params, latent):
        m = self.m
        logits = self.mlp_out(p, "actor", latent, m["mlp_layers"], out="head0")
        probs = (1 - m["unimix"]) * jax.nn.softmax(logits, -1) + m["unimix"] / logits.shape[-1]
        return self.r(jnp.log(probs))

    def sample(self, logits, gumbel):
        """The sample a Gumbel draw picks, as a one-hot, with the probabilities'
        gradient (straight-through)."""
        hard = jax.nn.one_hot(jnp.argmax(self.r(logits + gumbel), -1), logits.shape[-1], dtype=logits.dtype)
        probs = jax.nn.softmax(logits, -1)
        return hard + probs - lax.stop_gradient(probs)


def flat(z):
    return z.reshape(z.shape[:-2] + (-1,))


def twohot_logprob(logits, x, bins):
    """log-likelihood of the scalar ``x`` [..., 1] under two-hot bins in symlog space."""
    x = symlog(x)
    n = bins.shape[0]
    below = (bins <= x).astype(jnp.int32).sum(-1, keepdims=True) - 1
    above = jnp.minimum(below + 1, n - 1)
    below = jnp.maximum(below, 0)
    equal = below == above
    d_below = jnp.where(equal, 1.0, jnp.abs(bins[below] - x))
    d_above = jnp.where(equal, 1.0, jnp.abs(bins[above] - x))
    total = d_below + d_above
    target = (
        jax.nn.one_hot(below[..., 0], n) * (d_above / total) + jax.nn.one_hot(above[..., 0], n) * (d_below / total)
    )
    return (target * jax.nn.log_softmax(logits, -1)).sum(-1)


def twohot_mean(logits, bins, dtype=jnp.float32):
    """The mean of a two-hot head, decoded in ``dtype`` (float32 in the
    reference; a look at the program's bfloat16 decode passes bfloat16)."""
    logits, bins = logits.astype(dtype), bins.astype(dtype)
    return symexp((jax.nn.softmax(logits, -1) * bins).sum(-1, keepdims=True)).astype(jnp.float32)


def categorical_kl(p_logits, q_logits):
    probs = jnp.exp(p_logits)
    return (probs * (p_logits - q_logits)).sum(-1).sum(-1)


# ------------------------------------------------------------------ the losses
def world_model_loss(net: Net, p: Params, batch, gumbel_post):
    m = net.m
    sg = lax.stop_gradient
    T, B = batch["rewards"].shape[:2]
    emb = net.encode(p, batch)
    actions = jnp.concatenate([jnp.zeros_like(batch["actions"][:1]), batch["actions"][:-1]], 0)
    is_first = batch["is_first"].at[0].set(1.0)
    h_init = jnp.broadcast_to(jnp.tanh(p["wm/h0"]), (B, m["recurrent"]))
    z_prior0 = net.prior_logits(p, h_init)
    z_init = flat(jax.nn.one_hot(jnp.argmax(z_prior0, -1), m["discrete"]))

    def step(carry, x):
        h, z = carry
        a, e, first, g = x
        a = (1 - first) * a
        h = (1 - first) * h + first * h_init
        z = (1 - first) * z + first * z_init
        h = net.recurrent(p, z, a, h)
        prior = net.prior_logits(p, h)
        post = net.post_logits(p, h, e)
        z = flat(net.sample(post, g))
        return (h, z), (h, z, post, prior)

    zeros = (jnp.zeros((B, m["recurrent"])), jnp.zeros((B, m["stoch"] * m["discrete"])))
    _, (hs, zs, post, prior) = lax.scan(step, zeros, (actions, emb, is_first, gumbel_post))
    latent = jnp.concatenate([zs, hs], -1)

    obs_loss = 0.0
    if m["cnn_keys"]:
        recon = net.decode_cnn(p, latent)
        target = jnp.concatenate([batch[k].astype(jnp.float32) / 255.0 - 0.5 for k in m["cnn_keys"]], -1)
        obs_loss = obs_loss + ((recon - target) ** 2).sum((-3, -2, -1))
    for j, key in enumerate(m["mlp_decoder_keys"]):
        x = net.hidden(p, "wm/dec_mlp", latent, m["mlp_layers"])
        mean = net.mm(x, p[f"wm/dec_mlp/head{j}/w"]) + p[f"wm/dec_mlp/head{j}/b"]
        obs_loss = obs_loss + ((mean - symlog(batch[key])) ** 2).sum(-1)
    bins = jnp.linspace(-20.0, 20.0, m["bins"])
    reward_loss = -twohot_logprob(net.mlp_out(p, "wm/reward", latent, m["mlp_layers"]), batch["rewards"], bins)
    cont_logits = net.mlp_out(p, "wm/cont", latent, m["mlp_layers"])
    target = 1 - batch["terminated"]
    cont_loss = -(target * jax.nn.log_sigmoid(cont_logits) + (1 - target) * jax.nn.log_sigmoid(-cont_logits)).sum(-1)
    kl = categorical_kl(sg(post), prior)
    dyn = m["kl_dyn"] * jnp.maximum(kl, m["free_nats"])
    rep = m["kl_rep"] * jnp.maximum(categorical_kl(post, sg(prior)), m["free_nats"])
    loss = (dyn + rep + obs_loss + reward_loss + cont_loss).mean()
    parts = {"observation": jnp.mean(obs_loss), "reward": reward_loss.mean(), "continue": cont_loss.mean(),
             "state": (dyn + rep).mean(), "kl": kl.mean()}
    return loss, (sg(zs), sg(hs), sg(parts))


def lambda_returns(rewards, values, continues, lmbda):
    """L[t] = r[t] + c[t] ((1 - lambda) V[t] + lambda L[t+1]), L[T] = V[T-1]."""
    out = []
    nxt = values[-1]
    for t in reversed(range(rewards.shape[0])):
        nxt = rewards[t] + continues[t] * ((1 - lmbda) * values[t] + lmbda * nxt)
        out.append(nxt)
    return jnp.stack(out[::-1], 0)


def behaviour_loss(net: Net, actor: Params, wm: Params, critic: Params, moments, zs, hs, terminated, noise):
    """Imagination with the (updated) world model, and the actor's loss.
    Returns the loss and what the critic's loss needs."""
    m = net.m
    sg = lax.stop_gradient
    H = m["horizon"]
    z = zs.reshape(-1, zs.shape[-1])
    h = hs.reshape(-1, hs.shape[-1])
    latent0 = jnp.concatenate([z, h], -1)

    def act(latent, g):
        return net.sample(net.actor_logits(actor, sg(latent)), g)

    a0 = act(latent0, noise["actor"][0])

    def step(carry, g):
        z, h, a = carry
        h = net.recurrent(wm, z, a, h)
        z = flat(net.sample(net.prior_logits(wm, h), g["prior"]))
        latent = jnp.concatenate([z, h], -1)
        a = act(latent, g["actor"])
        return (z, h, a), (latent, a)

    _, (latents, acts) = lax.scan(step, (z, h, a0), {"prior": noise["img_prior"], "actor": noise["actor"][1:]})
    traj = jnp.concatenate([latent0[None], latents], 0)  # [H+1, TB, L]
    actions = jnp.concatenate([a0[None], acts], 0)
    bins = jnp.linspace(-20.0, 20.0, m["bins"])
    values = twohot_mean(net.mlp_out(critic, "critic", traj, m["mlp_layers"]), bins, net.decode_dtype)
    rewards = twohot_mean(net.mlp_out(wm, "wm/reward", traj, m["mlp_layers"]), bins, net.decode_dtype)
    cont = (jax.nn.sigmoid(net.mlp_out(wm, "wm/cont", traj, m["mlp_layers"])) > 0.5).astype(jnp.float32)
    cont = jnp.concatenate([(1 - terminated).reshape(1, -1, 1), cont[1:]], 0)
    lam = lambda_returns(rewards[1:], values[1:], cont[1:] * m["gamma"], m["lmbda"])
    discount = sg(jnp.cumprod(cont * m["gamma"], 0) / m["gamma"])
    mo = m["moments"]
    x = sg(lam)
    low = mo["decay"] * moments["low"] + (1 - mo["decay"]) * jnp.quantile(x, mo["low"])
    high = mo["decay"] * moments["high"] + (1 - mo["decay"]) * jnp.quantile(x, mo["high"])
    invscale = jnp.maximum(1.0 / mo["max"], high - low)
    advantage = (lam - low) / invscale - (values[:-1] - low) / invscale
    logits = net.actor_logits(actor, sg(traj))
    logp = (sg(actions) * logits).sum(-1, keepdims=True)[:-1]
    entropy = -(jnp.exp(logits) * logits).sum(-1)
    objective = logp * sg(advantage) + (m["ent_coef"] * entropy)[..., None][:-1]
    loss = -jnp.mean(discount[:-1] * objective)
    return loss, (sg(traj), sg(lam), discount, {"low": low, "high": high})


def critic_loss(net: Net, critic: Params, target: Params, traj, lam, discount):
    m = net.m
    bins = jnp.linspace(-20.0, 20.0, m["bins"])
    target_values = twohot_mean(
        net.mlp_out(target, "target_critic", traj[:-1], m["mlp_layers"]), bins, net.decode_dtype
    )
    logits = net.mlp_out(critic, "critic", traj[:-1], m["mlp_layers"])
    loss = -twohot_logprob(logits, lam, bins) - twohot_logprob(logits, lax.stop_gradient(target_values), bins)
    return jnp.mean(loss * discount[:-1][..., 0])


# ------------------------------------------------------------------ the optimiser
def clip_by_global_norm(grads: Params, max_norm: float) -> Params:
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return {k: g * scale for k, g in grads.items()}


def adam_init(params: Params) -> Dict[str, Any]:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"count": jnp.zeros((), jnp.int32), "mu": zeros, "nu": dict(zeros)}


def adam_update(params: Params, grads: Params, opt, lr: float, eps: float, b1: float = 0.9, b2: float = 0.999):
    count = opt["count"] + 1
    mu = {k: b1 * opt["mu"][k] + (1 - b1) * grads[k] for k in params}
    nu = {k: b2 * opt["nu"][k] + (1 - b2) * grads[k] ** 2 for k in params}
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)
    new = {k: params[k] - lr * (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + eps) for k in params}
    return new, {"count": count, "mu": mu, "nu": nu}


def split(params: Params, prefix: str) -> Params:
    return {k: v for k, v in params.items() if k.startswith(prefix + "/")}


class Reference:
    """Three steps of the recipe on the benchmark's weights; each of the three
    gradients is its own jitted block, so the widest configuration fits."""

    def __init__(self, model: Dict[str, Any], precision: str = "highest") -> None:
        self.net = Net(model, precision)
        self.model = model
        net = self.net

        @jax.jit
        def wm_block(wm, opt, batch, gumbel_post):
            (loss, (zs, hs, parts)), grads = jax.value_and_grad(
                partial(world_model_loss, net), argnums=0, has_aux=True
            )(wm, batch, gumbel_post)
            o = model["optim"]["world_model"]
            grads = clip_by_global_norm(grads, o["clip"])
            wm, opt = adam_update(wm, grads, opt, o["lr"], o["eps"])
            return wm, opt, loss, parts, grads, zs, hs

        @jax.jit
        def actor_block(actor, opt, wm, critic, moments, zs, hs, terminated, noise):
            (loss, aux), grads = jax.value_and_grad(partial(behaviour_loss, net), argnums=0, has_aux=True)(
                actor, wm, critic, moments, zs, hs, terminated, noise
            )
            o = model["optim"]["actor"]
            grads = clip_by_global_norm(grads, o["clip"])
            actor, opt = adam_update(actor, grads, opt, o["lr"], o["eps"])
            return actor, opt, loss, grads, aux

        @jax.jit
        def critic_block(critic, opt, target, traj, lam, discount, tau):
            loss, grads = jax.value_and_grad(partial(critic_loss, net))(critic, target, traj, lam, discount)
            o = model["optim"]["critic"]
            grads = clip_by_global_norm(grads, o["clip"])
            critic, opt = adam_update(critic, grads, opt, o["lr"], o["eps"])
            new_target = {
                k: tau * critic["critic/" + k.split("/", 1)[1]] + (1 - tau) * v for k, v in target.items()
            }
            return critic, opt, new_target, loss, grads

        self._wm_block, self._actor_block, self._critic_block = wm_block, actor_block, critic_block
        self._recurrent = jax.jit(net.recurrent)

    def player_recurrent(self, params: Params, z, a, h) -> jax.Array:
        """The acting step's recurrent state from the previous stochastic
        state, action and recurrent state (one env step, no sampling in it)."""
        return self._recurrent(params, z, a, h)

    def world_model_gradient(self, state: Dict[str, Any], batch, noise) -> Params:
        """The world model's gradient as the optimizer gets it, at ``state``."""
        return self._wm_block(split(state["params"], "wm"), state["opt"]["wm"], batch, noise["post"])[4]

    def init(self, params: Params) -> Dict[str, Any]:
        return {
            "params": dict(params),
            "opt": {name: adam_init(split(params, name)) for name in ("wm", "actor", "critic")},
            "moments": {"low": jnp.zeros(()), "high": jnp.zeros(())},
        }

    def step(self, state: Dict[str, Any], batch, noise, tau) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        params, opt = state["params"], state["opt"]
        wm, opt_wm, wm_loss, parts, wm_grads, zs, hs = self._wm_block(
            split(params, "wm"), opt["wm"], batch, noise["post"]
        )
        actor, opt_actor, policy_loss, actor_grads, (traj, lam, discount, moments) = self._actor_block(
            split(params, "actor"), opt["actor"], wm, split(params, "critic"), state["moments"],
            zs, hs, batch["terminated"], {"actor": noise["actor"], "img_prior": noise["img_prior"]},
        )
        critic, opt_critic, target, value_loss, critic_grads = self._critic_block(
            split(params, "critic"), opt["critic"], split(params, "target_critic"), traj, lam, discount,
            jnp.asarray(tau, jnp.float32),
        )
        new = {
            "params": {**wm, **actor, **critic, **target},
            "opt": {"wm": opt_wm, "actor": opt_actor, "critic": opt_critic},
            "moments": moments,
        }
        out = {
            "losses": {"world_model": wm_loss, "policy": policy_loss, "value": value_loss, **parts},
            "grads": {**wm_grads, **actor_grads, **critic_grads},
        }
        return new, out

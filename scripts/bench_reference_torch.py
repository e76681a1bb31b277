"""Same-host torch measurement of the reference's CPU benchmark workloads.

The reference's published CPU numbers (README.md:100-140: PPO 65,536 steps in
81.27 s, A2C in 84.76 s, SAC in 320.21 s) were taken on a 4-vCPU box; ours
run on this 1-core host, so cross-host ratios conflate hardware with
framework. This harness re-measures the torch side ON THIS HOST: the same
three benchmark workloads (sheeprl/configs/exp/{ppo,a2c,sac}_benchmarks.yaml
— same envs, model shapes, batch/rollout sizes, optimizers, update cadence)
implemented in plain torch (lightning/hydra are not installed here, so the
reference cannot run verbatim; this is a from-scratch reimplementation of
its per-step work, not its code). The result is an apples-to-apples
same-host column next to bench.py's JAX numbers (BENCH_TORCH_SAMEHOST.jsonl).

Workload fidelity notes (semantics from the reference, cited per workload):
- PPO  (ppo_benchmarks.yaml): CartPole-v1, 1 sync env, Tanh MLP encoder
  64x2 -> linear actor/critic heads (actor/critic mlp_layers=0), GAE(0.99,
  0.95), 10 epochs x minibatch 64 over 128-step rollouts, Adam 3e-4,
  normalize_advantages, vf_coef 0.5, grad-clip 0.5, 65,536 steps.
- A2C  (a2c_benchmarks.yaml): CartPole-v1, 1 env, rollout 5, batch 5,
  RMSprop(lr 7e-4, alpha 0.99, eps 1e-5), mean loss reduction, vf_coef 1.0,
  grad-clip 0.5, 65,536 steps.
- SAC  (sac_benchmarks.yaml + algos/sac/sac.py:222-355): LunarLanderContinuous
  (v3 here; v2 is removed from this gymnasium), 4 sync envs, hidden 256,
  twin Q + EMA targets (tau 0.005, every update), auto-alpha, replay_ratio
  1.0 via the Ratio scheduler (sample once per iter at
  grad_steps*batch_size, then chunked updates), Adam 3e-4, learning_starts
  100, batch 256, 65,536 steps.

Usage: python scripts/bench_reference_torch.py [ppo|a2c|sac|all]
Prints one JSON line per workload:
  {"metric": ..., "value": <env-steps/s>, "unit": "env-steps/sec",
   "harness": "torch-same-host", "wall_seconds": ...}
"""

from __future__ import annotations

import contextlib
import json
import math
import sys
import time

import gymnasium as gym
import numpy as np
import torch
import torch.nn as nn

torch.set_num_threads(1)  # the host has one core; oversubscription only slows it

TOTAL_STEPS = 65536


# --------------------------------------------------------------- PPO / A2C
class ActorCritic(nn.Module):
    """Tanh-MLP encoder (dense_units x mlp_layers) with linear actor/critic
    heads — the benchmark shape (encoder.mlp_features_dim=null,
    actor/critic mlp_layers=0)."""

    def __init__(self, obs_dim: int, n_actions: int, dense_units: int = 64, mlp_layers: int = 2):
        super().__init__()
        layers, d = [], obs_dim
        for _ in range(mlp_layers):
            layers += [nn.Linear(d, dense_units), nn.Tanh()]
            d = dense_units
        self.encoder = nn.Sequential(*layers)
        self.actor = nn.Linear(d, n_actions)
        self.critic = nn.Linear(d, 1)

    def forward(self, obs: torch.Tensor):
        feats = self.encoder(obs)
        return self.actor(feats), self.critic(feats)


def _gae(rewards, values, dones, next_value, gamma=0.99, lmbda=0.95):
    T = rewards.shape[0]
    advantages = torch.zeros_like(rewards)
    last_adv = 0.0
    for t in reversed(range(T)):
        next_v = next_value if t == T - 1 else values[t + 1]
        not_done = 1.0 - dones[t]
        delta = rewards[t] + gamma * next_v * not_done - values[t]
        last_adv = delta + gamma * lmbda * not_done * last_adv
        advantages[t] = last_adv
    return advantages, advantages + values


def _rollout_policy_phase(env, model, obs, steps):
    """Shared on-policy collection: sample actions, step, stack tensors."""
    obs_buf, act_buf, logp_buf, val_buf, rew_buf, done_buf = [], [], [], [], [], []
    for _ in range(steps):
        with torch.no_grad():
            logits, value = model(obs)
            dist = torch.distributions.Categorical(logits=logits)
            action = dist.sample()
            logp = dist.log_prob(action)
        nobs, reward, term, trunc, _ = env.step(int(action.item()))
        obs_buf.append(obs)
        act_buf.append(action)
        logp_buf.append(logp)
        val_buf.append(value.squeeze(-1))
        rew_buf.append(torch.as_tensor([float(reward)]))
        done = term or trunc
        done_buf.append(torch.as_tensor([float(done)]))
        if done:
            nobs, _ = env.reset()
        obs = torch.as_tensor(nobs, dtype=torch.float32).unsqueeze(0)
    with torch.no_grad():
        _, next_value = model(obs)
    return (
        obs,
        torch.cat(obs_buf),
        torch.cat(act_buf),
        torch.cat(logp_buf),
        torch.stack(val_buf),
        torch.stack(rew_buf),
        torch.stack(done_buf),
        next_value.squeeze(-1),
    )


def bench_ppo():
    env = gym.make("CartPole-v1")
    obs, _ = env.reset(seed=42)
    obs = torch.as_tensor(obs, dtype=torch.float32).unsqueeze(0)
    model = ActorCritic(env.observation_space.shape[0], env.action_space.n)
    opt = torch.optim.Adam(model.parameters(), lr=3e-4, eps=1e-5)
    rollout, batch, epochs = 128, 64, 10

    t0 = time.perf_counter()
    for _ in range(TOTAL_STEPS // rollout):
        obs, b_obs, b_act, b_logp, values, rewards, dones, next_value = _rollout_policy_phase(
            env, model, obs, rollout
        )
        adv, returns = _gae(rewards, values, dones, next_value)
        adv, returns = adv.reshape(-1), returns.reshape(-1)
        for _ in range(epochs):
            perm = torch.randperm(rollout)
            for start in range(0, rollout, batch):
                idx = perm[start : start + batch]
                logits, value = model(b_obs[idx])
                dist = torch.distributions.Categorical(logits=logits)
                new_logp = dist.log_prob(b_act[idx])
                ratio = torch.exp(new_logp - b_logp[idx])
                mb_adv = adv[idx]
                mb_adv = (mb_adv - mb_adv.mean()) / (mb_adv.std() + 1e-8)
                pg = -torch.min(
                    ratio * mb_adv, torch.clamp(ratio, 0.8, 1.2) * mb_adv
                ).mean()
                v_loss = 0.5 * (value.squeeze(-1) - returns[idx]).pow(2).mean()
                loss = pg + 0.5 * v_loss
                opt.zero_grad(set_to_none=True)
                loss.backward()
                nn.utils.clip_grad_norm_(model.parameters(), 0.5)
                opt.step()
    wall = time.perf_counter() - t0
    env.close()
    return {"metric": "ppo_cartpole_env_steps_per_sec", "value": round(TOTAL_STEPS / wall, 2),
            "unit": "env-steps/sec", "harness": "torch-same-host", "wall_seconds": round(wall, 1)}


def bench_a2c():
    env = gym.make("CartPole-v1")
    obs, _ = env.reset(seed=42)
    obs = torch.as_tensor(obs, dtype=torch.float32).unsqueeze(0)
    model = ActorCritic(env.observation_space.shape[0], env.action_space.n)
    opt = torch.optim.RMSprop(model.parameters(), lr=7e-4, alpha=0.99, eps=1e-5)
    rollout = 5

    t0 = time.perf_counter()
    for _ in range(TOTAL_STEPS // rollout):
        obs, b_obs, b_act, _b_logp, values, rewards, dones, next_value = _rollout_policy_phase(
            env, model, obs, rollout
        )
        adv, returns = _gae(rewards, values, dones, next_value)
        logits, value = model(b_obs)
        dist = torch.distributions.Categorical(logits=logits)
        pg = -(dist.log_prob(b_act) * adv.reshape(-1).detach()).mean()
        v_loss = (value.squeeze(-1) - returns.reshape(-1).detach()).pow(2).mean()
        loss = pg + v_loss
        opt.zero_grad(set_to_none=True)
        loss.backward()
        nn.utils.clip_grad_norm_(model.parameters(), 0.5)
        opt.step()
    wall = time.perf_counter() - t0
    env.close()
    return {"metric": "a2c_cartpole_env_steps_per_sec", "value": round(TOTAL_STEPS / wall, 2),
            "unit": "env-steps/sec", "harness": "torch-same-host", "wall_seconds": round(wall, 1)}


# --------------------------------------------------------------------- SAC
class SACActor(nn.Module):
    def __init__(self, obs_dim, act_dim, hidden=256):
        super().__init__()
        self.net = nn.Sequential(
            nn.Linear(obs_dim, hidden), nn.ReLU(), nn.Linear(hidden, hidden), nn.ReLU()
        )
        self.mean = nn.Linear(hidden, act_dim)
        self.log_std = nn.Linear(hidden, act_dim)

    def forward(self, obs):
        h = self.net(obs)
        mean, log_std = self.mean(h), torch.clamp(self.log_std(h), -5, 2)
        std = torch.exp(log_std)
        normal = torch.distributions.Normal(mean, std)
        x = normal.rsample()
        action = torch.tanh(x)
        logp = (normal.log_prob(x) - torch.log(1 - action.pow(2) + 1e-6)).sum(-1, keepdim=True)
        return action, logp


class SACCritic(nn.Module):
    def __init__(self, obs_dim, act_dim, hidden=256):
        super().__init__()
        self.net = nn.Sequential(
            nn.Linear(obs_dim + act_dim, hidden), nn.ReLU(),
            nn.Linear(hidden, hidden), nn.ReLU(), nn.Linear(hidden, 1),
        )

    def forward(self, obs, act):
        return self.net(torch.cat([obs, act], -1))


def bench_sac():
    num_envs, batch, hidden, learning_starts = 4, 256, 256, 100
    env = gym.vector.SyncVectorEnv(
        [lambda: gym.make("LunarLanderContinuous-v3") for _ in range(num_envs)]
    )
    obs_dim = env.single_observation_space.shape[0]
    act_dim = env.single_action_space.shape[0]
    actor = SACActor(obs_dim, act_dim, hidden)
    q1, q2 = SACCritic(obs_dim, act_dim, hidden), SACCritic(obs_dim, act_dim, hidden)
    q1_t, q2_t = SACCritic(obs_dim, act_dim, hidden), SACCritic(obs_dim, act_dim, hidden)
    q1_t.load_state_dict(q1.state_dict())
    q2_t.load_state_dict(q2.state_dict())
    log_alpha = torch.zeros(1, requires_grad=True)
    target_entropy = -float(act_dim)
    actor_opt = torch.optim.Adam(actor.parameters(), lr=3e-4, eps=1e-5)
    q_opt = torch.optim.Adam(list(q1.parameters()) + list(q2.parameters()), lr=3e-4, eps=1e-5)
    alpha_opt = torch.optim.Adam([log_alpha], lr=3e-4, eps=1e-5)
    gamma, tau = 0.99, 0.005

    cap = TOTAL_STEPS + 1
    buf_obs = np.zeros((cap, obs_dim), np.float32)
    buf_nobs = np.zeros((cap, obs_dim), np.float32)
    buf_act = np.zeros((cap, act_dim), np.float32)
    buf_rew = np.zeros((cap, 1), np.float32)
    buf_term = np.zeros((cap, 1), np.float32)
    size = 0

    obs, _ = env.reset(seed=42)
    grad_debt = 0.0  # the Ratio scheduler: replay_ratio 1.0
    t0 = time.perf_counter()
    step = 0
    while step < TOTAL_STEPS:
        if step < learning_starts:
            actions = env.action_space.sample()
        else:
            with torch.no_grad():
                actions, _ = actor(torch.as_tensor(obs, dtype=torch.float32))
            actions = actions.numpy()
        nobs, rewards, terms, truncs, _ = env.step(actions)
        for i in range(num_envs):
            j = (size + i) % cap
            buf_obs[j], buf_nobs[j], buf_act[j] = obs[i], nobs[i], actions[i]
            buf_rew[j, 0], buf_term[j, 0] = rewards[i], float(terms[i])
        size = min(size + num_envs, cap)
        obs = nobs
        step += num_envs

        if step >= learning_starts:
            grad_debt += num_envs  # replay_ratio 1.0: one grad step per policy step
            grad_steps = int(grad_debt)
            grad_debt -= grad_steps
            if grad_steps > 0:
                idx = np.random.randint(0, size, grad_steps * batch)
                g_obs = torch.as_tensor(buf_obs[idx])
                g_nobs = torch.as_tensor(buf_nobs[idx])
                g_act = torch.as_tensor(buf_act[idx])
                g_rew = torch.as_tensor(buf_rew[idx])
                g_term = torch.as_tensor(buf_term[idx])
                for k in range(grad_steps):
                    sl = slice(k * batch, (k + 1) * batch)
                    o, no, a, r, d = g_obs[sl], g_nobs[sl], g_act[sl], g_rew[sl], g_term[sl]
                    alpha = log_alpha.exp().detach()
                    with torch.no_grad():
                        na, nlogp = actor(no)
                        tq = torch.min(q1_t(no, na), q2_t(no, na)) - alpha * nlogp
                        target = r + (1 - d) * gamma * tq
                    q_loss = (q1(o, a) - target).pow(2).mean() + (q2(o, a) - target).pow(2).mean()
                    q_opt.zero_grad(set_to_none=True)
                    q_loss.backward()
                    q_opt.step()
                    with torch.no_grad():
                        for t_p, p in zip(q1_t.parameters(), q1.parameters()):
                            t_p.mul_(1 - tau).add_(tau * p)
                        for t_p, p in zip(q2_t.parameters(), q2.parameters()):
                            t_p.mul_(1 - tau).add_(tau * p)
                    pa, plogp = actor(o)
                    pq = torch.min(q1(o, pa), q2(o, pa))
                    a_loss = (alpha * plogp - pq).mean()
                    actor_opt.zero_grad(set_to_none=True)
                    a_loss.backward()
                    actor_opt.step()
                    al_loss = (-log_alpha.exp() * (plogp.detach() + target_entropy)).mean()
                    alpha_opt.zero_grad(set_to_none=True)
                    al_loss.backward()
                    alpha_opt.step()
    wall = time.perf_counter() - t0
    env.close()
    return {"metric": "sac_env_steps_per_sec", "value": round(TOTAL_STEPS / wall, 2),
            "unit": "env-steps/sec", "harness": "torch-same-host", "wall_seconds": round(wall, 1)}


# ---------------------------------------------------------------- Dreamer
# Same-host torch measurement of the reference's Dreamer benchmark
# workloads (sheeprl/configs/exp/dreamer_v{1,2,3}_benchmarks.yaml): 16,384
# env steps from a 64x64x3 pixel env, micro world model
# (cnn_channels_multiplier 2, recurrent/dense size 8, stochastic 4 [x4
# discrete for v2/v3]), replay_ratio 0.0625 (one grad step per 16 policy
# steps), learning_starts 1024, batch x sequence = 50x50 (v1) / 16x50 (v2)
# / 16x64 (v3), imagination horizon 15. The env is the same deterministic
# dummy pixel env bench.py uses (ALE absent; documented divergence there).
# Per-step WORK is the reference's: conv encode of B*T frames, LN-GRU RSSM
# scan over T, pixel reconstruction, KL (balanced for v2/v3, with free
# nats/bits), reward/continue heads, then an imagined rollout of horizon
# 15 from every posterior state driving actor/critic updates (dynamics
# backprop for v1; REINFORCE + target/EMA critic for v2/v3; symlog +
# two-hot 255-bin heads and 1% unimix for v3). Optimizer lrs don't affect
# throughput; shapes, scan lengths and head widths do, and those match.

class _LNGRUCell(nn.Module):
    """LayerNorm GRU cell (the reference's LayerNormGRUCell,
    sheeprl/models/models.py): one fused input+recurrent linear, LN over
    the stacked gates."""

    def __init__(self, input_size: int, hidden_size: int):
        super().__init__()
        self.linear = nn.Linear(input_size + hidden_size, 3 * hidden_size, bias=False)
        self.ln = nn.LayerNorm(3 * hidden_size)
        self.hidden_size = hidden_size

    def forward(self, x, h):
        gates = self.ln(self.linear(torch.cat([x, h], -1)))
        reset, cand, update = gates.chunk(3, -1)
        reset = torch.sigmoid(reset)
        cand = torch.tanh(reset * cand)
        update = torch.sigmoid(update - 1)
        return update * cand + (1 - update) * h


class _ConvEncoder(nn.Module):
    """4 stages k4/s2/p1: 64->32->16->8->4, channels mult*(1,2,4,8)."""

    def __init__(self, mult: int = 2, act=nn.SiLU):
        super().__init__()
        chans = [3] + [mult * (2 ** i) for i in range(4)]
        self.net = nn.Sequential(*[
            m for i in range(4)
            for m in (nn.Conv2d(chans[i], chans[i + 1], 4, 2, 1), act())
        ])
        self.out_dim = chans[-1] * 4 * 4

    def forward(self, x):  # (N, 3, 64, 64) -> (N, out_dim)
        return self.net(x).flatten(1)


class _ConvDecoder(nn.Module):
    """Latent -> dense -> 4 transposed stages back to (3, 64, 64)."""

    def __init__(self, in_dim: int, mult: int = 2, act=nn.SiLU):
        super().__init__()
        c0 = mult * 8
        self.fc = nn.Linear(in_dim, c0 * 4 * 4)
        chans = [c0, mult * 4, mult * 2, mult, 3]
        mods = []
        for i in range(4):
            mods.append(nn.ConvTranspose2d(chans[i], chans[i + 1], 4, 2, 1))
            if i < 3:
                mods.append(act())
        self.net = nn.Sequential(*mods)
        self.c0 = c0

    def forward(self, z):
        return self.net(self.fc(z).view(-1, self.c0, 4, 4))


def _mlp(in_dim, out_dim, hidden=8, layers=1, act=nn.SiLU):
    mods, d = [], in_dim
    for _ in range(layers):
        mods += [nn.Linear(d, hidden), act()]
        d = hidden
    mods.append(nn.Linear(d, out_dim))
    return nn.Sequential(*mods)


def _symlog(x):
    return torch.sign(x) * torch.log1p(torch.abs(x))


def _two_hot_loss(logits, target_symlog, bins):
    """Cross-entropy against the two-hot encoding of the (symlog) target —
    the v3 reward/value head objective at its real 255-bin width."""
    lo, hi = -20.0, 20.0
    idx = (target_symlog.clamp(lo, hi) - lo) / (hi - lo) * (bins - 1)
    low = idx.floor().long().clamp(0, bins - 1)
    high = (low + 1).clamp(0, bins - 1)
    w_high = idx - low.float()
    target = torch.zeros_like(logits)
    target.scatter_(-1, low.unsqueeze(-1), (1 - w_high).unsqueeze(-1))
    target.scatter_add_(-1, high.unsqueeze(-1), w_high.unsqueeze(-1))
    return -(target * torch.log_softmax(logits, -1)).sum(-1)


class _TorchDreamer:
    """One micro Dreamer (version-parametrized) with the reference
    benchmark's per-step work. Not a learner to admire — a cost model to
    measure: every tensor it touches has the benchmark shape."""

    def __init__(self, version: int, n_actions: int = 2, mult: int = 2,
                 hidden: int = 8, stoch: int = 4, discrete: int = 4,
                 bins: int = 255, horizon: int = 15):
        act = {1: nn.ReLU, 2: nn.ELU, 3: nn.SiLU}[version]
        self.version = version
        self.n_actions = n_actions
        self.horizon = horizon
        self.bins = bins
        self.stoch = stoch
        self.discrete = discrete if version >= 2 else 0
        self.stoch_dim = stoch * discrete if version >= 2 else stoch
        feat = hidden + self.stoch_dim  # h ++ z
        self.encoder = _ConvEncoder(mult, act)
        self.decoder = _ConvDecoder(feat, mult, act)
        self.gru = _LNGRUCell(hidden, hidden)
        self.gru_in = _mlp(self.stoch_dim + n_actions, hidden, hidden, 1, act)
        rep_out = stoch * discrete if version >= 2 else 2 * stoch
        self.representation = _mlp(self.encoder.out_dim + hidden, rep_out, hidden, 1, act)
        self.transition = _mlp(hidden, rep_out, hidden, 1, act)
        self.reward = _mlp(feat, bins if version == 3 else 1, hidden, 1, act)
        self.value = _mlp(feat, bins if version == 3 else 1, hidden, 1, act)
        self.actor = _mlp(feat, n_actions, hidden, 1, act)
        self.continue_head = _mlp(feat, 1, hidden, 1, act) if version >= 2 else None
        if version >= 2:
            import copy

            self.target_value = copy.deepcopy(self.value)
        wm_params = [
            *self.encoder.parameters(), *self.decoder.parameters(),
            *self.gru.parameters(), *self.gru_in.parameters(),
            *self.representation.parameters(), *self.transition.parameters(),
            *self.reward.parameters(),
            *(self.continue_head.parameters() if self.continue_head else []),
        ]
        self.wm_opt = torch.optim.Adam(wm_params, lr=3e-4, eps=1e-8)
        self.actor_opt = torch.optim.Adam(self.actor.parameters(), lr=8e-5, eps=1e-8)
        self.value_opt = torch.optim.Adam(self.value.parameters(), lr=8e-5, eps=1e-8)
        self._wm_params, self._return_scale = wm_params, 1.0

    # ------------------------------------------------------------- latents
    def _post_sample(self, logits_or_stats):
        if self.version >= 2:
            logits = logits_or_stats.view(*logits_or_stats.shape[:-1], self.stoch, self.discrete)
            if self.version == 3:  # 1% unimix
                probs = 0.99 * torch.softmax(logits, -1) + 0.01 / self.discrete
                logits = probs.log()
            dist = torch.distributions.OneHotCategoricalStraightThrough(logits=logits)
            return dist.rsample().flatten(-2), logits
        mean, std = logits_or_stats.chunk(2, -1)
        std = torch.nn.functional.softplus(std) + 0.1
        return mean + std * torch.randn_like(std), (mean, std)

    def _kl(self, post_stats, prior_stats):
        if self.version >= 2:
            post = torch.distributions.Categorical(logits=post_stats)
            prior = torch.distributions.Categorical(logits=prior_stats)
            post_sg = torch.distributions.Categorical(logits=post_stats.detach())
            prior_sg = torch.distributions.Categorical(logits=prior_stats.detach())
            # KL balancing (v2: 0.8/0.2; v3: 0.5/0.1 with free bits 1.0)
            lhs = torch.distributions.kl_divergence(post_sg, prior).sum(-1)
            rhs = torch.distributions.kl_divergence(post, prior_sg).sum(-1)
            if self.version == 3:
                return 0.5 * lhs.clamp(min=1.0) + 0.1 * rhs.clamp(min=1.0)
            return 0.8 * lhs + 0.2 * rhs
        pm, ps = post_stats
        rm, rs = prior_stats
        post = torch.distributions.Normal(pm, ps)
        prior = torch.distributions.Normal(rm, rs)
        return torch.distributions.kl_divergence(post, prior).sum(-1).clamp(min=3.0)

    # --------------------------------------------------------------- phases
    def policy_step(self, frame_u8, h, z):
        with torch.no_grad():
            embed = self.encoder(frame_u8.float().div_(255.0))
            h = self.gru(self.gru_in(torch.cat([z, torch.zeros(1, self.n_actions)], -1)), h)
            z, _ = self._post_sample(self.representation(torch.cat([embed, h], -1)))
            logits = self.actor(torch.cat([h, z], -1))
            return int(torch.distributions.Categorical(logits=logits).sample()), h, z

    def train_step(self, frames_u8, actions, rewards, dones):
        B, T = frames_u8.shape[:2]
        obs = frames_u8.float().div(255.0).flatten(0, 1)
        embed = self.encoder(obs).view(B, T, -1)
        onehot = torch.nn.functional.one_hot(actions, self.n_actions).float()
        h = torch.zeros(B, self.gru.hidden_size)
        z = torch.zeros(B, self.stoch_dim)
        feats, kls = [], []
        for t in range(T):  # the RSSM scan (eager loop, as the reference runs it)
            h = self.gru(self.gru_in(torch.cat([z, onehot[:, t]], -1)), h)
            prior_stats_raw = self.transition(h)
            post_raw = self.representation(torch.cat([embed[:, t], h], -1))
            z, post_stats = self._post_sample(post_raw)
            if self.version >= 2:
                prior_stats = prior_stats_raw.view(B, self.stoch, self.discrete)
                post_for_kl = post_raw.view(B, self.stoch, self.discrete)
                kls.append(self._kl(post_for_kl, prior_stats))
            else:
                _, prior_stats = self._post_sample(prior_stats_raw)
                kls.append(self._kl(post_stats, prior_stats))
            feats.append(torch.cat([h, z], -1))
        feat = torch.stack(feats, 1)  # (B, T, feat)

        recon = self.decoder(feat.flatten(0, 1))
        target_pix = _symlog(obs) if self.version == 3 else obs - 0.5
        recon_loss = 0.5 * (recon - target_pix).pow(2).sum((1, 2, 3)).view(B, T)
        if self.version == 3:
            rew_loss = _two_hot_loss(self.reward(feat), _symlog(rewards), self.bins)
        else:
            rew_loss = 0.5 * (self.reward(feat).squeeze(-1) - rewards).pow(2)
        kl_loss = torch.stack(kls, 1)
        loss = (recon_loss + rew_loss + kl_loss).mean()
        if self.continue_head is not None:
            cont_logits = self.continue_head(feat).squeeze(-1)
            loss = loss + nn.functional.binary_cross_entropy_with_logits(cont_logits, 1 - dones)
        self.wm_opt.zero_grad(set_to_none=True)
        loss.backward()
        nn.utils.clip_grad_norm_(self._wm_params, 100.0)
        self.wm_opt.step()

        # ------------------------------------------------ imagined rollout
        start_h = feat[..., : self.gru.hidden_size].detach().flatten(0, 1)
        start_z = feat[..., self.gru.hidden_size:].detach().flatten(0, 1)
        v1 = self.version == 1
        im_feats, im_logps, im_ents = [], [], []
        h, z = start_h, start_z
        for _ in range(self.horizon):
            f = torch.cat([h, z], -1)
            # v1 backprops through the dynamics (the whole point of its
            # actor objective); v2/v3 are REINFORCE — actor forward stays
            # in-graph, the imagined transition does not.
            logits = self.actor(f if v1 else f.detach())
            dist = torch.distributions.Categorical(logits=logits)
            a = dist.sample()
            a_oh = torch.nn.functional.one_hot(a, self.n_actions).float()
            if v1:  # dynamics backprop: straight-through action
                probs = torch.softmax(logits, -1)
                a_oh = a_oh + probs - probs.detach()
            dyn_ctx = contextlib.nullcontext() if v1 else torch.no_grad()
            with dyn_ctx:
                h = self.gru(self.gru_in(torch.cat([z, a_oh], -1)), h)
                z, _ = self._post_sample(self.transition(h))
            im_feats.append(torch.cat([h, z], -1))
            im_logps.append(dist.log_prob(a))
            im_ents.append(dist.entropy())
        im_feat = torch.stack(im_feats, 0)  # (H, B*T, feat)

        if self.version == 3:
            centers = torch.linspace(-20.0, 20.0, self.bins)
            rew = torch.sinh((torch.softmax(self.reward(im_feat), -1) * centers).sum(-1))
            val = torch.sinh((torch.softmax(self.value(im_feat), -1) * centers).sum(-1))
            with torch.no_grad():
                tval = torch.sinh((torch.softmax(self.target_value(im_feat), -1) * centers).sum(-1))
        else:
            rew = self.reward(im_feat).squeeze(-1)
            val = self.value(im_feat).squeeze(-1)
            tval = (self.target_value(im_feat).squeeze(-1)
                    if self.version == 2 else val).detach()
        # lambda-returns over the horizon (gamma 0.997/0.99, lambda 0.95)
        gamma, lmbda = (0.997, 0.95) if self.version == 3 else (0.99, 0.95)
        rets = [None] * self.horizon
        last = tval[-1]
        for t in reversed(range(self.horizon)):
            boot = tval[t + 1] if t + 1 < self.horizon else tval[-1]
            last = rew[t] + gamma * ((1 - lmbda) * boot + lmbda * last)
            rets[t] = last
        rets = torch.stack(rets, 0)

        if v1:
            actor_loss = -rets.mean()  # dynamics backprop straight through
        else:
            if self.version == 3:  # percentile return normalization
                with torch.no_grad():
                    lo = torch.quantile(rets, 0.05)
                    hi = torch.quantile(rets, 0.95)
                    self._return_scale = max(1.0, float(hi - lo))
            adv = (rets - val.detach()) / self._return_scale
            logp = torch.stack(im_logps, 0)
            ent = torch.stack(im_ents, 0)
            actor_loss = -(logp * adv.detach()).mean() - 3e-4 * ent.mean()
        self.actor_opt.zero_grad(set_to_none=True)
        actor_loss.backward()
        nn.utils.clip_grad_norm_(self.actor.parameters(), 100.0)
        self.actor_opt.step()

        vin = im_feat.detach()
        if self.version == 3:
            value_loss = _two_hot_loss(self.value(vin), _symlog(rets.detach()), self.bins).mean()
        else:
            value_loss = 0.5 * (self.value(vin).squeeze(-1) - rets.detach()).pow(2).mean()
        self.value_opt.zero_grad(set_to_none=True)
        value_loss.backward()
        nn.utils.clip_grad_norm_(self.value.parameters(), 100.0)
        self.value_opt.step()
        if self.version >= 2:  # EMA / periodic target update (v3 EMA 0.02)
            with torch.no_grad():
                for tp, p in zip(self.target_value.parameters(), self.value.parameters()):
                    tp.mul_(0.98).add_(0.02 * p)


def _bench_dreamer_torch(version: int, batch: int, seq: int, published_seconds: float):
    import os

    # SHEEPRL_TORCH_BENCH_STEPS: plumbing smoke only — a shrunk run is not a
    # publishable number (anchor scales with it below).
    total = int(os.environ.get("SHEEPRL_TORCH_BENCH_STEPS", "16384"))
    learning_starts, replay_ratio = min(1024, total // 2), 0.0625
    n_actions, H, W = 2, 64, 64
    model = _TorchDreamer(version)
    frames = np.zeros((total, H, W, 3), np.uint8)
    acts = np.zeros((total,), np.int64)
    rews = np.zeros((total,), np.float32)
    dones = np.zeros((total,), np.float32)

    h = torch.zeros(1, 8)
    z = torch.zeros(1, model.stoch_dim)
    grad_debt, size, t_anchor = 0.0, 0, None
    anchor_step = min(2048, learning_starts + max(16, (total - learning_starts) // 8))
    t0 = time.perf_counter()
    for step in range(total):
        frame = np.full((H, W, 3), step % 256, np.uint8)  # the dummy pixel env
        if step < learning_starts:
            a = np.random.randint(n_actions)
        else:
            a, h, z = model.policy_step(torch.as_tensor(frame).permute(2, 0, 1).unsqueeze(0), h, z)
        frames[size], acts[size] = frame, a
        rews[size], dones[size] = float(step % 16 == 0), float(step % 4 == 3)
        size += 1
        if step >= learning_starts and size > seq:
            grad_debt += replay_ratio
            while grad_debt >= 1.0:
                grad_debt -= 1.0
                starts = np.random.randint(0, size - seq, batch)
                idx = starts[:, None] + np.arange(seq)[None, :]
                model.train_step(
                    torch.as_tensor(frames[idx]).permute(0, 1, 4, 2, 3),
                    torch.as_tensor(acts[idx]),
                    torch.as_tensor(rews[idx]),
                    torch.as_tensor(dones[idx]),
                )
        if step + 1 == anchor_step:
            t_anchor = time.perf_counter()
    wall = time.perf_counter() - t0
    if t_anchor is None:  # smoke run shorter than the anchor
        t_anchor, anchor_step = t0, 0
    sps = (total - anchor_step) / (time.perf_counter() - t_anchor)
    return {"metric": f"dreamer_v{version}_env_steps_per_sec", "value": round(sps, 2),
            "unit": "env-steps/sec", "harness": "torch-same-host",
            "wall_seconds": round(wall, 1),
            "published_4cpu_sps": round(16384 / published_seconds, 2)}


def bench_dreamer_v1():
    return _bench_dreamer_torch(1, batch=50, seq=50, published_seconds=2207.13)


def bench_dreamer_v2():
    return _bench_dreamer_torch(2, batch=16, seq=50, published_seconds=906.42)


def bench_dreamer_v3():
    return _bench_dreamer_torch(3, batch=16, seq=64, published_seconds=1589.30)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    workloads = {
        "ppo": bench_ppo, "a2c": bench_a2c, "sac": bench_sac,
        "dreamer_v1": bench_dreamer_v1, "dreamer_v2": bench_dreamer_v2,
        "dreamer_v3": bench_dreamer_v3,
    }
    names = list(workloads) if which == "all" else [which]
    for name in names:
        torch.manual_seed(42)
        np.random.seed(42)
        print(json.dumps(workloads[name]()), flush=True)


if __name__ == "__main__":
    main()

"""Operations one DreamerV3 gradient step needs, from the configuration's
widths and the cell's batch, sequence and horizon: matrix products and
convolutions only, two operations per multiply-add, every scan step counted,
nothing counted twice (no recomputation).

What needs a backward pass: the world-model loss (forward + gradient by input
and by weight = 3x forward); the actor, once per imagined latent (3x forward:
the program evaluates it twice, the algorithm needs it once); the critic on
the imagined latents but the last (3x). What needs a forward pass only: the
imagination rollout itself (with discrete actions the REINFORCE objective
stops every gradient at the rollout), the reward, continue and value heads on
the rollout, and the target critic.
"""

from __future__ import annotations

from typing import Any, Dict


def _mlp(rows: int, d_in: int, width: int, layers: int, d_out: int = 0) -> float:
    macs = rows * (d_in * width + max(layers - 1, 0) * width * width + (width * d_out if d_out else 0))
    return 2.0 * macs


def step_flops(model: Dict[str, Any]) -> Dict[str, float]:
    T, B, H = model["sequence"], model["batch"], model["horizon"]
    N = T * B
    SD = model["stoch"] * model["discrete"]
    R, W, L = model["recurrent"], model["dense"], model["mlp_layers"]
    A = int(sum(model["actions"]))
    mult, stages, size = model["cnn_mult"], model["cnn_stages"], model["screen"]
    bins = model["bins"]
    channels = sum(model["cnn_channels"])
    latent = SD + R

    enc = 0.0
    c_in, hw = channels, size
    for i in range(stages):
        c_out, hw = (2**i) * mult, hw // 2
        enc += 2.0 * N * hw * hw * 16 * c_in * c_out
        c_in = c_out
    embed = c_in * hw * hw
    vec = sum(model["mlp_keys"].values()) if model["mlp_keys"] else 0
    if vec:
        enc += _mlp(N, vec, W, L)
        embed += W
    rec_in = _mlp(1, SD + A, W, 1)
    gru = 2.0 * (R + W) * 3 * R
    prior = _mlp(1, R, model["hidden"], 1, SD)
    post = _mlp(1, R + embed, model["hidden"], 1, SD)
    rssm = N * (rec_in + gru + prior + post) + B * prior
    dec = 2.0 * N * latent * embed_cnn(model)
    c_in, hw = (2 ** (stages - 1)) * mult, 4
    for i in reversed(range(stages)):
        c_out = (2 ** (i - 1)) * mult if i > 0 else channels
        dec += 2.0 * N * hw * hw * 16 * c_in * c_out
        c_in, hw = c_out, hw * 2
    if model.get("mlp_decoder_keys"):
        dec += _mlp(N, latent, W, L, vec)
    heads = _mlp(N, latent, W, L, bins) + _mlp(N, latent, W, L, 1)
    world_model = 3.0 * (enc + rssm + dec + heads)

    rollout = H * N * (rec_in + gru + prior)
    rollout_heads = (H + 1) * (_mlp(N, latent, W, L, bins) * 2 + _mlp(N, latent, W, L, 1))  # reward, value, continue
    actor = 3.0 * (H + 1) * _mlp(N, latent, W, L, A)
    critic = 3.0 * H * _mlp(N, latent, W, L, bins) + H * _mlp(N, latent, W, L, bins)  # critic + target
    out = {
        "world_model": world_model,
        "imagination": rollout + rollout_heads,
        "actor": actor,
        "critic": critic,
    }
    out["total"] = sum(out.values())
    return out


def embed_cnn(model: Dict[str, Any]) -> int:
    return (2 ** (model["cnn_stages"] - 1)) * model["cnn_mult"] * 16

"""Operations one PPO gradient step over a `phi4flash` token policy needs, from
the configuration's widths and the cell's shapes: two operations per
multiply-add, forward + backward (3x the forward), the rematerialised forward
not counted.

Counted per position of the padded minibatch (``batch`` sequences of
``prompt_len + rollout_steps`` positions: the step computes every one of
them). Matrix products by their widths. Attention's two products by what a
query may see: the causal triangle for the full and the cross layers (half
the context on average), the band for a window layer (``sliding_window`` keys,
fewer for the first positions). The selective scan's elementwise work (per
position and (inner, state) element: the decay's multiply, exponential and
multiply-add, the input's multiply and multiply-add, the output's
multiply-add: 9 operations, not matrix products) is counted apart, under
``scan_elementwise``, and is part of ``total``: it is work the algorithm needs
and the chip has no faster unit for it. The heads only at the positions the
loss reads (``rollout_steps`` a sequence), the tied embedding as the head.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmarks.reference.phi4flash_ppo import kind_of  # the placement of the kinds by published index, as the reference has it


def step_flops(model: Dict[str, Any]) -> Dict[str, float]:
    H, heads, kv = model["hidden_size"], model["num_attention_heads"], model["num_key_value_heads"]
    d = H // heads
    inner, state, rank, taps = model["expand"] * H, model["d_state"], model["dt_rank"], model["d_conv"]
    S = model["prompt_len"] + model["rollout_steps"]
    W = model["sliding_window"]
    positions = model["batch"] * S
    depth = model["published"]["num_hidden_layers"]
    first, count = model["layers_held"]
    kinds = [kind_of(i, depth) for i in range(first, first + count)]

    # attention per query: scores over `d` and values over `2 d` a pair = heads * 2 d multiply-adds a key seen
    seen_full = (S + 1) / 2.0
    seen_band = (sum(min(t + 1, W) for t in range(S))) / float(S)
    per_key = heads * 2 * d
    macs = {
        "ssm": kinds.count("ssm") * positions * (H * 2 * inner + taps * inner + inner * (rank + 2 * state) + rank * inner + inner * H),
        "window_attn": kinds.count("swa") * positions * (H * (heads + 2 * kv) * d + heads * d * H + per_key * seen_band),
        "full_attn": kinds.count("full") * positions * (H * (heads + 2 * kv) * d + heads * d * H + per_key * seen_full),
        "cross_attn": kinds.count("cross") * positions * (H * heads * d + heads * d * H + per_key * seen_full),
        "gmu": kinds.count("gmu") * positions * (2 * H * inner),
        "dense_mlp": count * positions * 3 * H * model["intermediate_size"],
        "head": model["batch"] * model["rollout_steps"] * H * (model["vocab_size"] + 1),
    }
    out = {name: 3 * 2.0 * value for name, value in macs.items()}
    out["scan_elementwise"] = 3 * 9.0 * kinds.count("ssm") * positions * inner * state
    out["total"] = sum(out.values())
    return out

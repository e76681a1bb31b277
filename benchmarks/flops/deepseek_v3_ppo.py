"""Operations one PPO gradient step over a deepseek_v3 token policy needs,
from the configuration's widths and the cell's shapes: matrix products only,
two operations per multiply-add, forward + backward (3x the forward), the
rematerialised forward not counted.

Counted per position of the padded minibatch (``batch`` sequences of
``prompt_len + rollout_steps`` positions: the step computes every one of
them; `update.padded_share` says how many are padding). The routed experts at
their expected load: of a token's ``num_experts_per_tok`` choices,
``held / n_routed_experts`` fall on an expert held here. Attention as the
causal half: a position attends to half the context on average. The heads
only at the positions the loss reads (``rollout_steps`` a sequence).
"""

from __future__ import annotations

from typing import Any, Dict


def step_flops(model: Dict[str, Any]) -> Dict[str, float]:
    H, heads = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv, r = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"]
    layers, dense_layers = model["num_hidden_layers"], model["first_k_dense_replace"]
    held = model["experts_held"][1]
    S = model["prompt_len"] + model["rollout_steps"]
    positions = model["batch"] * S

    mla = H * heads * (dn + dr) + H * (r + dr) + r * heads * (dn + dv) + heads * dv * H
    scores = heads * (dn + dr + dv) * S / 2.0
    dense = 3 * H * model["intermediate_size"]
    router = H * model["n_routed_experts"]
    shared = 3 * H * model["moe_intermediate_size"] * model["n_shared_experts"]
    routed = model["num_experts_per_tok"] * held / model["n_routed_experts"] * 3 * H * model["moe_intermediate_size"]
    heads_macs = model["batch"] * model["rollout_steps"] * H * (model["vocab_size"] + 1)

    macs = {
        "mla": positions * layers * (mla + scores),
        "dense_mlp": positions * dense_layers * dense,
        "moe_route": positions * (layers - dense_layers) * router,
        "moe_shared": positions * (layers - dense_layers) * shared,
        "moe_experts": positions * (layers - dense_layers) * routed,
        "head": heads_macs,
    }
    out = {name: 3 * 2.0 * value for name, value in macs.items()}
    out["total"] = sum(out.values())
    return out

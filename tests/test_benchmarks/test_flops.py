"""`flops/dreamer_v3.py` against a hand count at micro widths, every scan step counted."""

import conftest  # noqa: F401
from benchmarks.flops.dreamer_v3 import step_flops

MICRO = dict(sequence=2, batch=3, horizon=2, stoch=2, discrete=2, recurrent=4, dense=3, hidden=5, mlp_layers=1,
             actions=[2], cnn_mult=1, cnn_stages=4, screen=64, bins=7, cnn_channels=[3], mlp_keys={})


def hand_count():
    N, B, H = 6, 3, 2
    SD, R, W, hid, A, bins = 4, 4, 3, 5, 2, 7
    latent = SD + R
    # encoder: 64->32->16->8->4, channels 3->1->2->4->8, 4x4 kernels
    enc = N * (32 * 32 * 16 * 3 * 1 + 16 * 16 * 16 * 1 * 2 + 8 * 8 * 16 * 2 * 4 + 4 * 4 * 16 * 4 * 8)
    embed = 8 * 16
    rec_in = (SD + A) * W
    gru = (R + W) * 3 * R
    prior = R * hid + hid * SD
    post = (R + embed) * hid + hid * SD
    rssm = N * (rec_in + gru + prior + post) + B * prior  # T*B steps of the scan, and the initial state's prior
    dec = N * latent * embed + N * (4 * 4 * 16 * 8 * 4 + 8 * 8 * 16 * 4 * 2 + 16 * 16 * 16 * 2 * 1 + 32 * 32 * 16 * 1 * 3)
    head = lambda out: latent * W + W * out  # noqa: E731  one hidden layer
    heads = N * (head(bins) + head(1))
    world_model = 3 * (enc + rssm + dec + heads)
    imagination = H * N * (rec_in + gru + prior) + (H + 1) * N * (2 * head(bins) + head(1))
    actor = 3 * (H + 1) * N * head(A)
    critic = 3 * H * N * head(bins) + H * N * head(bins)
    return {k: 2.0 * v for k, v in dict(world_model=world_model, imagination=imagination, actor=actor, critic=critic).items()}


def test_hand_count():
    got, want = step_flops(MICRO), hand_count()
    for part, value in want.items():
        assert got[part] == value, part
    assert got["total"] == sum(want.values())


def test_scan_steps_are_counted():
    base = step_flops(MICRO)
    longer = step_flops(dict(MICRO, horizon=4))
    assert longer["imagination"] == 2 * base["imagination"] - 0 * base["total"] or longer["imagination"] > 1.6 * base["imagination"]
    twice = step_flops(dict(MICRO, sequence=4))
    assert abs(twice["actor"] - 2 * base["actor"]) < 1e-6

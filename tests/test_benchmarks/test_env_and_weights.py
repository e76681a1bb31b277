"""The benchmark's own env and weights: everything from the seed."""

import numpy as np
import pytest

import conftest  # noqa: F401
from benchmarks.envs.pixel_env import PixelEnv


def rollout(seed, steps=200, **kw):
    env = PixelEnv(seed=seed, length_low=20, length_high=40, **kw)
    frames, ends = [env.reset()[0]["rgb"]], []
    for t in range(steps):
        obs, reward, terminated, truncated, _ = env.step(0)
        frames.append(obs["rgb"])
        if terminated:
            ends.append(t + 1)
            frames.append(env.reset()[0]["rgb"])
    return np.stack(frames), ends


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 7])
def test_env_from_seed(seed):
    a, ends_a = rollout(seed)
    b, ends_b = rollout(seed)
    assert (a == b).all() and ends_a == ends_b
    other, _ = rollout(seed + 1)
    assert (a != other).any()
    assert a.dtype == np.uint8 and a.shape[1:] == (64, 64, 3)
    # rows all differ
    assert len({row.tobytes() for row in a}) == len(a)


def test_env_warm_episodes_and_lengths():
    _, ends = rollout(3, warm_lengths=[6, 14])
    lengths = np.diff([0] + ends)
    assert list(lengths[:2]) == [6, 14]
    assert all(20 <= n <= 40 for n in lengths[2:])


def test_weights_from_seed():
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.weights import make_weights

    shapes = {"a": {"kernel": jax.ShapeDtypeStruct((6, 4), jnp.float32), "scale": jax.ShapeDtypeStruct((4,), jnp.float32)},
              "b": {"bias": jax.ShapeDtypeStruct((3,), jnp.float32)}}
    one, two = make_weights(shapes, 11), make_weights(shapes, 11)
    assert all((x == y).all() for x, y in zip(jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(two)))
    assert (make_weights(shapes, 12)["a"]["kernel"] != one["a"]["kernel"]).any()
    # a sub-tree draws the same leaves: streams are per path
    sub = make_weights({"a": shapes["a"]}, 11)
    assert (sub["a"]["kernel"] == one["a"]["kernel"]).all()
    assert abs(float(one["a"]["scale"].mean()) - 1.0) < 0.3
    assert all(float(jnp.abs(x).min()) > 0 for x in jax.tree_util.tree_leaves(one))


def test_large_seed_is_folded():
    from benchmarks.harness.weights import make_weights
    import jax
    import jax.numpy as jnp

    shapes = {"w": jax.ShapeDtypeStruct((2, 2), jnp.float32)}
    assert jnp.isfinite(make_weights(shapes, 2**31 + 12345)["w"]).all()

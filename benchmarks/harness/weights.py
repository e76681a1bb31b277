"""The benchmark's weights: made from the seed, on the device, in one jitted
call, for any tree of shapes. The program and the plain reference are both
handed these; neither makes its own.

Every leaf is drawn from its own stream (the seed folded with a checksum of
the leaf's path), so a tree and any sub-tree of it agree leaf by leaf:

- a leaf called ``scale`` (a norm's gain): 1 + 0.1 N(0, 1)
- any other leaf with one axis (biases, the initial recurrent state): 0.1 N(0, 1)
- a leaf with more axes (dense and convolution kernels): N(0, 1) / sqrt(fan_in),
  fan_in = the product of all axes but the last

No leaf is zero, so every leaf has a gradient and moves under Adam.
"""

from __future__ import annotations

import zlib
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def leaf_paths(tree: Any) -> Dict[Tuple[str, ...], Any]:
    """``{path: leaf}`` with plain-string paths, in the tree's own order."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out[tuple(str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path)] = leaf
    return out


def _draw(key: jax.Array, path: Tuple[str, ...], shape: Tuple[int, ...], dtype: Any) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(key, shape, jnp.float32)
    if path[-1] == "scale":
        value = 1.0 + 0.1 * noise
    elif len(shape) <= 1:
        value = 0.1 * noise
    else:
        value = noise / np.sqrt(float(np.prod(shape[:-1])))
    return value.astype(dtype)


def draw(shapes: Any, key: jax.Array) -> Any:
    """A tree shaped like ``shapes``, filled from ``key``; traceable."""
    treedef = jax.tree_util.tree_structure(shapes)
    leaves = [_draw(key, path, tuple(leaf.shape), leaf.dtype) for path, leaf in leaf_paths(shapes).items()]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def seed_key(seed: int) -> jax.Array:
    return jax.random.PRNGKey(int(seed) % (2**31 - 1))


def make_weights(shapes: Any, seed: int, device: Any = None) -> Any:
    """A tree shaped like ``shapes`` (anything with ``.shape``/``.dtype``
    leaves), filled from ``seed`` in one jitted call on ``device``."""
    build = jax.jit(lambda key: draw(shapes, key))
    if device is not None:
        with jax.default_device(device):
            return build(seed_key(seed))
    return build(seed_key(seed))

"""PRNG threading utilities.

JAX's functional PRNG replaces the reference's global seeding
(fabric.seed_everything): one root key per run, split deterministically into
named streams; environment/numpy seeding stays host-side.
"""

from __future__ import annotations

import random
from typing import Dict, Iterator, Sequence

import jax
import numpy as np


def seed_everything(seed: int, rank: int = 0) -> jax.Array:
    """Seed python/numpy host RNGs and return the root JAX key.

    The HOST streams (python/numpy — replay sampling, env glue) fold in the
    process ``rank`` so multi-host ranks draw distinct sequences; the
    returned JAX root key deliberately does NOT — model initialization must
    be identical on every rank (algorithms derive per-rank jax streams
    explicitly via fold_in where divergence is wanted).

    ``rank`` defaults to single-process semantics and is never probed here:
    asking JAX for ``process_index()`` builds the backend, which must not
    happen before ``jax.distributed.initialize()``. Multi-host flows seed
    through ``Runtime.seed_everything`` AFTER ``launch()``, which passes the
    real rank.
    """
    random.seed(seed + int(rank))
    np.random.seed(seed + int(rank))
    return jax.random.PRNGKey(seed)


def make_streams(root: jax.Array, names: Sequence[str]) -> Dict[str, jax.Array]:
    keys = jax.random.split(root, len(names))
    return {name: key for name, key in zip(names, keys)}


class KeySequence:
    """Host-side iterator of fresh PRNG keys (for per-iteration sampling).

    Only for host-loop use — never call inside jit (it would retrace).
    """

    def __init__(self, root: jax.Array):
        self._key = root

    def __iter__(self) -> Iterator[jax.Array]:
        return self

    def __next__(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def next(self) -> jax.Array:
        return self.__next__()

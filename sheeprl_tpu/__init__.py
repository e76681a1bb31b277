"""sheeprl-tpu: a TPU-native deep reinforcement learning framework.

Capability parity with SheepRL (PyTorch + Lightning Fabric), re-designed for
TPU: JAX/XLA compute graphs, pjit/shard_map data- and model-parallelism over a
device mesh, Pallas kernels for the RSSM hot loop, host-side numpy replay
buffers with async infeed, and a native YAML config composition engine.
"""

from __future__ import annotations

__version__ = "0.1.0"

import os

# Import every algorithm module so their @register_algorithm decorators run
# (parity with the reference's sheeprl/__init__.py:18-47 registration scheme).
# Kept lazy-safe: a broken optional dependency in one algo must not break the
# others, so each import is individually guarded.
_ALGO_MODULES = [
    "sheeprl_tpu.algos.ppo.ppo",
    "sheeprl_tpu.algos.ppo.ppo_decoupled",
    "sheeprl_tpu.algos.ppo_recurrent.ppo_recurrent",
    "sheeprl_tpu.algos.ppo_lm.ppo_lm",
    "sheeprl_tpu.algos.a2c.a2c",
    "sheeprl_tpu.algos.sac.sac",
    "sheeprl_tpu.algos.sac.sac_decoupled",
    "sheeprl_tpu.algos.sac_ae.sac_ae",
    "sheeprl_tpu.algos.droq.droq",
    "sheeprl_tpu.algos.dreamer_v1.dreamer_v1",
    "sheeprl_tpu.algos.dreamer_v2.dreamer_v2",
    "sheeprl_tpu.algos.dreamer_v3.dreamer_v3",
    "sheeprl_tpu.algos.p2e_dv1.p2e_dv1_exploration",
    "sheeprl_tpu.algos.p2e_dv1.p2e_dv1_finetuning",
    "sheeprl_tpu.algos.p2e_dv2.p2e_dv2_exploration",
    "sheeprl_tpu.algos.p2e_dv2.p2e_dv2_finetuning",
    "sheeprl_tpu.algos.p2e_dv3.p2e_dv3_exploration",
    "sheeprl_tpu.algos.p2e_dv3.p2e_dv3_finetuning",
]

_EVAL_MODULES = [m.rsplit(".", 1)[0] + ".evaluate" for m in _ALGO_MODULES]


def register_all() -> None:
    """Import all algorithm + evaluation modules, populating the registries.

    A module that is absent from disk (not yet built / optional) is skipped
    silently; a module that EXISTS but fails to import is a real bug (a broken
    refactor would otherwise surface later as "unknown algorithm"), so it
    warns — or raises under SHEEPRL_TPU_STRICT_IMPORTS=1.
    """
    import importlib
    import importlib.util
    import warnings

    for mod in _ALGO_MODULES + _EVAL_MODULES:
        try:
            importlib.import_module(mod)
        except ImportError as e:
            if os.environ.get("SHEEPRL_TPU_STRICT_IMPORTS", "0") == "1":
                raise
            try:
                on_disk = importlib.util.find_spec(mod) is not None
            except ModuleNotFoundError:
                on_disk = False
            if on_disk:
                warnings.warn(
                    f"algorithm module '{mod}' exists but failed to import ({e!r}); "
                    "its algorithms will be unavailable",
                    ImportWarning,
                )

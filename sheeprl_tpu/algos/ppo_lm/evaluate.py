"""PPO over tokens: evaluation entrypoint."""

from __future__ import annotations

from typing import Any, Dict

from sheeprl_tpu.algos.ppo_lm.agent import build_agent
from sheeprl_tpu.algos.ppo_lm.utils import test
from sheeprl_tpu.registry import register_evaluation
from sheeprl_tpu.utils.env import make_env
from sheeprl_tpu.utils.logger import get_log_dir, get_logger


@register_evaluation(algorithms="ppo_lm")
def evaluate_ppo_lm(runtime, cfg: Dict[str, Any], state: Dict[str, Any]):
    logger = get_logger(runtime, cfg)
    if logger is not None:
        logger.log_hyperparams(cfg.as_dict() if hasattr(cfg, "as_dict") else dict(cfg))
    log_dir = get_log_dir(runtime, cfg.root_dir, cfg.run_name, logger=logger)
    runtime.print(f"Log dir: {log_dir}")

    env = make_env(cfg, cfg.seed, 0, log_dir, "test", vector_env_idx=0)()
    vocab_size = int(env.action_space.n)
    prompt_len = int(env.observation_space["prompt"].shape[0])
    env.close()

    agent, params = build_agent(runtime, cfg, vocab_size, prompt_len, state["agent"])
    test(agent, params, runtime, cfg, log_dir, logger)

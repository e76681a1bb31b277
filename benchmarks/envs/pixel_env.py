"""The benchmark's own pixel env: 64x64x3 uint8 frames drawn from a seed.

It stands in for Crafter / ALE / Super Mario Bros, none of which is installed
(and the chip machine has no network). A step does no work beyond producing
the frame: a real emulator step costs the host more, so `env_steps_per_s` read
with this env is an upper bound for those users.

Frames are not constant: a bank of `bank` frames is drawn once from the seed
and a step returns the bank row picked by the episode's own stream, with the
step counter written into the first row of the image, so consecutive rows of
the replay all differ. Nor is the stream stationary: the frames' brightness
follows a triangle wave over the run's steps (`level_period` steps from full
to `level_low`/256 and back), so the windows a batch is made of differ in
what they cost to reconstruct, and a step that leaves part of its batch out
shows in its loss. Episode lengths: the first `warm_lengths` are fixed (so
that an episode ends in the prefill and one just after training starts, which
warms the reset programs during set-up); the rest are drawn from the seed,
uniformly in [length_low, length_high].
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import gymnasium as gym
import numpy as np


class PixelEnv(gym.Env):
    metadata = {"render_modes": []}

    def __init__(
        self,
        id: str = "pixel_bench",
        seed: int = 0,
        screen_size: int = 64,
        num_actions: int = 17,
        length_low: int = 200,
        length_high: int = 600,
        warm_lengths: Sequence[int] = (),
        bank: int = 64,
        reward_scale: float = 1.0,
        level_period: int = 256,
        level_low: int = 32,
    ) -> None:
        self._rng = np.random.default_rng([int(seed) % (2**32), 0x9E3779B1])
        self._bank = self._rng.integers(0, 256, (int(bank), screen_size, screen_size, 3), dtype=np.uint8)
        self.observation_space = gym.spaces.Dict(
            {"rgb": gym.spaces.Box(0, 255, (screen_size, screen_size, 3), np.uint8)}
        )
        self.action_space = gym.spaces.Discrete(int(num_actions))
        self._lengths = (int(length_low), int(length_high))
        self._warm = [int(n) for n in warm_lengths]
        self._reward_scale = float(reward_scale)
        self._level = (int(level_period), int(level_low))
        self._episode = 0
        self._t = 0
        self._length = 0
        self._total = 0

    def _frame(self) -> Dict[str, np.ndarray]:
        frame = self._bank[int(self._rng.integers(0, len(self._bank)))]
        period, low = self._level
        phase = (self._total % period) / period
        level = int(low + (256 - low) * abs(2.0 * phase - 1.0))  # 256 -> low -> 256 over one period
        frame = ((frame.astype(np.uint16) * level) >> 8).astype(np.uint8)
        # the running step count, so that no two rows of a run are equal
        frame[0, :4, 0] = np.frombuffer(np.uint32(self._total).tobytes(), np.uint8)
        return {"rgb": frame}

    def reset(self, *, seed: Optional[int] = None, options: Optional[Dict[str, Any]] = None):
        if self._episode < len(self._warm):
            self._length = self._warm[self._episode]
        else:
            self._length = int(self._rng.integers(self._lengths[0], self._lengths[1] + 1))
        self._episode += 1
        self._t = 0
        return self._frame(), {}

    def step(self, action) -> Tuple[Dict[str, np.ndarray], float, bool, bool, Dict[str, Any]]:
        self._t += 1
        self._total += 1
        reward = self._reward_scale * float(self._rng.integers(-1, 2))
        terminated = self._t >= self._length
        return self._frame(), reward, bool(terminated), False, {}

    def render(self):
        return None

    def close(self) -> None:
        pass

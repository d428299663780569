"""The env the loop cells collect from: seeded pixel frames, a discrete
action space and episodes of seeded length.  It stands in for the Atari and
Crafter envs of the published runs (the sealed machine has neither ROMs nor
the package); what it keeps of them is what the loop pays for — a 64x64x3
uint8 frame per step, a reward, and episode ends at realistic distances.

Frames are cheap on purpose (a bank of seeded noise frames, shifted in
intensity by the step count), so the env worker never bounds the loop."""

from __future__ import annotations

import gymnasium as gym
import numpy as np

BANK = 61


class SeededPixelsEnv(gym.Env):
    metadata = {"render_modes": ["rgb_array"], "render_fps": 30}
    render_mode = "rgb_array"

    def __init__(self, seed: int = 0, image_size=(64, 64, 3), action_dim: int = 6,
                 episode_steps_min: int = 1000, episode_steps_max: int = 27000):
        self.observation_space = gym.spaces.Dict(
            {"rgb": gym.spaces.Box(0, 255, shape=tuple(image_size), dtype=np.uint8)}
        )
        self.action_space = gym.spaces.Discrete(int(action_dim))
        self._rng = np.random.default_rng(int(seed))
        self._bank = self._rng.integers(0, 256, (BANK,) + tuple(image_size), dtype=np.uint8)
        self._lo, self._hi = int(episode_steps_min), int(episode_steps_max)
        self._t = 0
        self._end = self._lo

    def _obs(self):
        return {"rgb": self._bank[self._t % BANK] + np.uint8(self._t % 256)}

    def reset(self, seed=None, options=None):
        super().reset(seed=seed)
        self._t = 0
        self._end = int(self._rng.integers(self._lo, self._hi + 1))
        return self._obs(), {}

    def step(self, action):
        self._t += 1
        reward = float(self._rng.random() < 0.05)
        return self._obs(), reward, self._t >= self._end, False, {}

    def render(self):
        return self._obs()["rgb"]


def make_env(id: str = "seeded_pixels", **kwargs) -> gym.Env:
    return SeededPixelsEnv(**kwargs)

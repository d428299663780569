"""Token environment for a language-model policy that generates by diffusion
over blocks: an episode is one response of fixed length, an environment step
is one denoising step, which reveals one token of the block in progress.

- ``reset(key)``: a prompt of ``prompt_len`` ids drawn uniformly from the
  vocabulary without ``mask_id``, followed by ``response_len`` ``mask_id``s;
- action ``(u, x)``: step ``t`` writes token ``x`` at position ``u`` of
  response block ``t // block_length``.  Which position to reveal is the
  policy's choice (the collector's sampler only offers masked ones);
- the episode terminates after ``response_len`` steps, and only that step is
  rewarded, by a programmatic rule: the share of response positions that
  repeat the prompt (``response[i] == prompt[i % prompt_len]``).

The observation (``"tokens"``) is the sequence as it stands.  Everything is
fixed-shape and device-resident (``core.JaxEnv``).
"""

from __future__ import annotations

from typing import Dict

import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np

from sheeprl_tpu.envs.jax.core import JaxEnv


class TokenEnvJax(JaxEnv):
    """State pytree: ``{"tokens": (P + R,) i32, "t": () i32}``."""

    def __init__(self, vocab_size: int = 64, prompt_len: int = 8, response_len: int = 16, block_length: int = 4,
                 mask_id: int = 63):
        if prompt_len % block_length or response_len % block_length:
            raise ValueError(f"prompt_len and response_len must be multiples of block_length={block_length}")
        if not 0 <= mask_id < vocab_size:
            raise ValueError(f"mask_id {mask_id} lies outside the vocabulary of {vocab_size}")
        self.vocab_size, self.mask_id = int(vocab_size), int(mask_id)
        self.prompt_len, self.response_len, self.block_length = int(prompt_len), int(response_len), int(block_length)
        self._conf = (self.vocab_size, self.prompt_len, self.response_len, self.block_length, self.mask_id)
        n = self.prompt_len + self.response_len
        self.observation_space = gym.spaces.Dict(
            {"tokens": gym.spaces.Box(0, self.vocab_size - 1, (n,), np.int32)}
        )
        self.action_space = gym.spaces.MultiDiscrete([self.block_length, self.vocab_size])
        self.max_episode_steps = None  # the episode ends itself, after response_len steps

    def reset(self, key: jax.Array):
        ids = jax.random.randint(key, (self.prompt_len,), 0, self.vocab_size - 1)
        ids = ids + (ids >= self.mask_id)  # uniform over the vocabulary without mask_id
        tokens = jnp.concatenate([ids, jnp.full((self.response_len,), self.mask_id)]).astype(jnp.int32)
        return {"tokens": tokens, "t": jnp.zeros((), jnp.int32)}, {"tokens": tokens}

    def step(self, state: Dict[str, jax.Array], action: jax.Array, key: jax.Array):
        t = state["t"]
        u, x = action[0].astype(jnp.int32), action[1].astype(jnp.int32)
        at = self.prompt_len + (t // self.block_length) * self.block_length + u
        tokens = state["tokens"].at[at].set(x)
        terminated = t + 1 >= self.response_len
        prompt, response = tokens[: self.prompt_len], tokens[self.prompt_len:]
        repeats = (response == prompt[jnp.arange(self.response_len) % self.prompt_len]).mean()
        reward = jnp.where(terminated, repeats, 0.0).astype(jnp.float32)
        return {"tokens": tokens, "t": t + 1}, {"tokens": tokens}, reward, terminated, {}

"""Seeded causal episodes, made on the device, and their plain numpy reference.

``fill`` makes a rollout of whole episodes in one jitted call from the seed:
prompt and response ids uniform over ``0 .. n_ids - 1``, one env step a
response token (the action is ``(0, token)``: position 0 of a block of one),
and one reward per episode, at its last step.  Every value is a 32-bit integer
hash of (seed, stream, episode, index) (``rollout_fill._hash``, the streams of
that module), so ``reference`` is the same arithmetic in numpy and ``check``
holds what the device made to it, value for value.

The arrays are laid out as the program's fused collector lays a rollout out
(``sheeprl_tpu/envs/jax/collect.py``: time-major ``(T, E, ...)``, the prompt as
``(1, E, P)``); this module imports nothing of the program."""

from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench.rollout_fill import PROMPT, RESPONSE, REWARD, _hash


def _make(xp, seed: int, episodes: int, prompt_len: int, response_len: int, n_ids: int) -> Dict[str, object]:
    e = xp.arange(episodes)[:, None]
    prompt = (_hash(xp, seed, PROMPT, e, xp.arange(prompt_len)[None, :]) % xp.uint32(n_ids)).astype(xp.int32)
    response = (_hash(xp, seed, RESPONSE, e, xp.arange(response_len)[None, :]) % xp.uint32(n_ids)).astype(xp.int32)
    actions = xp.stack([xp.zeros_like(response), response], axis=-1)  # (E, T, 2)
    last = (_hash(xp, seed, REWARD, xp.arange(episodes), xp.zeros(episodes, xp.int32)) >> xp.uint32(8)).astype(xp.float32)
    rewards = xp.zeros((response_len, episodes, 1), xp.float32)
    dones = xp.zeros((response_len, episodes, 1), xp.float32)
    if xp is np:
        rewards[-1, :, 0], dones[-1, :, 0] = last / np.float32(2**24), 1.0
    else:
        rewards = rewards.at[-1, :, 0].set(last / xp.float32(2**24))
        dones = dones.at[-1, :, 0].set(1.0)
    return {"prompt": prompt[None], "actions": xp.swapaxes(actions, 0, 1).astype(xp.int32), "rewards": rewards,
            "dones": dones}


def fill(seed: int, episodes: int, prompt_len: int, response_len: int, n_ids: int):
    """The rollout on the device (``prompt``, ``actions``, ``rewards``,
    ``dones``); log-probabilities and values are the policy's to add."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: _make(jnp, seed, episodes, prompt_len, response_len, n_ids))()


def reference(seed: int, episodes: int, prompt_len: int, response_len: int, n_ids: int):
    with np.errstate(over="ignore"):
        return _make(np, seed, episodes, prompt_len, response_len, n_ids)


def check(got: Dict[str, np.ndarray], seed: int, episodes: int, prompt_len: int, response_len: int, n_ids: int) -> str:
    """'' when the device's rollout is the reference's, value for value, and
    every id lies in the slice; else what differs."""
    want = reference(seed, episodes, prompt_len, response_len, n_ids)
    for k, v in want.items():
        g = np.asarray(got[k])
        if g.shape != v.shape or g.dtype != v.dtype or not np.array_equal(g, v):
            return f"{k}: shape {g.shape} {g.dtype} vs {v.shape} {v.dtype}, {int((g != v).sum()) if g.shape == v.shape else '?'} differ"
    ids = np.concatenate([np.asarray(got["prompt"]).ravel(), np.asarray(got["actions"])[..., 1].ravel()])
    if ids.min() < 0 or ids.max() >= n_ids or np.asarray(got["actions"])[..., 0].any():
        return f"ids outside 0..{n_ids - 1}, or a position other than 0"
    return ""

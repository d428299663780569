"""Seeded replay contents, made on the device, and their plain reference.

``fill`` writes a whole replay ring in one jitted call from the seed: frames
from a bank of seeded noise frames shifted by the step index, rewards that
encode (step, env) exactly, one-hot actions, and episode boundaries every
``episode_steps_min..max`` steps per env stream.  ``frame``/``reward`` are
the same formulas in numpy: ``check_batch`` uses them to hold a sampled
batch to what the ring must contain — every sequence a contiguous window of
one env stream that does not cross the write head.

The ring arrays are laid out ``(capacity, n_envs, *feature)`` as
``sheeprl_tpu/data/device_buffer.py`` lays them out; this module reads that
layout from the arrays it is given and imports nothing of the program."""

from __future__ import annotations

from typing import Dict

import numpy as np

BANK = 251  # prime: (7 t + 31 e) mod BANK walks the whole bank
REWARD_SCALE = float(2**24)  # integers below 2^24 are exact in float32
CHUNK = 500  # rows generated per loop step: bounds the temporaries


def boundaries(seed: int, capacity: int, n_envs: int, lo: int, hi: int):
    """(is_first, terminated), each (capacity, n_envs, 1) float32."""
    rng = np.random.default_rng(seed)
    first = np.zeros((capacity, n_envs, 1), np.float32)
    last = np.zeros((capacity, n_envs, 1), np.float32)
    for e in range(n_envs):
        t = 0
        while t < capacity:
            first[t, e, 0] = 1.0
            t += int(rng.integers(lo, hi + 1))
            if t - 1 < capacity:
                last[t - 1, e, 0] = 1.0
    return first, last


def bank(seed: int, feature_shape):
    import jax

    return jax.random.bits(jax.random.key(seed), (BANK,) + tuple(feature_shape), dtype=np.uint8)


def frame(bank_np: np.ndarray, t: np.ndarray, e: np.ndarray) -> np.ndarray:
    return bank_np[(7 * t + 31 * e) % BANK] + (t % 256).astype(np.uint8).reshape(t.shape + (1,) * (bank_np.ndim - 1))


def reward(t: np.ndarray, e: np.ndarray, n_envs: int) -> np.ndarray:
    return ((t * n_envs + e) / REWARD_SCALE).astype(np.float32)


def fill(bufs: Dict[str, "jax.Array"], seed: int, image_key: str, lo: int, hi: int):
    """-> (new ring arrays with the shardings of ``bufs``, the bank).  The
    old arrays are donated."""
    import jax
    import jax.numpy as jnp

    capacity, n_envs = bufs[image_key].shape[:2]
    n_actions = bufs["actions"].shape[-1]
    if capacity * n_envs >= REWARD_SCALE:
        raise ValueError("ring too large for the exact reward code")
    chunk = next(c for c in range(min(CHUNK, capacity), 0, -1) if capacity % c == 0)
    first, last = boundaries(seed, capacity, n_envs, lo, hi)
    bank_dev = bank(seed, bufs[image_key].shape[2:])

    def make(bufs, bank_dev, first, last):
        t = jnp.arange(capacity, dtype=jnp.int32)[:, None]
        e = jnp.arange(n_envs, dtype=jnp.int32)[None, :]
        out = dict(bufs)
        out["rewards"] = ((t * n_envs + e).astype(jnp.float32) / REWARD_SCALE)[..., None].astype(bufs["rewards"].dtype)
        out["actions"] = jax.nn.one_hot((3 * t + e) % n_actions, n_actions, dtype=bufs["actions"].dtype)
        out["is_first"] = first.astype(bufs["is_first"].dtype)
        out["terminated"] = last.astype(bufs["terminated"].dtype)
        out["truncated"] = jnp.zeros_like(bufs["truncated"])

        def body(i, img):
            tt = i * chunk + jnp.arange(chunk, dtype=jnp.int32)[:, None]
            rows = bank_dev[(7 * tt + 31 * e) % BANK] + (tt % 256).astype(jnp.uint8).reshape(chunk, 1, *([1] * (img.ndim - 2)))
            return jax.lax.dynamic_update_slice_in_dim(img, rows.astype(img.dtype), i * chunk, 0)

        out[image_key] = jax.lax.fori_loop(0, capacity // chunk, body, bufs[image_key])
        return out

    shardings = {k: v.sharding for k, v in bufs.items()}
    filled = jax.jit(make, donate_argnums=(0,), out_shardings=shardings)(bufs, bank_dev, first, last)
    return filled, bank_dev


def check_batch(batch: Dict[str, np.ndarray], bank_np: np.ndarray, seed: int, capacity: int, n_envs: int,
                image_key: str, lo: int, hi: int) -> str:
    """'' when every sequence of a sampled batch (arrays (T, B, ...)) is a
    contiguous window of one seeded env stream; else what is wrong."""
    first, last = boundaries(seed, capacity, n_envs, lo, hi)
    seq_len, batch_size = batch["rewards"].shape[:2]
    code = np.rint(batch["rewards"][0, :, 0].astype(np.float64) * REWARD_SCALE).astype(np.int64)
    t0, env = code // n_envs, code % n_envs
    if np.any(t0 < 0) or np.any(t0 + seq_len > capacity):
        return f"a sequence crosses the write head: starts {t0.tolist()} of capacity {capacity}"
    t = t0[None, :] + np.arange(seq_len)[:, None]
    e = np.broadcast_to(env[None, :], t.shape)
    if not np.array_equal(batch["rewards"][..., 0], reward(t, e, n_envs)):
        return "rewards are not one contiguous window per sequence"
    if not np.array_equal(batch["is_first"][..., 0], first[t, e, 0]):
        return "is_first does not match the seeded episode starts"
    if not np.array_equal(batch["terminated"][..., 0], last[t, e, 0]):
        return "terminated does not match the seeded episode ends"
    if not np.array_equal(batch[image_key], frame(bank_np, t, e)):
        return "frames differ from the seeded stream"
    if len({(int(a), int(b)) for a, b in zip(t0, env)}) < max(2, batch_size // 2):
        return "the sampler returned nearly the same window for every sequence"
    return ""

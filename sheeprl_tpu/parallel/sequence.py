"""Sequence/context parallelism: train a causal transformer with the
SEQUENCE axis sharded over the device mesh.

The reference framework has no long-context support at all (SURVEY §5.7:
no attention anywhere, sequence scaling = truncated BPTT). This module is
the TPU-first extension that makes long context first-class:

- each device holds a contiguous ``S/n`` shard of every sequence;
- attention runs as a ring: K/V shards rotate over ICI with
  ``jax.lax.ppermute`` while an online softmax folds one block per hop
  (``sheeprl_tpu.ops.ring_attention``) — per-device memory stays
  O(S/n * block) even under gradients: a custom VJP re-rotates K/V
  around the ring in the backward pass instead of saving the forward
  scan's per-hop K/V carries (numbers in
  benchmarks/results/ring_attention_r4.json);
- gradients are ``pmean``-reduced across the ring, so the step is a drop-in
  SPMD train step: params replicated in, params replicated out.

Wrap-around targets: inputs/targets are pre-shifted HOST-side
(``inputs = tokens[:, :-1]``, ``targets = tokens[:, 1:]``) so no logits ever
need to cross a shard boundary.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def make_sequence_parallel_train_step(
    mesh: Mesh,
    model,
    tx: optax.GradientTransformation,
    axis_name: str = "data",
) -> Tuple[Callable, NamedSharding]:
    """Build a jitted sequence-parallel LM train step over ``mesh``.

    ``model`` must be a flax module built with ``parallelism="ring"`` and
    the same ``axis_name`` (e.g. ``models.SequenceTransformer``). Returns
    ``(step, token_sharding)`` where ``step(params, opt_state, inputs,
    targets) -> (params, opt_state, loss)`` and inputs/targets are
    ``(B, S)`` int32 with S divisible by the axis size, placed with
    ``token_sharding``.
    """
    token_spec = P(None, axis_name)

    @jax.jit
    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), token_spec, token_spec),
        out_specs=(P(), P(), P()),
    )
    def step(params, opt_state, inputs, targets):
        def loss_fn(p):
            logits = model.apply(p, inputs)  # (B, S_local, V), ring attention inside
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return nll.mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        # average across the ring: every device saw S/n of each sequence
        grads = jax.lax.pmean(grads, axis_name)
        loss = jax.lax.pmean(loss, axis_name)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, NamedSharding(mesh, token_spec)

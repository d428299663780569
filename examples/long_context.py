"""Long-context sequence parallelism demo.

Trains a causal SequenceTransformer on a copy task with the SEQUENCE axis
sharded over an 8-device mesh: each device holds S/8 of every sequence,
ring attention rotates K/V shards over the ring (ICI on real hardware)
while an online softmax folds one block per hop, and gradients are
pmean-reduced. Per-device memory stays O(S/8) — the mechanism that scales
to million-token contexts on TPU pods.

Run (no TPU needed):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context.py
"""

import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from sheeprl_tpu.models.models import SequenceTransformer
from sheeprl_tpu.parallel import MeshRuntime
from sheeprl_tpu.parallel.sequence import make_sequence_parallel_train_step

if __name__ == "__main__":
    runtime = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    vocab, batch, seq = 32, 4, 128  # sequence sharded 16 tokens/device

    model = SequenceTransformer(
        vocab_size=vocab, embed_dim=64, depth=2, num_heads=4, max_len=seq,
        parallelism="ring", axis_name="data",
    )
    init_model = SequenceTransformer(  # same params, init outside shard_map
        vocab_size=vocab, embed_dim=64, depth=2, num_heads=4, max_len=seq,
        parallelism="blockwise",
    )

    rng = np.random.default_rng(0)
    half = seq // 2 + 1
    first = rng.integers(1, vocab, (batch, half))
    tokens = np.concatenate([first, first], axis=1)[:, : seq + 1].astype(np.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    params = init_model.init(jax.random.PRNGKey(0), jnp.asarray(inputs[:, : seq // 8]))
    tx = optax.adam(3e-3)
    step, token_sharding = make_sequence_parallel_train_step(runtime.mesh, model, tx)

    params = runtime.replicate(params)
    opt_state = runtime.replicate(tx.init(params))
    inputs = jax.device_put(jnp.asarray(inputs), token_sharding)
    targets = jax.device_put(jnp.asarray(targets), token_sharding)

    n_iters = int(os.environ.get("LONG_CONTEXT_ITERS", 30))
    for it in range(n_iters):
        params, opt_state, loss = step(params, opt_state, inputs, targets)
        if it % 10 == 0:
            print(f"iter {it:3d}  loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f} (copy task; random = {np.log(vocab):.2f})")

"""Worker body for the 2-process multi-host plane test.

Launched by ``test_multihost.py`` with SHEEPRL_COORDINATOR_ADDRESS /
_NUM_PROCESSES / _PROCESS_ID set: exercises the real
``jax.distributed.initialize`` branch in ``MeshRuntime.launch``
(parallel/mesh.py), the host-plane collectives (``all_gather_object``,
``barrier``) and ONE jitted sharded train step over the global 2-device
mesh — the CPU stand-in for the reference's multi-node
NCCL/TorchCollective backend (SURVEY.md §5.8).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

# jax may be imported before this file runs; the env
# var alone is too late (same dance as tests/conftest.py)
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


def main() -> int:
    rank = int(os.environ["SHEEPRL_PROCESS_ID"])

    from sheeprl_tpu.parallel.mesh import MeshRuntime

    rt = MeshRuntime(devices=-1, num_nodes=2, accelerator="cpu").launch()
    assert jax.process_count() == 2, jax.process_count()
    assert rt.global_rank == rank
    assert rt.world_size == 2, rt.world_size
    assert rt.is_global_zero == (rank == 0)

    # host plane: object all-gather + barrier
    gathered = rt.all_gather_object({"rank": rank, "tag": f"proc{rank}"})
    assert [g["rank"] for g in gathered] == [0, 1], gathered
    rt.barrier()

    # one sharded train step: the batch is sharded over the global "data"
    # axis (each process contributes its local rows), params replicated;
    # the mean reduction crosses the process boundary inside jit
    batch_sharding = NamedSharding(rt.mesh, P("data"))
    local_x = np.full((2, 8), float(rank + 1), np.float32)
    gx = jax.make_array_from_process_local_data(batch_sharding, local_x, global_shape=(4, 8))
    w = jax.make_array_from_process_local_data(
        NamedSharding(rt.mesh, P()), np.ones((8,), np.float32), global_shape=(8,)
    )

    @jax.jit
    def step(w, x):
        loss, grads = jax.value_and_grad(lambda w_: jnp.mean((x @ w_) ** 2))(w)
        return w - 0.1 * grads, loss

    new_w, loss = step(w, gx)
    # global rows are [1,1,2,2] * ones(8): x@w = [8,8,16,16], mean of
    # squares = (64+64+256+256)/4 = 160 — only correct if BOTH processes'
    # shards entered the reduction
    got = float(loss)
    assert abs(got - 160.0) < 1e-4, got
    assert np.isfinite(np.asarray(jax.device_get(new_w.addressable_shards[0].data))).all()
    rt.barrier()
    print(f"MULTIHOST_OK rank={rank} loss={got}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

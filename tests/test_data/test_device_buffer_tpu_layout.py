"""The sequence samplers of ``data/device_buffer.py`` never relay the ring.

On a v5e a ring ``u8[cap, n_envs, 64, 64, 3]`` keeps capacity on the lanes
(``{0,3,4,2,1:T(8,128)(4,1)}``); a two-index gather ``buf[t_idx, e_idx]``
first copies the whole ring to a gather-friendly layout (``copy.9``, 4.18 GB
of temporaries for a 2.09 GB ring, 26 ms a draw: PERF.md §6, PR 25).  The
programs are compiled here for a described, unattached chip
(on-chip-measurement guide §2.3) at the benchmark cells' ring shapes and held
to: no instruction but a parameter as large as the ring, and under 64 MB of
temporaries.  Nothing runs, so this says nothing about results or speed.

Needs the TPU compiler (libtpu); where another process holds its lock the
fixture skips, like ``tests/test_ops/test_tpu_compile.py``.
"""

import os
import re
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from sheeprl_tpu.data import device_buffer as db
from sheeprl_tpu.parallel.sharding import BATCH_AXES

B, L = 16, 64
TEMP_LIMIT = 64 * 2**20
IMAGE = (64, 64, 3)
# DreamerV3's replay row: one pixel key and five small vector keys
VECTOR_KEYS = {"actions": 17, "rewards": 1, "is_first": 1, "terminated": 1, "truncated": 1}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation, or its lock is taken
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


def _ring(cap, n_envs, sharding):
    shapes = {"rgb": ((cap, n_envs) + IMAGE, jnp.uint8)}
    shapes.update({k: ((cap, n_envs, f), jnp.float32) for k, f in VECTOR_KEYS.items()})
    return {k: jax.ShapeDtypeStruct(s, d, sharding=sharding) for k, (s, d) in shapes.items()}


def _vec(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _ring_sized(text, ring_elems):
    """Instructions of an optimized HLO module, other than parameters, whose
    result holds an array with at least the ring's element count."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m is None or m.group(2) == "parameter":
            continue
        shapes = re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", m.group(1))
        if m.group(2).endswith("slice-start"):
            shapes = shapes[1:]  # an async slice's result tuple names its operand first
        if any(np.prod([int(d) for d in s.split(",")], dtype=np.int64) >= ring_elems for s in shapes):
            found.append(line.strip()[:200])
    return found


def _one_chip_programs(topo, cap, n_envs, prioritized):
    chip = SingleDeviceSharding(topo.devices[0])
    geom = dict(n_samples=1, batch_size=B, seq_len=L, cap=cap, n_envs=n_envs)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
    heads = _vec(n_envs, jnp.int32, chip)
    if prioritized:
        from sheeprl_tpu.replay.priority_tree import PriorityTree

        tree = PriorityTree(cap * n_envs)
        draw = db._sample_draw_prioritized.lower(
            jax.ShapeDtypeStruct(tree.tree.shape, tree.tree.dtype, sharding=chip), key, heads, heads,
            jax.ShapeDtypeStruct((), jnp.float32, sharding=chip), depth=tree.depth, **geom,
        )
    else:
        draw = db._sample_draw.lower(key, heads, heads, **geom)
    read = db._sample.lower(_ring(cap, n_envs, chip), _vec(B, jnp.int32, chip), _vec(B, jnp.int32, chip), seq_len=L)
    return [draw, read]


def _mesh_programs(topo, cap):
    """The sharded sampler's two ``shard_map`` programs on the 2x2 mesh: one
    env stream and ``cap`` frames a chip, global batch 4 x B."""
    n_dev = len(topo.devices)
    mesh = Mesh(np.asarray(topo.devices).reshape(n_dev, 1), BATCH_AXES)

    # what ShardedDeviceReplayCache reads of a MeshRuntime
    cache = db.ShardedDeviceReplayCache(cap, n_dev, types.SimpleNamespace(device_count=n_dev, mesh=mesh))
    cache._bufs = _ring(cap, n_dev, NamedSharding(mesh, P(None, BATCH_AXES)))
    rows = NamedSharding(mesh, P(BATCH_AXES))
    replicated = NamedSharding(mesh, P())
    draw = cache._build_sharded_draw(1, n_dev * B, L).lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated), _vec(n_dev, jnp.int32, rows),
        _vec(n_dev, jnp.int32, rows),
    )
    read = cache._build_sharded_read(L).lower(
        cache._bufs, _vec(n_dev * B, jnp.int32, rows), _vec(n_dev * B, jnp.int32, rows)
    )
    return [draw, read]


@pytest.mark.parametrize(
    "cap,n_envs,kind",
    [(42500, 4, "uniform"), (170000, 1, "uniform"), (100000, 1, "mesh"), (42500, 4, "prioritized")],
    ids=["dv3_XL_train-42500x4", "dv3_XL_loop-170000x1", "dv3_XL_train_x4-100000x1-a-chip", "prioritized-42500x4"],
)
def test_sequence_sampler_compiles_for_v5e_without_a_whole_ring_op(topo, cap, n_envs, kind):
    if kind == "mesh":
        lowered = _mesh_programs(topo, cap)
    else:
        lowered = _one_chip_programs(topo, cap, n_envs, prioritized=kind == "prioritized")
    ring_elems = cap * n_envs * int(np.prod(IMAGE))
    for low in lowered:
        compiled = low.compile()
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < TEMP_LIMIT, f"{temp / 1e6:.0f} MB of temporaries"
        assert not _ring_sized(compiled.as_text(), ring_elems)

"""DeviceReplayCache (data/device_buffer.py): ring/window semantics must
mirror EnvIndependentReplayBuffer over SequentialReplayBuffer — per-env
write heads, wrap-around-safe uniform starts, contiguous single-env
windows — with everything device-resident."""

import jax
import numpy as np
import pytest

from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer, SequentialReplayBuffer
from sheeprl_tpu.data.device_buffer import DeviceReplayCache

CAP, N_ENVS = 16, 3


def _row(t, n_envs=N_ENVS, envs=None):
    """One step row: 'clock' encodes (global step t) per env; 'rgb' is a
    uint8 image encoding t % 251 so dtype passthrough is visible."""
    cols = n_envs if envs is None else len(envs)
    return {
        "clock": np.full((1, cols, 1), float(t), np.float32),
        "rgb": np.full((1, cols, 2, 2, 1), t % 251, np.uint8),
    }


def test_append_sample_windows_are_contiguous_and_valid():
    cache = DeviceReplayCache(CAP, N_ENVS)
    for t in range(10):  # not yet full
        cache.add(_row(t))
    assert cache.can_sample(4)
    batches = cache.sample(n_samples=2, batch_size=5, seq_len=4, key=jax.random.PRNGKey(0))
    assert len(batches) == 2
    for b in batches:
        clock = np.asarray(b["clock"])  # (L, B, 1)
        assert clock.shape == (4, 5, 1)
        assert b["rgb"].dtype == np.uint8
        for col in range(5):
            w = clock[:, col, 0]
            assert np.all(np.diff(w) == 1.0), w  # contiguous
            assert 0 <= w[0] and w[-1] <= 9  # within stored history


def test_wraparound_never_crosses_write_head():
    cache = DeviceReplayCache(CAP, N_ENVS)
    total = 3 * CAP + 5
    for t in range(total):
        cache.add(_row(t))
    L = 6
    batches = cache.sample(n_samples=4, batch_size=8, seq_len=L, key=jax.random.PRNGKey(1))
    lo, hi = total - CAP, total - 1  # stored logical time range
    starts = set()
    for b in batches:
        clock = np.asarray(b["clock"])
        for col in range(clock.shape[1]):
            w = clock[:, col, 0]
            assert np.all(np.diff(w) == 1.0), w
            assert w[0] >= lo and w[-1] <= hi, (w, lo, hi)
            starts.add(int(w[0]))
    # uniform over the full valid start range: with 64 draws over 11 starts
    # we should see several distinct ones, including near both ends
    assert len(starts) >= 5


def test_reset_adds_diverge_cursors():
    cache = DeviceReplayCache(CAP, N_ENVS)
    for t in range(8):
        cache.add(_row(t))
    # env 1 gets two extra (reset) rows -> its ring advances further
    cache.add(_row(100, envs=[1]), indices=[1])
    cache.add(_row(101, envs=[1]), indices=[1])
    assert list(cache._filled) == [8, 10, 8]
    batches = cache.sample(n_samples=8, batch_size=8, seq_len=8, key=jax.random.PRNGKey(2))
    saw_reset_row = False
    for b in batches:
        clock = np.asarray(b["clock"])
        for col in range(clock.shape[1]):
            w = clock[:, col, 0]
            if w[-1] >= 100.0:
                saw_reset_row = True  # a window that runs into env 1's resets
                assert w[-2] <= 101.0
    assert saw_reset_row


def test_load_from_host_buffer_matches_content():
    rb = EnvIndependentReplayBuffer(CAP, n_envs=N_ENVS, buffer_cls=SequentialReplayBuffer)
    cache = DeviceReplayCache(CAP, N_ENVS)
    for t in range(CAP + 7):  # force wraparound on the host side too
        rb.add(_row(t))
    cache.load_from(rb)
    assert list(cache._pos) == [b._pos for b in rb.buffer]
    assert cache.can_sample(5)
    batches = cache.sample(n_samples=2, batch_size=6, seq_len=5, key=jax.random.PRNGKey(3))
    lo, hi = 7, CAP + 6
    for b in batches:
        clock = np.asarray(b["clock"])
        rgb = np.asarray(b["rgb"])
        for col in range(clock.shape[1]):
            w = clock[:, col, 0]
            assert np.all(np.diff(w) == 1.0), w
            assert w[0] >= lo and w[-1] <= hi
            np.testing.assert_array_equal(
                rgb[:, col, 0, 0, 0], (w.astype(np.int64) % 251).astype(np.uint8)
            )


def test_transitions_next_obs_pairs_and_head_exclusion():
    """Flat-transition draws (SAC family): next_<k> must be the row's
    successor, and with next-obs the row at the write head is excluded
    (its successor is stale)."""
    from sheeprl_tpu.data.buffers import ReplayBuffer

    cache = DeviceReplayCache(CAP, N_ENVS)
    total = CAP + 9  # wrapped: stale row = oldest stored successor crossing
    for t in range(total):
        cache.add(_row(t))
    out = cache.sample_transitions(
        4, 16, jax.random.PRNGKey(5), sample_next_obs=True, obs_keys=("clock",)
    )
    clock = np.asarray(out["clock"]).reshape(-1)
    nxt = np.asarray(out["next_clock"]).reshape(-1)
    np.testing.assert_array_equal(nxt, clock + 1.0)
    lo, hi = total - CAP, total - 1
    assert clock.min() >= lo
    # write-head exclusion: the newest row (hi) can never be drawn as the
    # base of a next-obs pair — its successor would be the oldest row
    assert clock.max() <= hi - 1

    # parity with the host buffer's own semantics
    rb = ReplayBuffer(CAP, N_ENVS, obs_keys=("clock",))
    for t in range(total):
        rb.add(_row(t))
    host = rb.sample(64, sample_next_obs=True)
    h_clock = host["clock"].reshape(-1)
    h_nxt = host["next_clock"].reshape(-1)
    np.testing.assert_array_equal(h_nxt, h_clock + 1.0)
    assert h_clock.min() >= lo and h_clock.max() <= hi - 1


def test_load_from_replay_matches_content():
    from sheeprl_tpu.data.buffers import ReplayBuffer

    rb = ReplayBuffer(CAP, N_ENVS, obs_keys=("clock",))
    for t in range(CAP + 3):
        rb.add(_row(t))
    cache = DeviceReplayCache(CAP, N_ENVS)
    cache.load_from_replay(rb)
    assert list(cache._pos) == [rb._pos] * N_ENVS
    out = cache.sample_transitions(2, 32, jax.random.PRNGKey(6))
    clock = np.asarray(out["clock"]).reshape(-1)
    rgb = np.asarray(out["rgb"]).reshape(-1, 4)[:, 0]
    assert clock.min() >= 3 and clock.max() <= CAP + 2
    np.testing.assert_array_equal(rgb, (clock.astype(np.int64) % 251).astype(np.uint8))
    assert out["rgb"].dtype == np.uint8


def test_sample_before_enough_data_raises():
    cache = DeviceReplayCache(CAP, N_ENVS)
    cache.add(_row(0))
    with pytest.raises(ValueError, match="Cannot sample"):
        cache.sample(1, 2, seq_len=4, key=jax.random.PRNGKey(0))


def test_changed_key_set_disables_cache():
    """A resume that changes the stored key set (e.g. flipping
    buffer.sample_next_obs) must fall back to the host path, not crash."""
    cache = DeviceReplayCache(CAP, N_ENVS)
    cache.add(_row(0))
    row2 = _row(1)
    row2["extra"] = np.zeros((1, N_ENVS, 1), np.float32)
    cache.add(row2)  # superset of cached keys
    assert not cache.active and cache._bufs is None
    cache.add(_row(2))  # further adds no-op
    assert not cache.can_sample(1)


def test_budget_gate_disables_without_error():
    cache = DeviceReplayCache(CAP, N_ENVS, budget_bytes=8)  # absurdly small
    cache.add(_row(0))
    assert not cache.active
    cache.add(_row(1))  # no-ops, no crash
    assert not cache.can_sample(1)


def test_sharded_cache_multi_device():
    """Env-sharded variant on the 8-virtual-device CPU mesh: windows must
    be contiguous/valid per env, the batch axis must come out sharded on
    'data' (matching runtime.batch_sharding(axis=1)), and env choice is
    stratified — each device contributes batch/n rows from its own envs."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sheeprl_tpu.data.device_buffer import ShardedDeviceReplayCache
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device mesh")
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    cache = ShardedDeviceReplayCache(CAP, 8, rt)
    total = 2 * CAP + 3
    rng = np.random.default_rng(0)
    for t in range(total):
        cache.add(
            {
                "clock": np.full((1, 8, 1), float(t), np.float32),
                "env_id": np.arange(8, dtype=np.float32).reshape(1, 8, 1),
            }
        )
    batches = cache.sample(n_samples=2, batch_size=16, seq_len=5, key=jax.random.PRNGKey(0))
    lo, hi = total - CAP, total - 1
    for b in batches:
        # the sampler's spec names both batch axes; 'fsdp' has size 1 here
        assert b["clock"].sharding.is_equivalent_to(rt.batch_sharding(axis=1), 3)
        assert b["clock"].sharding.is_equivalent_to(NamedSharding(rt.mesh, P(None, "data")), 3)
        clock = np.asarray(b["clock"])  # (L, B, 1)
        env_id = np.asarray(b["env_id"])
        assert clock.shape == (5, 16, 1)
        for col in range(16):
            w = clock[:, col, 0]
            assert np.all(np.diff(w) == 1.0), w
            assert lo <= w[0] and w[-1] <= hi
            # stratification: batch column c belongs to device c//2's env
            # (env axis sharded over 8 devices, 1 env each here)
            assert np.all(env_id[:, col, 0] == env_id[0, col, 0])
        # each device's 2 columns only reference its own env
        owner = env_id[0, :, 0].reshape(8, 2)
        np.testing.assert_array_equal(owner[:, 0], np.arange(8, dtype=np.float32))
        np.testing.assert_array_equal(owner[:, 1], np.arange(8, dtype=np.float32))


def test_sharded_cache_load_from_and_factory():
    """maybe_create_for returns the sharded variant on an opt-in
    multi-device mesh and refills it from the restored host buffer."""
    from sheeprl_tpu.data.device_buffer import ShardedDeviceReplayCache, maybe_create_for
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device mesh")
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()

    class FakeCfgBuf(dict):
        def get(self, k, d=None):
            return dict.get(self, k, d)

    class FakeCfg:
        buffer = FakeCfgBuf(device_cache=True, checkpoint=True)

    rb = EnvIndependentReplayBuffer(CAP, n_envs=8, buffer_cls=SequentialReplayBuffer)
    for t in range(CAP + 4):
        rb.add({"clock": np.full((1, 8, 1), float(t), np.float32)})
    cache = maybe_create_for(FakeCfg(), rt, rb, state={"rb": object()})
    assert isinstance(cache, ShardedDeviceReplayCache)
    batches = cache.sample(1, 8, 4, jax.random.PRNGKey(1))
    clock = np.asarray(batches[0]["clock"])
    for col in range(8):
        w = clock[:, col, 0]
        assert np.all(np.diff(w) == 1.0)
        assert 4 <= w[0] and w[-1] <= CAP + 3


def test_maybe_create_gating(monkeypatch):
    class FakeCfgBuf(dict):
        def get(self, k, d=None):
            return dict.get(self, k, d)

    class FakeCfg:
        buffer = FakeCfgBuf()

    class FakeRuntime:
        device_count = 1
        device = jax.devices("cpu")[0]

    # auto on a cpu platform: no win, stays off
    assert DeviceReplayCache.maybe_create(FakeCfg(), FakeRuntime(), 8, 2) is None
    # explicit on: created even on cpu (tests, smoke runs)
    FakeCfg.buffer = FakeCfgBuf(device_cache=True)
    assert DeviceReplayCache.maybe_create(FakeCfg(), FakeRuntime(), 8, 2) is not None
    # multi-device: always off
    FakeRuntime.device_count = 8
    assert DeviceReplayCache.maybe_create(FakeCfg(), FakeRuntime(), 8, 2) is None
    # env kill-switch beats config
    FakeRuntime.device_count = 1
    monkeypatch.setenv("SHEEPRL_DEVICE_CACHE", "0")
    assert DeviceReplayCache.maybe_create(FakeCfg(), FakeRuntime(), 8, 2) is None
    monkeypatch.delenv("SHEEPRL_DEVICE_CACHE")

    # EpisodeBuffer replay (DV2 prioritize_ends mode) keeps the host path
    # even with device_cache=True — only the uniform samplers are mirrored
    from sheeprl_tpu.data.buffers import EpisodeBuffer
    from sheeprl_tpu.data.device_buffer import maybe_create_for

    assert maybe_create_for(FakeCfg(), FakeRuntime(), EpisodeBuffer(32, 4)) is None


def test_int32_addressability_gate(capsys):
    """One ring array past 2^31 elements/bytes must refuse to allocate
    (XLA's TPU gather lowering linearizes offsets in int32; overflow
    crashes the TPU worker — observed with a 25000 x 8 x 64x64x3 ring).
    The gate flips the cache to the host path instead."""
    # 25000 * 8 * 64*64*3 = 2.46e9 B > 2^31: exactly the crash shape
    cache = DeviceReplayCache(25_000, 8)
    row = {"rgb": np.zeros((1, 8, 64, 64, 3), np.uint8)}
    cache.add(row)
    assert not cache.active and cache._bufs is None
    assert "int32-safe" in capsys.readouterr().out
    # same row shape with a modest capacity (well under the bound):
    # allocates fine — the gate must not false-positive
    ok = DeviceReplayCache(1_250, 8)
    assert ok._ensure(row) and ok.active
    # dtype width counts: f32 crosses 2^31 BYTES at 1/4 the element count
    f32 = DeviceReplayCache(25_000 // 4 + 64, 8)
    assert not f32._ensure({"x": np.zeros((1, 8, 64, 64, 3), np.float32)})
    assert not f32.active


def test_auto_mode_is_bounded_by_the_budget_and_the_int32_gate_alone(capsys):
    """``auto`` hands the cache its ``device_cache_budget_gb``; explicit
    opt-in passes none.  There is no other ring-size gate: between the
    budget and int32 addressability every ring is admitted."""
    row = {"rgb": np.zeros((1, 8, 64, 64, 3), np.uint8)}
    # 128/env x 8 x 12288 B = 12.6 MB > 10 MB budget: auto refuses, no alloc
    auto = DeviceReplayCache(128, 8, budget_bytes=10_000_000)
    assert not auto._ensure(row) and not auto.active
    assert "budget" in capsys.readouterr().out
    # explicit mode has no budget (int32 gate only)
    explicit = DeviceReplayCache(128, 8)
    assert explicit._ensure(row) is True
    # a budget that covers the footprint admits the same ring in auto mode
    widened = DeviceReplayCache(128, 8, budget_bytes=20_000_000)
    assert widened._ensure(row) is True


def test_resume_load_paths_apply_size_gates(capsys):
    """load_from / load_from_replay (checkpoint resume) must apply the same
    gates as the fresh-run path — a resumed oversized ring must not slip
    past the budget the fresh run was held to."""
    from sheeprl_tpu.data.buffers import ReplayBuffer

    rb = ReplayBuffer(64, 4, obs_keys=("rgb",))
    for t in range(8):
        rb.add({"rgb": np.full((1, 4, 16, 16, 3), t, np.uint8)})
    # 64 x 4 x 768 B = 196 KB > 100 KB budget: the refill refuses
    cache = DeviceReplayCache(64, 4, budget_bytes=100_000)
    cache.load_from_replay(rb)
    assert not cache.active and cache._bufs is None
    assert "budget" in capsys.readouterr().out
    # without a budget it refills fine
    ok = DeviceReplayCache(64, 4)
    ok.load_from_replay(rb)
    assert ok.active and ok._bufs is not None


def test_windowed_add_matches_per_row_adds():
    """T>1 add (one _append_window dispatch) must leave the rings, write
    heads, and fill counts identical to T sequential per-row adds —
    including across a ring wrap and past capacity overflow."""
    a = DeviceReplayCache(CAP, N_ENVS)
    b = DeviceReplayCache(CAP, N_ENVS)
    total = CAP + 7  # wraps the ring
    rows = [_row(t) for t in range(total)]
    for r in rows:
        a.add(r)
    b.add({k: np.concatenate([r[k] for r in rows], axis=0) for k in rows[0]})
    assert np.array_equal(np.asarray(a._pos), np.asarray(b._pos))
    assert np.array_equal(np.asarray(a._filled), np.asarray(b._filled))
    for k in a._bufs:
        assert np.array_equal(np.asarray(a._bufs[k]), np.asarray(b._bufs[k])), k
    # a window longer than the ring keeps only the last CAP rows, at the
    # SAME ring positions sequential adds would have left them
    c = DeviceReplayCache(CAP, N_ENVS)
    d = DeviceReplayCache(CAP, N_ENVS)
    long_rows = [_row(t) for t in range(2 * CAP + 3)]
    c.add({k: np.concatenate([r[k] for r in long_rows], axis=0) for k in long_rows[0]})
    for r in long_rows:
        d.add(r)
    assert np.array_equal(np.asarray(c._pos), np.asarray(d._pos))
    assert np.array_equal(np.asarray(c._filled), np.asarray(d._filled))
    for k in c._bufs:
        assert np.array_equal(np.asarray(c._bufs[k]), np.asarray(d._bufs[k])), k


def test_windowed_add_partial_env_indices():
    """Windowed adds route columns through `indices` exactly like the
    per-row path (EnvIndependent semantics: per-env write heads move
    independently)."""
    a = DeviceReplayCache(CAP, N_ENVS)
    b = DeviceReplayCache(CAP, N_ENVS)
    rows = [_row(t, envs=[0, 2]) for t in range(5)]
    for r in rows:
        a.add(r, indices=[0, 2])
    b.add({k: np.concatenate([r[k] for r in rows], axis=0) for k in rows[0]}, indices=[0, 2])
    assert np.array_equal(np.asarray(a._pos), np.asarray(b._pos))
    assert np.array_equal(np.asarray(a._filled), np.asarray(b._filled))
    for k in a._bufs:
        assert np.array_equal(np.asarray(a._bufs[k]), np.asarray(b._bufs[k])), k


# ------------------------------------------- window read vs the plain index form
# The samplers read each window as contiguous slices along the capacity
# axis; what they return must be ``ring[(start + arange(L)) % cap, env]``.
READ_CAP, READ_L = 24, 6


def _plain_windows(ring, starts, envs, seq_len):
    """(cap, n_envs, *feat) numpy ring -> (L, B, *feat)."""
    t_idx = (np.asarray(starts)[None, :] + np.arange(seq_len)[:, None]) % ring.shape[0]
    return ring[t_idx, np.asarray(envs)[None, :]]


def _random_ring(cap, n_envs, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "rgb": rng.integers(0, 256, (cap, n_envs, 4, 4, 3), dtype=np.uint8),
        "actions": rng.normal(size=(cap, n_envs, 5)).astype(np.float32),
        "rewards": rng.normal(size=(cap, n_envs, 1)).astype(np.float32),
    }


@pytest.mark.parametrize("n_envs", [1, 4])
def test_window_read_equals_plain_index_form_at_every_wrap_split(n_envs):
    """Starts 0, cap - L (the last that does not wrap) and cap - L + 1 ..
    cap - 1 (every split of a window between the ring's end and its head)."""
    from sheeprl_tpu.data.device_buffer import _sample

    ring = _random_ring(READ_CAP, n_envs)
    starts = np.asarray([0] + list(range(READ_CAP - READ_L, READ_CAP)), np.int32)
    envs = (np.arange(len(starts)) % n_envs).astype(np.int32)
    got = _sample({k: jax.numpy.asarray(v) for k, v in ring.items()}, starts, envs, seq_len=READ_L)
    for k, v in ring.items():
        assert got[k].dtype == v.dtype and got[k].shape == (READ_L, len(starts)) + v.shape[2:]
        np.testing.assert_array_equal(np.asarray(got[k]), _plain_windows(v, starts, envs, READ_L), err_msg=k)


def _filled_cache(kind, n_envs, total):
    """A cache of ``kind`` holding ``total`` random rows per env (the ring
    wraps when total > READ_CAP), and the runtime of a sharded one."""
    from sheeprl_tpu.data.device_buffer import ShardedDeviceReplayCache
    from sheeprl_tpu.parallel.mesh import MeshRuntime

    prioritized = kind.endswith("prioritized")
    if kind.startswith("sharded"):
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-virtual-device mesh")
        rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
        cache = ShardedDeviceReplayCache(READ_CAP, n_envs, rt, prioritized=prioritized)
    else:
        cache = DeviceReplayCache(READ_CAP, n_envs, prioritized=prioritized)
    rows = _random_ring(total, n_envs, seed=total)
    for t in range(total):
        cache.add({k: v[t : t + 1] for k, v in rows.items()})
    return cache


def _own_draw(cache, kind, n_samples, batch, key):
    """Per gradient step the (starts, GLOBAL envs) that ``sample`` /
    ``sample_per`` read for ``key``, from the module's own draw programs."""
    import jax.numpy as jnp

    from sheeprl_tpu.data import device_buffer as db

    geom = dict(n_samples=n_samples, batch_size=batch, seq_len=READ_L, cap=cache.capacity, n_envs=cache.n_envs)
    heads = (jnp.asarray(cache._pos), jnp.asarray(cache._filled))
    if kind == "plain":
        starts, envs = db._sample_draw(key, *heads, **geom)
    elif kind == "prioritized":
        starts, envs, _ = db._sample_draw_prioritized(
            cache._tree.tree, key, *heads, jnp.float32(0.0), depth=cache._tree.depth, **geom
        )
    else:
        n_dev = cache._n_dev
        n_local = cache.n_envs // n_dev
        if kind == "sharded":
            starts, envs = cache._build_sharded_draw(n_samples, batch, READ_L)(key, *heads)
            # column c of the global batch was drawn by device c // (batch / n_dev) among its own envs
            owner = np.arange(batch) // (batch // n_dev)
            envs = [owner * n_local + np.asarray(e) for e in envs]
        else:  # every shard proposes all `batch` draws; the owner's is the one read
            draw = cache._build_sharded_per(n_samples, batch, READ_L, ())
            (rows, envs_l, own), _ = draw(cache._bufs, cache._tree.trees, key, *heads, jnp.float32(0.0))
            starts, envs = [], []
            for r, e, o in zip(rows, envs_l, own):
                o = np.asarray(o).reshape(n_dev, batch)
                assert (o.sum(0) == 1).all()  # one owner a draw
                d = o.argmax(0)
                col = np.arange(batch)
                starts.append(np.asarray(r).reshape(n_dev, batch)[d, col])
                envs.append(d * n_local + np.asarray(e).reshape(n_dev, batch)[d, col])
    return [np.asarray(s) for s in starts], [np.asarray(e) for e in envs]


@pytest.mark.parametrize("n_samples", [1, 3])
@pytest.mark.parametrize("kind", ["plain", "prioritized", "sharded", "sharded_prioritized"])
def test_sample_equals_plain_index_form_of_its_own_draw(kind, n_samples):
    """Same key, same ring: ``sample`` / ``sample_per`` return the windows
    that the plain index form reads at the module's own (start, env) draw."""
    n_envs, batch = (8, 16) if kind.startswith("sharded") else (4, 8)
    cache = _filled_cache(kind, n_envs, total=2 * READ_CAP + 5)  # full ring, write head at 5
    ring = {k: np.asarray(v) for k, v in cache._bufs.items()}
    key = jax.random.PRNGKey(7 + n_samples)
    starts, envs = _own_draw(cache, kind, n_samples, batch, key)
    if kind.endswith("prioritized"):
        batches = cache.sample_per(n_samples, batch, READ_L, key, beta=0.0)
    else:
        batches = cache.sample(n_samples, batch, READ_L, key)
    assert len(batches) == n_samples
    assert any(s.max() > READ_CAP - READ_L for s in starts)  # some window wraps
    for b, s, e in zip(batches, starts, envs):
        assert set(b) == set(ring)
        for k, v in ring.items():
            np.testing.assert_array_equal(np.asarray(b[k]), _plain_windows(v, s, e, READ_L), err_msg=k)

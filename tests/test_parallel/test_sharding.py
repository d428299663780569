"""Pod-scale sharded training (parallel/sharding.py): mesh-shape
resolution, the canonical layout specs, DP/FSDP guarded updates on the
8-virtual-device CPU mesh with flat post-warmup compile counters, the
shard-aware prioritized replay parity with a single-device sum-tree, and
the regression guards for the deleted uniform/CPU fallbacks."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from sheeprl_tpu.parallel import MeshRuntime, ShardingLayout, parse_mesh_shape
from sheeprl_tpu.parallel.sharding import BATCH_AXES


def _need8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-virtual-device mesh")


# ------------------------------------------------------------- mesh shape
def test_parse_mesh_shape_auto_follows_strategy():
    assert parse_mesh_shape("auto", 8, "dp") == (8, 1)
    assert parse_mesh_shape(None, 8, "auto") == (8, 1)
    # fsdp auto: every device on the fsdp axis — the pre-2-D ZeRO layout
    # (params and batch sharded over the same devices)
    assert parse_mesh_shape("auto", 8, "fsdp") == (1, 8)
    assert parse_mesh_shape("auto", 1, "fsdp") == (1, 1)


def test_parse_mesh_shape_explicit_and_inferred():
    assert parse_mesh_shape("4x2", 8) == (4, 2)
    assert parse_mesh_shape("2,4", 8) == (2, 4)
    assert parse_mesh_shape([8, 1], 8) == (8, 1)
    assert parse_mesh_shape((-1, 2), 8) == (4, 2)
    assert parse_mesh_shape([2, -1], 8) == (2, 4)
    with pytest.raises(ValueError, match="does not tile"):
        parse_mesh_shape([3, 2], 8)
    with pytest.raises(ValueError, match="two entries"):
        parse_mesh_shape([8], 8)
    with pytest.raises(ValueError, match="at most one"):
        parse_mesh_shape([-1, -1], 8)


def test_layout_specs_and_shard_bytes():
    _need8()
    rt = MeshRuntime(devices=8, strategy="fsdp", accelerator="cpu", mesh_shape="4x2").launch()
    layout = rt.layout
    assert (rt.data_size, rt.fsdp_size) == (4, 2)
    assert rt.world_size == 8  # batch shards cover BOTH axes
    assert layout.batch_spec(0) == P(BATCH_AXES)
    assert layout.batch_spec(1) == P(None, BATCH_AXES)
    # largest fsdp-divisible dim is sharded; scalars/indivisible replicated
    assert layout.param_spec((16, 32)) == P(None, "fsdp")
    assert layout.param_spec((64, 32)) == P("fsdp", None)
    assert layout.param_spec((3,)) == P()
    assert layout.param_spec(()) == P()
    params = {"w": jnp.zeros((16, 32)), "b": jnp.zeros((3,))}
    # w shards /2 over fsdp, b stays whole
    assert layout.param_shard_bytes(params) == (16 * 32 // 2 + 3) * 4
    d = layout.describe()
    assert d["axes"] == {"data": 4, "fsdp": 2}


def test_flat_rank_matches_batch_split_order():
    """The flat device index inside a shard_map body must match the order
    the flattened batch spec splits arrays in (shard i of
    P(("data", "fsdp")) lands on flat device i)."""
    _need8()
    rt = MeshRuntime(devices=8, strategy="fsdp", accelerator="cpu", mesh_shape="4x2").launch()
    layout = rt.layout
    fn = jax.shard_map(
        lambda x: x * 0 + layout.flat_rank(),
        mesh=rt.mesh,
        in_specs=(layout.batch_spec(0),),
        out_specs=layout.batch_spec(0),
        check_vma=False,
    )
    out = np.asarray(jax.jit(fn)(jnp.zeros(8, jnp.int32)))
    np.testing.assert_array_equal(out, np.arange(8))


def test_constrain_batch_pins_flattened_batch_axes():
    _need8()
    rt = MeshRuntime(devices=8, strategy="fsdp", accelerator="cpu", mesh_shape="4x2").launch()
    out = jax.jit(lambda x: rt.layout.constrain_batch({"x": x * 2})["x"])(jnp.arange(16.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(16.0) * 2)
    assert out.sharding.spec == P(BATCH_AXES)


def test_explicit_mesh_shape_fsdp_placement():
    _need8()
    rt = MeshRuntime(devices=8, strategy="fsdp", accelerator="cpu", mesh_shape=[4, 2]).launch()
    placed = rt.replicate({"w": jnp.ones((8, 16)), "s": jnp.float32(1.0)})
    assert placed["w"].sharding.spec == P(None, "fsdp")
    assert placed["s"].sharding.spec == P()
    batch = rt.shard_batch({"x": np.zeros((16, 4), np.float32)})
    assert batch["x"].sharding.spec == P(BATCH_AXES)


# ----------------------------------------------- guarded updates on the mesh
def _toy_problem(rt):
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=(16, 32)), jnp.float32), "b": jnp.zeros((32,))}
    tx = optax.adam(1e-2)

    def update(params, opt_state, batch):
        def loss_fn(p):
            pred = batch["x"] @ p["w"] + p["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, {"Loss/mse": loss, "Grads/agent": optax.global_norm(grads)}

    batch = {
        "x": rng.normal(size=(32, 16)).astype(np.float32),
        "y": rng.normal(size=(32, 32)).astype(np.float32),
    }
    return params, tx, update, batch


@pytest.mark.parametrize("strategy,mesh_shape", [("dp", "auto"), ("fsdp", "auto"), ("fsdp", "4x2")])
def test_guarded_update_dp_fsdp_smoke_flat_compiles(strategy, mesh_shape):
    """8-device DP and FSDP guarded updates: numerics match the 1-device
    update and the post-warmup compile counter stays FLAT (layout
    constraints and collectives are part of the one traced program)."""
    _need8()
    from sheeprl_tpu.obs import RecompileMonitor
    from sheeprl_tpu.resilience.sentinel import guard_update

    rt = MeshRuntime(devices=8, strategy=strategy, accelerator="cpu", mesh_shape=mesh_shape).launch()
    params, tx, update, batch = _toy_problem(rt)
    cfg = types.SimpleNamespace()  # no algo node -> sentinel defaults (off)
    guarded = guard_update(rt, update, cfg, n_state=2, donate_argnums=())

    p = rt.replicate(params)
    o = rt.replicate(tx.init(params))
    b = rt.shard_batch(batch)
    monitor = RecompileMonitor(name="sharding-test", warn=False).install()
    try:
        for i in range(4):
            p, o, metrics = guarded(p, o, b)
            if i == 0:
                warm = monitor.snapshot()["total"]
        assert monitor.snapshot()["total"] == warm, "post-warmup retrace in the guarded update"
    finally:
        monitor.uninstall()

    if strategy == "fsdp":
        # ZeRO layout held through the boundary constraint
        assert p["w"].sharding.spec == rt.layout.param_spec(p["w"].shape)

    # same math on one device
    rt1 = MeshRuntime(devices=1, accelerator="cpu").launch()
    params1, tx1, update1, _ = _toy_problem(rt1)
    g1 = guard_update(rt1, update1, cfg, n_state=2, donate_argnums=())
    p1, o1 = rt1.replicate(params1), rt1.replicate(tx1.init(params1))
    b1 = rt1.shard_batch(batch)
    for _ in range(4):
        p1, o1, m1 = g1(p1, o1, b1)
    np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(p1["w"]), rtol=2e-5, atol=1e-6)


def test_sentinel_state_replicated_on_mesh():
    """With the sentinel armed on a multi-device mesh, the verdict state
    must come out of every dispatch fully replicated (the host polls it;
    a sharded layout would make the poll a cross-device fetch)."""
    _need8()
    from sheeprl_tpu.resilience.sentinel import guard_update

    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    params, tx, update, batch = _toy_problem(rt)
    node = {"enabled": True, "warmup": 2}

    class _Cfg:
        class algo:
            @staticmethod
            def get(k, d=None):
                return {"sentinel": node}.get(k, d)

    cfg = _Cfg()
    guarded = guard_update(rt, update, cfg, n_state=2, donate_argnums=())
    p, o = rt.replicate(params), rt.replicate(tx.init(params))
    b = rt.shard_batch(batch)
    p, o, _ = guarded(p, o, b)
    st = guarded.health.device_state
    for leaf in st:
        assert leaf.sharding.is_fully_replicated, leaf.sharding
    # and the guarded result is healthy
    assert bool(jax.device_get(st.last_ok))


# ------------------------------------------------- sharded prioritized replay
def _filled_caches(cap=16, n_envs=8, steps=12, prioritized=True):
    from sheeprl_tpu.data.device_buffer import DeviceReplayCache, ShardedDeviceReplayCache

    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    sharded = ShardedDeviceReplayCache(
        cap, n_envs, rt, prioritized=prioritized, per_alpha=1.0, per_eps=0.0
    )
    single = DeviceReplayCache(cap, n_envs, prioritized=prioritized, per_alpha=1.0, per_eps=0.0)
    rng = np.random.default_rng(1)
    for t in range(steps):
        row = {
            "obs": rng.normal(size=(1, n_envs, 3)).astype(np.float32),
            "rewards": np.full((1, n_envs, 1), t, np.float32),
        }
        sharded.add(row)
        single.add(row)
    return rt, sharded, single, rng


def test_sharded_per_marginals_match_single_device_tree():
    """The parity property the sharded design rests on: with identical
    priorities, the 8-device per-shard-sub-tree sampler's distribution
    matches the single global sum-tree's marginals (one psum'd total-mass
    reduction per draw, each draw owned by exactly one shard)."""
    _need8()
    cap, n_envs = 16, 8
    rt, sharded, single, rng = _filled_caches(cap, n_envs)
    n = cap * n_envs
    written = np.zeros((cap, n_envs), np.float32)
    written[:12] = 1.0
    pri = (rng.uniform(0.1, 3.0, size=(cap, n_envs)).astype(np.float32) * written).reshape(-1)
    idx = np.arange(n)
    sharded._tree.set_priorities(idx, pri)
    single._tree.set_priorities(idx, pri)
    assert sharded._tree.total == pytest.approx(single._tree.total, rel=1e-5)

    draws_s, draws_1 = [], []
    for i in range(25):
        _, lv_s = sharded.sample_transitions_per(
            4, 64, jax.random.PRNGKey(100 + i), beta=0.0, sample_next_obs=True, obs_keys=("obs",)
        )
        _, lv_1 = single.sample_transitions_per(
            4, 64, jax.random.PRNGKey(500 + i), beta=0.0, sample_next_obs=True, obs_keys=("obs",)
        )
        draws_s.append(np.asarray(lv_s).reshape(-1))
        draws_1.append(np.asarray(lv_1).reshape(-1))
    emp_s = np.bincount(np.concatenate(draws_s), minlength=n).astype(np.float64)
    emp_1 = np.bincount(np.concatenate(draws_1), minlength=n).astype(np.float64)
    emp_s /= emp_s.sum()
    emp_1 /= emp_1.sum()
    # both must match the analytic proportional marginals (head rows of
    # each env are excluded by validity on both paths)
    head = (sharded._pos - 1) % cap
    pw = pri.copy().reshape(cap, n_envs)
    pw[head, np.arange(n_envs)] = 0.0
    pw = pw.reshape(-1)
    pw /= pw.sum()
    assert np.abs(emp_s - pw).max() < 0.008
    assert np.abs(emp_s - emp_1).max() < 0.012


def test_sharded_per_update_priorities_roundtrip_and_state():
    """``update_priorities`` through the sharded tree: written values read
    back exactly, the running max stays global, and the checkpoint state
    round-trips in single-device leaf order (sharded and single-device
    runs can resume each other)."""
    _need8()
    from sheeprl_tpu.replay.priority_tree import PriorityTree

    cap, n_envs = 16, 8
    rt, sharded, single, rng = _filled_caches(cap, n_envs)
    n = cap * n_envs
    idx = rng.choice(n, size=40, replace=False).astype(np.int32)
    td = np.abs(rng.normal(size=40)).astype(np.float32)
    sharded.update_priorities(idx, td)
    single.update_priorities(idx, td)
    np.testing.assert_allclose(
        np.asarray(sharded._tree.priorities(idx)),
        np.asarray(single._tree.priorities(idx)),
        rtol=1e-5,
    )
    assert float(sharded._tree.max_priority) == pytest.approx(float(single._tree.max_priority))
    sd = sharded.priority_state()
    np.testing.assert_allclose(sd["leaves"], single.priority_state()["leaves"], rtol=1e-5)
    # load the sharded state into a fresh single-device tree and back
    t1 = PriorityTree(n, alpha=1.0, eps=0.0)
    t1.load_state_dict(sd)
    np.testing.assert_allclose(
        np.asarray(t1.priorities(np.arange(n))), sd["leaves"], rtol=1e-6
    )
    sharded.load_priority_state(single.priority_state())
    np.testing.assert_allclose(
        np.asarray(sharded._tree.priorities(np.arange(n))), sd["leaves"], rtol=1e-5
    )


def test_sharded_per_sequence_windows_contiguous():
    _need8()
    cap, n_envs = 16, 8
    rt, sharded, _, _ = _filled_caches(cap, n_envs)
    out = sharded.sample_per(2, 16, 4, jax.random.PRNGKey(9), beta=0.0)
    assert out[0]["obs"].shape == (4, 16, 3)
    rw = np.asarray(out[0]["rewards"])[:, :, 0]
    assert set(np.unique(rw[1:] - rw[:-1])) <= {1.0}  # windows advance one row per step


def test_sharded_per_is_weights_scale_down_only():
    _need8()
    rt, sharded, _, rng = _filled_caches()
    out, _ = sharded.sample_transitions_per(
        2, 32, jax.random.PRNGKey(3), beta=0.7, sample_next_obs=True, obs_keys=("obs",)
    )
    w = np.asarray(out["is_weights"])
    assert w.shape == (2, 32, 1)
    assert w.max() == pytest.approx(1.0)
    assert (w > 0).all()


# ----------------------------------------------------- deleted fallbacks
def test_uniform_fallback_notice_cannot_fire(capsys):
    """The PR-5 'sampling stays uniform' fallback is DELETED: a
    multi-device prioritized run gets the sharded cache (with sub-trees),
    and the notice string is gone from the module entirely."""
    _need8()
    import inspect

    import sheeprl_tpu.data.device_buffer as db

    assert "sampling stays uniform" not in inspect.getsource(db)

    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    cfg = types.SimpleNamespace(buffer={"device_cache": True, "prioritized": True})
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer

    rb = EnvIndependentReplayBuffer(16, n_envs=8)
    cache = db.maybe_create_for(cfg, rt, rb)
    out = capsys.readouterr().out
    assert type(cache) is db.ShardedDeviceReplayCache
    assert cache.prioritized
    assert "prioritized per-shard sum-trees" in out
    assert "uniform" not in out


def test_prioritized_multi_device_blockers_raise_not_downgrade():
    """PER with an unbuildable sharded cache is a loud config error — not
    a silent switch to a different (uniform) sampling distribution."""
    _need8()
    import sheeprl_tpu.data.device_buffer as db
    from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer

    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    cfg = types.SimpleNamespace(buffer={"device_cache": "auto", "prioritized": True})
    rb = EnvIndependentReplayBuffer(16, n_envs=6)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="prioritized"):
        db.maybe_create_for(cfg, rt, rb)


def test_prioritized_with_cache_off_is_config_error():
    """The old CPU-forcing/ignore path: device_cache=off + prioritized now
    refuses instead of silently sampling uniform."""
    import sheeprl_tpu.data.device_buffer as db

    rt = MeshRuntime(devices=1, accelerator="cpu").launch()
    cfg = types.SimpleNamespace(buffer={"device_cache": False, "prioritized": True})
    with pytest.raises(ValueError, match="prioritized"):
        db.DeviceReplayCache.maybe_create(cfg, rt, capacity=16, n_envs=2)


def test_sharded_uniform_transitions_stratified_marginals():
    """The sharded flat-transition uniform sampler (SAC family multi-device
    path): stratified per-shard draws, output sharded over the batch axes,
    row marginals uniform over the valid window."""
    _need8()
    rt, sharded, _, _ = _filled_caches(prioritized=False)
    out = sharded.sample_transitions(2, 64, jax.random.PRNGKey(5), sample_next_obs=True, obs_keys=("obs",))
    assert out["obs"].shape == (2, 64, 3)
    assert out["obs"].sharding.spec == P(None, BATCH_AXES)
    rews = np.concatenate(
        [
            np.asarray(
                sharded.sample_transitions(
                    2, 64, jax.random.PRNGKey(50 + i), sample_next_obs=True, obs_keys=("obs",)
                )["rewards"]
            ).reshape(-1)
            for i in range(20)
        ]
    )
    # rows 0..10 valid (head row excluded when next-obs gathered)
    counts = np.bincount(rews.astype(np.int64), minlength=12)
    assert counts[11] == 0  # the newest row's successor is stale
    frac = counts[:11] / counts.sum()
    assert np.abs(frac - 1 / 11).max() < 0.02


# ------------------------------------------------------------- e2e smokes
def _cli(args):
    from sheeprl_tpu.cli import run

    run(args)


def _e2e_args(tmp_path, name):
    return [
        "env=dummy",
        "env.num_envs=8",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.accelerator=cpu",
        "fabric.devices=8",
        "metric.log_level=1",
        f"metric.logger.root_dir={tmp_path}/logs",
        "checkpoint.save_last=True",
        "buffer.memmap=False",
        "seed=0",
        f"root_dir={tmp_path}/{name}",
    ]


def test_e2e_a2c_dp_8_devices(tmp_path):
    """8-device DP through the real CLI: the shard_map DDP core over the
    flattened batch axes, guard_update boundary, telemetry mesh key."""
    _need8()
    _cli(
        _e2e_args(tmp_path, "a2c")
        + [
            "dry_run=True",
            "exp=a2c",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=4",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.mlp_keys.encoder=[state]",
        ]
    )
    import glob
    import json

    tele = glob.glob(f"{tmp_path}/a2c/**/telemetry.jsonl", recursive=True)
    assert tele
    recs = [json.loads(line) for line in open(tele[0])]
    mesh_recs = [r["mesh"] for r in recs if "mesh" in r]
    assert mesh_recs, "telemetry must carry the mesh key"
    assert mesh_recs[-1]["axes"] == {"data": 8, "fsdp": 1}
    assert mesh_recs[-1]["param_bytes_total"] > 0


def test_e2e_sac_fsdp_sharded_per_8_devices(tmp_path):
    """The headline config this PR unlocks: 8-device FSDP training with
    buffer.prioritized=true running on the env-sharded device cache —
    no CPU forcing, no uniform fallback — through the real CLI."""
    _need8()
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        _cli(
            _e2e_args(tmp_path, "sac")
            + [
                "dry_run=False",
                "algo.total_steps=64",
                "exp=sac",
                "env.id=dummy_continuous",
                "fabric.strategy=fsdp",
                "algo.per_rank_batch_size=8",
                "algo.hidden_size=8",
                "algo.learning_starts=8",
                "algo.mlp_keys.encoder=[state]",
                "buffer.prioritized=True",
                "buffer.device_cache=True",
            ]
        )
    out = buf.getvalue()
    assert "env-sharded replay window enabled" in out
    assert "prioritized per-shard sum-trees" in out
    assert "uniform" not in out


def test_e2e_decoupled_tcp_trainer_mesh_8_devices(tmp_path):
    """Multi-host-shaped decoupled smoke: players talk to the trainer over
    the tcp transport (the exact path a cross-host run uses via
    algo.tcp_host/tcp_port) while the trainer's update runs on the
    8-device mesh — rollout shards in over tcp, params broadcasts out,
    the jitted update sharded over (data, fsdp)."""
    _need8()
    _cli(
        _e2e_args(tmp_path, "ppodec")
        + [
            "dry_run=True",
            "exp=ppo_decoupled",
            "algo.decoupled_transport=tcp",
            "algo.rollout_steps=8",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=2",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.mlp_keys.encoder=[state]",
        ]
    )
    import glob

    ckpts = glob.glob(f"{tmp_path}/ppodec/**/ckpt_*.ckpt", recursive=True)
    assert len(ckpts) > 0

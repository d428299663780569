import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sheeprl_tpu.parallel import MeshRuntime


def test_launch_auto_single_device():
    rt = MeshRuntime(devices=1, accelerator="cpu").launch()
    assert rt.world_size == 1
    assert rt.is_global_zero


def test_launch_8_device_dp_mesh():
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    assert rt.world_size == 8
    # 2-D mesh, auto shape: dp lays every device on the data axis
    assert rt.mesh.axis_names == ("data", "fsdp")
    assert rt.data_size == 8 and rt.fsdp_size == 1


def test_fsdp_param_sharding_and_train_step():
    """strategy="fsdp": replicate() shards params over the data axis
    (ZeRO-3 layout) and a jitted SGD step still produces the same result
    as the replicated-DP layout."""
    import optax

    rt = MeshRuntime(devices=8, strategy="fsdp", accelerator="cpu").launch()
    rng = np.random.default_rng(0)
    params = {
        "w": jnp.asarray(rng.normal(size=(16, 32)), jnp.float32),  # both dims % 8 == 0
        "b": jnp.asarray(rng.normal(size=(3,)), jnp.float32),  # indivisible
        "s": jnp.float32(2.0),  # scalar
    }
    placed = rt.replicate(params)
    # the LARGEST divisible dim is sharded (dim 1, 32 > 16) — avoids tiny
    # shards on small leading axes like conv spatial dims; auto mesh_shape
    # under fsdp puts every device on the fsdp axis
    assert rt.fsdp_size == 8
    assert placed["w"].sharding.spec == jax.sharding.PartitionSpec(None, "fsdp")
    assert placed["b"].sharding.spec == jax.sharding.PartitionSpec()

    tx = optax.sgd(0.1)
    opt_state = rt.replicate(tx.init(params))
    batch = rt.shard_batch({"x": np.asarray(rng.normal(size=(16, 16)), np.float32)})

    def step(p, o, b):
        def loss_fn(p_):
            y = b["x"] @ p_["w"] + p_["s"]
            return jnp.mean(y**2) + jnp.sum(p_["b"] ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    jstep = rt.setup_step(step)
    new_params, opt_state, loss = jstep(placed, opt_state, batch)
    assert np.isfinite(float(loss))

    # same math on a plain replicated DP mesh gives identical numbers
    rt_dp = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    p_dp = rt_dp.replicate(params)
    o_dp = rt_dp.replicate(tx.init(params))
    np_dp, _, loss_dp = rt_dp.setup_step(step)(p_dp, o_dp, batch)
    np.testing.assert_allclose(float(loss), float(loss_dp), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(new_params["w"]), np.asarray(np_dp["w"]), rtol=1e-5, atol=1e-6
    )


def test_strategy_validation():
    with pytest.raises(ValueError):
        MeshRuntime(strategy="pipeline")


def test_devices_minus_one_uses_all():
    rt = MeshRuntime(devices=-1, accelerator="cpu").launch()
    assert rt.device_count == len(jax.devices("cpu"))


def test_too_many_devices_raises():
    with pytest.raises(RuntimeError):
        MeshRuntime(devices=999, accelerator="cpu").launch()


def test_precision_policy():
    rt = MeshRuntime(accelerator="cpu", precision="bf16-mixed")
    assert rt.compute_dtype == jnp.bfloat16
    assert rt.param_dtype == jnp.float32
    rt2 = MeshRuntime(accelerator="cpu", precision="bf16-true")
    assert rt2.param_dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        MeshRuntime(precision="fp8")


def test_seed_and_keys():
    rt = MeshRuntime(accelerator="cpu").launch()
    k1 = rt.seed_everything(42)
    a = rt.next_key()
    b = rt.next_key()
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    rt.seed_everything(42)
    a2 = rt.next_key()
    np.testing.assert_array_equal(np.asarray(a), np.asarray(a2))


def test_shard_batch_and_psum_semantics():
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    batch = {"x": np.arange(16, dtype=np.float32).reshape(16, 1)}
    sharded = rt.shard_batch(batch)
    # batches always shard over the flattened (data, fsdp) axes
    assert sharded["x"].sharding.spec == jax.sharding.PartitionSpec(("data", "fsdp"))

    # a jitted global mean over the sharded batch == DDP-style all-reduce
    step = rt.setup_step(lambda b: b["x"].mean())
    got = float(step(sharded))
    assert got == pytest.approx(np.arange(16).mean())


def test_grad_step_on_mesh_matches_single_device():
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    params = {"w": jnp.ones((1,))}
    x = np.arange(16, dtype=np.float32).reshape(16, 1)

    def loss_fn(p, batch):
        pred = batch @ p["w"][None, :].T
        return ((pred - 2.0) ** 2).mean()

    grads_fn = rt.setup_step(jax.grad(loss_fn))
    g_mesh = grads_fn(rt.replicate(params), rt.shard_batch(x))
    g_single = jax.grad(loss_fn)(params, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(g_mesh["w"]), np.asarray(g_single["w"]), rtol=1e-5)


def test_single_device_view():
    rt = MeshRuntime(devices=8, strategy="dp", accelerator="cpu").launch()
    single = rt.single_device()
    assert single.world_size == 1
    assert single.precision == rt.precision


class _FakeChip:
    platform = "tpu"
    id = 0


@pytest.mark.parametrize(
    "choice,on_chip,cpu_backend,bound_multiple,expect_cpu,why",
    [
        ("accelerator", True, True, 0, False, "player_device=accelerator"),
        ("auto", False, True, 0, False, "already the host CPU"),
        ("cpu", False, True, 0, False, "already the host CPU"),
        ("auto", True, True, 0, True, "beside the local chip"),
        ("cpu", True, True, 0, True, "beside the local chip"),
        ("auto", True, False, 0, False, "no host CPU backend"),
        # auto beside a chip goes by the bytes of the player's weights
        ("auto", True, True, 0.999, True, "beside the local chip"),
        ("auto", True, True, 1, False, "shares the learner's arrays"),
        ("auto", True, True, 25, False, "shares the learner's arrays"),
        ("cpu", True, True, 25, True, "beside the local chip"),
        ("auto", False, True, 25, False, "already the host CPU"),
        ("auto", True, False, 25, False, "no host CPU backend"),
    ],
)
def test_player_device_decision_table(monkeypatch, choice, on_chip, cpu_backend, bound_multiple, expect_cpu, why):
    """An explicit ``accelerator``, training already on the CPU, no CPU
    backend, and beside a local chip: ``cpu`` the host whatever the player
    weighs, ``auto`` the host below ``PLAYER_ON_CHIP_BYTES`` and the chip from
    it up."""
    from sheeprl_tpu.parallel.mesh import PLAYER_ON_CHIP_BYTES

    rt = MeshRuntime(devices=1, accelerator="cpu").launch()
    fake_cpu = object()

    def fake_local_devices(backend=None):
        if not cpu_backend:
            raise RuntimeError("Unknown backend cpu")
        return [fake_cpu]

    monkeypatch.setattr("jax.local_devices", fake_local_devices)
    if on_chip:
        monkeypatch.setattr(type(rt), "device", property(lambda self: _FakeChip()))
    device, reason = rt._player_device_decision(choice, int(bound_multiple * PLAYER_ON_CHIP_BYTES))
    assert (device is fake_cpu) == expect_cpu and (device is None) != expect_cpu
    assert why in reason


@pytest.mark.parametrize("choice,floats", [("auto", 1 << 10), ("auto", 1 << 26), ("cpu", 1 << 26)])
def test_player_device_reason_names_the_bytes_and_the_bound(monkeypatch, capsys, choice, floats):
    """``Player device: <where> (<why>)`` is what a run prints and what
    chip_smoke.py parses: beside a chip the why carries both numbers."""
    from sheeprl_tpu.parallel.mesh import PLAYER_ON_CHIP_BYTES

    rt = MeshRuntime(devices=1, accelerator="cpu", player_device=choice).launch()
    cpu = jax.devices("cpu")[0]
    monkeypatch.setattr(type(rt), "device", property(lambda self: _FakeChip()))
    monkeypatch.delenv("SHEEPRL_PLAYER_DEVICE", raising=False)
    params = {"w": jax.ShapeDtypeStruct((floats,), np.float32), "b": np.zeros((3,), np.float16)}
    nbytes = 4 * floats + 6
    device = rt.player_device(params)
    assert (device is None) == (choice == "auto" and nbytes >= PLAYER_ON_CHIP_BYTES)
    assert device is None or device == cpu
    line = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("Player device: ")]
    assert len(line) == 1
    assert f"{nbytes} B of player weights" in line[0] and f"bound {PLAYER_ON_CHIP_BYTES} B" in line[0]
    assert line[0].startswith("Player device: training device (" if device is None else f"Player device: {cpu} (")


def test_requested_accelerator_that_is_absent_raises():
    """``fabric.accelerator=tpu`` on a machine without one must not carry
    on on the CPU and exit 0."""
    with pytest.raises(RuntimeError, match="tpu"):
        MeshRuntime(devices=1, accelerator="tpu").launch()


# ------------------------------------------------------ compile-cache placement
@pytest.fixture
def cache_config():
    """Restore jax's cache directory after a test that moves it."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, cache_config):
    from sheeprl_tpu.parallel import mesh

    jax.config.update("jax_compilation_cache_dir", "/somewhere/jax/read/from/the/variable")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    assert mesh.configure_compilation_cache() == "/placed/from/outside"
    # the code set nothing: what jax holds is untouched
    assert jax.config.jax_compilation_cache_dir == "/somewhere/jax/read/from/the/variable"


def test_cache_dir_defaults_to_the_checkout_and_never_moves(monkeypatch, cache_config):
    import os

    from sheeprl_tpu.parallel import mesh

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    expected = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", None)
    MeshRuntime(devices=1, accelerator="cpu").launch()
    first = jax.config.jax_compilation_cache_dir
    MeshRuntime(devices=1, accelerator="cpu").launch()
    assert first == jax.config.jax_compilation_cache_dir == expected
    assert mesh.configure_compilation_cache() == expected


def test_cache_key_covers_the_metadata(monkeypatch, cache_config):
    """A profile must show the ``jax.named_scope`` names of the code that
    ran: the cache may not serve an executable compiled before a rename."""
    from sheeprl_tpu.parallel import mesh

    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    mesh.configure_compilation_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True

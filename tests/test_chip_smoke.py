"""The CPU rehearsals of ``chip_smoke.py`` (on-chip-measurement guide §2):
the same code at tiny widths, on one CPU device and on four virtual ones.
They find wrong paths, arguments, control flow and sharding rules before a
chip call is spent on them — and they must never report a pass."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (imports nothing that touches a backend)


def _smoke(args, n_devices):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
        cwd=REPO,
    )
    # every line of its standard output is one JSON object
    lines = [json.loads(line) for line in proc.stdout.splitlines() if line.strip()]
    assert lines, proc.stderr[-3000:]
    return proc, lines


def _no_tpu(count):
    return {"ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": count}, "reason": "no tpu"}


def test_without_a_tpu_it_fails_at_once():
    proc, lines = _smoke([], 1)
    assert proc.returncode != 0
    assert lines == [_no_tpu(1)]


def test_tiny_rehearsal_runs_every_phase_and_never_passes():
    proc, lines = _smoke(["--tiny"], 1)
    assert proc.returncode != 0
    assert lines[-1] == _no_tpu(1)
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == ["device", "train", "kernel:fused", "kernel:fused_seq", "serve", "totals"]
    assert all(line["ok"] for line in phases.values()), proc.stdout
    train = phases["train"]
    assert train["gradient_steps"] >= 32
    assert train["replay_cache"]["admitted"] and train["post_warmup_compiles"] == 0
    assert train["checkpoint_reloaded_equal"]
    assert phases["serve"]["requests"] == 64


def test_four_device_rehearsal_spreads_batch_ring_and_params():
    proc, lines = _smoke(["--tiny", "--chips", "4"], 4)
    assert proc.returncode != 0
    assert lines[-1] == _no_tpu(4)
    phases = {line["phase"]: line for line in lines[:-1]}
    assert list(phases) == ["device", "mesh:one_chip", "mesh:dp4", "mesh:fsdp4", "totals"]
    assert all(line["ok"] for line in phases.values()), proc.stdout
    for name in ("mesh:dp4", "mesh:fsdp4"):
        assert len(set(phases[name]["devices"])) == 4
        assert phases[name]["ring_shards"] == phases[name]["batch_shards"] == 4
        assert phases[name]["worst_rel_loss_diff"] <= phases[name]["loss_rtol"]
    fsdp = phases["mesh:fsdp4"]["largest_param"]
    assert np.prod(fsdp["per_device"]) * 4 == np.prod(fsdp["shape"])


def test_placement_check_fires_for_a_ring_on_one_device():
    """Code that has only seen one chip may put everything on devices[0]:
    the four-chip phase must notice."""
    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs four virtual devices")
    mesh = jax.make_mesh((4,), ("data",), devices=devices)
    ring = np.zeros((8, 4, 3), np.uint8)
    sharded = jax.device_put(ring, NamedSharding(mesh, P(None, "data")))
    assert chip_smoke.spread("ring", sharded, devices) == 4
    on_one = jax.device_put(ring, devices[0])
    with pytest.raises(chip_smoke.SmokeFailure, match="ring has no shard on"):
        chip_smoke.spread("ring", on_one, devices)


def test_tiny_widths_are_those_of_the_dv3_tests():
    from tests.test_algos.test_algos import _dv3_tiny_args

    assert chip_smoke.TINY_WIDTHS == _dv3_tiny_args()

"""Paired A2C CPU benchmark: async vs synchronous checkpointing stall.

ISSUE 2 acceptance criterion: for a replay-buffer-bearing state, the
async checkpoint writer (``checkpoint.async_save=True``) must cut the
in-loop save stall by >= 5x vs the synchronous path, with telemetry
recording BOTH the stall and the total (background) write time.

The pair runs the real A2C CPU training loop end to end through the CLI
with identical configs except ``checkpoint.async_save``. The dummy env's
vector observation is inflated (``env.wrapper.vector_shape``) so the
rollout buffer — persisted via ``buffer.checkpoint_on_policy=True`` —
weighs tens of MB, the regime where the zip write dominates the
device->host snapshot. Stall/write seconds come from the run's own
``telemetry.jsonl`` (the PR-1 observability sink; the CheckpointManager
publishes its stats under the ``ckpt`` key), so the numbers reported here
are exactly what a production run records about itself.

Usage: JAX_PLATFORMS=cpu python benchmarks/bench_resilience_stall.py \
           [--out benchmarks/results/resilience_stall.json] [--obs-dim 65536]
"""

import argparse
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from sheeprl_tpu.cli import run  # noqa: E402
from sheeprl_tpu.obs import read_records  # noqa: E402

# 4 envs x 64 rollout steps = 256 policy steps per iteration. Checkpoints
# land every third iteration: back-to-back saves would measure the async
# writer's double-buffer backpressure (submit blocking on the previous
# write) instead of the steady-state stall — production cadences leave far
# more loop time between saves than one write takes
_NUM_ENVS = 4
_ROLLOUT = 64
_ITERS = 16
_CKPT_EVERY_ITERS = 3


def _run_variant(root: str, async_save: bool, obs_dim: int) -> dict:
    name = "async" if async_save else "sync"
    run(
        [
            "exp=a2c",
            "env=dummy",
            f"env.num_envs={_NUM_ENVS}",
            "env.sync_env=True",
            "env.capture_video=False",
            f"env.wrapper.vector_shape=[{obs_dim}]",
            "fabric.accelerator=cpu",
            "fabric.devices=1",
            "metric.log_level=1",
            f"metric.log_every={_NUM_ENVS * _ROLLOUT}",
            f"metric.logger.root_dir={root}/logs",
            "buffer.memmap=False",
            "buffer.checkpoint_on_policy=True",  # the buffer-bearing state
            f"algo.rollout_steps={_ROLLOUT}",
            "algo.per_rank_batch_size=64",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.mlp_keys.encoder=[state]",
            f"algo.total_steps={_NUM_ENVS * _ROLLOUT * _ITERS}",
            "algo.run_test=False",
            f"checkpoint.every={_NUM_ENVS * _ROLLOUT * _CKPT_EVERY_ITERS}",
            f"checkpoint.async_save={async_save}",
            "checkpoint.save_last=True",
            "checkpoint.keep_last=2",
            f"root_dir={root}",
            f"run_name={name}",
            "seed=0",
        ]
    )
    telemetry = glob.glob(f"{root}/**/{name}/**/telemetry.jsonl", recursive=True)
    assert telemetry, f"{name}: no telemetry.jsonl written"
    records = [r for r in read_records(telemetry[0]) if "ckpt" in r]
    assert records, f"{name}: telemetry carries no ckpt section"
    last = records[-1]["ckpt"]
    assert last["saves"] > 0, f"{name}: no checkpoints recorded"
    return {
        "saves": last["saves"],
        "total_stall_s": last["total_stall_s"],
        "stall_per_save_s": last["total_stall_s"] / last["saves"],
        "total_write_s": last["total_write_s"],
        "async": last["async"],
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default=None, help="write the result JSON here")
    parser.add_argument(
        "--obs-dim",
        type=int,
        default=65536,
        help="dummy-env vector obs dim (65536 -> ~67 MB rollout buffer)",
    )
    args = parser.parse_args()

    buffer_mb = _ROLLOUT * _NUM_ENVS * args.obs_dim * 4 / 1e6
    print(f"A2C CPU pair: {_ITERS} iters, ~{buffer_mb:.0f} MB rollout buffer in each checkpoint")

    with tempfile.TemporaryDirectory(prefix="resilience_stall_") as root:
        sync = _run_variant(root, async_save=False, obs_dim=args.obs_dim)
        async_ = _run_variant(root, async_save=True, obs_dim=args.obs_dim)

    speedup = sync["stall_per_save_s"] / max(async_["stall_per_save_s"], 1e-9)
    result = {
        "buffer_mb": round(buffer_mb, 1),
        "sync": sync,
        "async": async_,
        "stall_reduction_x": round(speedup, 2),
    }
    print(json.dumps(result, indent=2))
    print(
        f"\nin-loop save stall: sync {sync['stall_per_save_s'] * 1e3:.1f} ms/save -> "
        f"async {async_['stall_per_save_s'] * 1e3:.1f} ms/save  ({speedup:.1f}x reduction; "
        f"background write {async_['total_write_s'] / async_['saves'] * 1e3:.1f} ms/save)"
    )
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {args.out}")
    return 0 if speedup >= 5.0 else 1


if __name__ == "__main__":
    raise SystemExit(main())

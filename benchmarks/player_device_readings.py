"""The readings behind ``PLAYER_ON_CHIP_BYTES`` (parallel/mesh.py): what a
policy step and a weight refresh cost with the player on the host CPU and on
the training device, by the size of the player's weights.

Each reading is one ``sheeprl_tpu.cli.run`` in a process of its own (a chip
belongs to one process at a time; this parent never touches JAX): DreamerV3
at the repo's published sizes on the benchmark's seeded pixel env, or the
MLP policy of ``exp=ppo`` on CartPole, for ``--steps`` policy steps after
``learning_starts``.  Read from the run's ``telemetry.jsonl``, over the
records that follow the last compile: the wall per policy step, which is
what decides, and beside it ``Time/player_step`` and ``Time/params_refresh``
per policy step (PPO has neither span: its ``Time/env_interaction_time``).
The two spans say where the host's time goes, not who wins: with a host
player the refresh also holds the wait for the update, which a chip player
pays later, in ``Time/loss_fetch``.

    python benchmarks/player_device_readings.py --sizes S,M,XL,ppo --players cpu,accelerator

One JSON line per reading, and all of them in ``chiprun_out/<--out>``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def overrides(size: str, player: str, steps: int, run_dir: str, extra: list) -> list:
    common = [
        f"fabric.player_device={player}", "metric.log_every=16", "checkpoint.save_last=False",
        "checkpoint.every=100000000", "algo.run_test=False", f"root_dir={run_dir}", f"run_name={size}_{player}",
    ] + list(extra)
    if size == "ppo":
        return common + [
            "exp=ppo", "env.id=CartPole-v1", "env.num_envs=4", "env.sync_env=True", "env.capture_video=False",
            "buffer.memmap=False", "algo.rollout_steps=32", f"algo.total_steps={8 * steps}",
        ]
    with open(os.path.join(REPO, "chipbench", "configs", "dv3_XL.json")) as f:
        base = json.load(f)["overrides"]
    learning_starts = 256
    return base + common + [
        f"algo=dreamer_v3_{size}", "env.num_envs=1", "algo.replay_ratio=0.5", f"algo.learning_starts={learning_starts}",
        f"algo.total_steps={learning_starts + steps}", "buffer.size=20000", "buffer.device_cache=auto",
        "buffer.checkpoint=False",
    ]


def reduce(path: str) -> dict:
    records = [json.loads(line) for line in open(path) if line.strip()]
    # the steady records: everything after the last one that saw a compile
    last_compile = 0
    for i in range(1, len(records)):
        if records[i]["compiles"]["total"] != records[i - 1]["compiles"]["total"]:
            last_compile = i
    steady = records[last_compile + 1:]
    if not steady:
        return {"error": f"no steady record among {len(records)}"}
    steps = steady[-1]["step"] - records[last_compile]["step"]
    wall = steady[-1]["ts"] - records[last_compile]["ts"]
    timers: dict = {}
    for r in steady:
        for k, v in r["timers_s"].items():
            timers[k] = timers.get(k, 0.0) + v
    per_step = {k: 1e3 * v / steps for k, v in timers.items()}
    return {
        "policy_steps": steps,
        "wall_ms_per_step": 1e3 * wall / steps,
        "player_step_ms": per_step.get("Time/player_step"),
        "params_refresh_ms_per_step": per_step.get("Time/params_refresh"),
        "env_interaction_ms": per_step.get("Time/env_interaction_time"),
        "player": steady[-1].get("player"),
    }


def one(size: str, player: str, steps: int, run_dir: str, extra: list) -> dict:
    shutil.rmtree(os.path.join(run_dir, f"{size}_{player}"), ignore_errors=True)
    code = "import sys; from sheeprl_tpu.cli import run; run(sys.argv[1:])"
    proc = subprocess.run(
        [sys.executable, "-c", code] + overrides(size, player, steps, run_dir, extra),
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    placed = [ln for ln in proc.stdout.splitlines() if ln.startswith("Player device:")]
    out = {"size": size, "player_device": player, "rc": proc.returncode, "placed": placed[0] if placed else None}
    paths = glob.glob(os.path.join(run_dir, f"{size}_{player}", "**", "telemetry.jsonl"), recursive=True)
    if proc.returncode != 0 or not paths:
        out["tail"] = proc.stdout[-2000:]
        return out
    out.update(reduce(paths[0]))
    shutil.rmtree(os.path.join(run_dir, f"{size}_{player}"), ignore_errors=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="S,M,XL,ppo")
    ap.add_argument("--players", default="cpu,accelerator")
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--run-dir", default=os.path.join(REPO, "runs", "player_device_readings"))
    ap.add_argument("--out", default="player_device_readings.jsonl", help="file name under chiprun_out/")
    ap.add_argument("--extra", nargs="*", default=[], help="further overrides: widths between the published sizes")
    ap.add_argument("--label", default="", help="a name for --extra's widths, kept in each line")
    args = ap.parse_args()
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", args.out), "a") as sink:
        for size in args.sizes.split(","):
            for player in args.players.split(","):
                line = json.dumps({"label": args.label, **one(size, player, args.steps, args.run_dir, args.extra)})
                print(line, flush=True)
                sink.write(line + "\n")
                sink.flush()


if __name__ == "__main__":
    main()

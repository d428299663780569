"""Dreamer V1/V2/V3 CLI wall-clock on the reference's own benchmark
protocol (reference benchmarks/benchmark.py + configs/exp/dreamer_v*_benchmarks.yaml:
tiny model, 16384 total steps, replay_ratio 0.0625, 1 env, checkpoints on).

The reference protocol runs Atari MsPacman; this image has no ale_py
(no network egress), so the runs substitute
``env=dummy`` with identical 64x64x3 pixel shapes. Disclosure: a dummy
step is cheaper than an ALE step, which flatters the env-interaction
share of the wall-clock — but at replay_ratio 0.0625 with the tiny model
this protocol is dominated by framework/dispatch overhead, which is what
it exists to compare. Reference 4-CPU anchors (BASELINE.md):
DV1 2207.13 s, DV2 906.42 s, DV3 1589.30 s.

Usage: python benchmarks/bench_dreamer_cli.py [--algos dv1 dv2 dv3]
           [--out benchmarks/results/dreamer_cli_bench_r4.json]
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ANCHORS = {"dv1": 2207.13, "dv2": 906.42, "dv3": 1589.30}


def run_one(name: str, log_path: str) -> float:
    version = name[-1]
    cmd = [
        sys.executable,
        os.path.join(REPO, "sheeprl.py"),
        f"exp=dreamer_v{version}_benchmarks",
        "env=dummy",
        "env.id=dummy_discrete",
        "env.capture_video=False",
        "metric.log_level=0",
        "metric.disable_timer=True",
        f"root_dir=/tmp/sheeprl_tpu_bench/{name}_cli",
        "run_name=bench",
    ]
    tic = time.perf_counter()
    with open(log_path, "a") as lf:
        subprocess.run(cmd, check=True, stdout=lf, stderr=lf, cwd=REPO)
    return time.perf_counter() - tic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--algos", nargs="+", default=["dv1", "dv2", "dv3"],
                    choices=["dv1", "dv2", "dv3"])
    ap.add_argument("--out", default="benchmarks/results/dreamer_cli_bench_r4.json")
    ap.add_argument("--log", default="/tmp/dreamer_cli_bench.log")
    args = ap.parse_args()

    rows = {}
    for name in args.algos:
        wall = run_one(name, args.log)
        rows[name] = {
            "wallclock_s": round(wall, 2),
            "reference_4cpu_s": ANCHORS[name],
            "vs_baseline": round(ANCHORS[name] / wall, 2),
        }
        print(json.dumps({name: rows[name]}), flush=True)

    out = {
        "protocol": (
            "reference benchmark protocol (exp=dreamer_v*_benchmarks: tiny model, "
            "16384 steps, replay_ratio 0.0625, 1 env, checkpoints on), env=dummy "
            "substituted for Atari (no ale_py in image; dummy steps are cheaper "
            "than ALE steps, disclosed), single run each, wall-clock of the whole "
            "CLI process including compile"
        ),
        "rows": rows,
    }
    if os.path.dirname(args.out):
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}", file=sys.stderr)


if __name__ == "__main__":
    main()

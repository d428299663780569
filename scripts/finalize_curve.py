"""Shared end-of-chain pipeline: curve + greedy eval -> one JSON artifact.

Every long learning run repeats the same closing steps: stitch the reward
curve across the chain's legs, find the newest checkpoint, sanity-check it
belongs to this chain, greedy-eval it, and fold everything plus run
metadata into a benchmarks/results artifact. This script is that pipeline
once, parameterized — the per-run finalize_*.sh wrappers just supply paths
and metadata (they had drifted as six near-copies before this existed).

Usage:
    python scripts/finalize_curve.py \
        --chain-dir runs/x/chain_r4 --run-dir runs/x \
        --out benchmarks/results/x_curve_r4.json \
        --experiment "..." --protocol "..." \
        [--expl-chain-dir runs/x/chain_expl]  # P2E: exploration-phase trace

Hard-fails (non-zero exit, artifact not written) when the checkpoint is
missing, belongs to a different chain (step gap > --delta-cap), or the
eval produced no ``Test - Reward:`` line — a published artifact always
carries a real greedy-eval number.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.curve_from_logs import stitch  # noqa: E402
from scripts.train_chain import latest_ckpt  # noqa: E402



def parse_eval_output(eval_txt: str):
    """(last Test-Reward float | None, eval-protocol dict | None).

    The protocol line is emitted by sheeprl_tpu/utils/eval_protocol.py;
    older checkpoints' evals only have the per-episode Test-Reward lines."""
    rewards = re.findall(r"Test - Reward: ([-\d.]+)", eval_txt)
    protocols = re.findall(r"Eval protocol: (\{.*\})", eval_txt)
    protocol = None
    if protocols:
        try:
            protocol = json.loads(protocols[-1])
        except (json.JSONDecodeError, ValueError):
            # a truncated/garbled protocol line (killed eval, interleaved
            # writes) must not crash the whole finalize — fall back to the
            # legacy Test-Reward path with a visible warning
            print(
                "WARNING: 'Eval protocol:' line is not valid JSON (truncated "
                "eval output?); falling back to the legacy 'Test - Reward:' "
                "number only.",
                file=sys.stderr,
            )
    return (float(rewards[-1]) if rewards else None, protocol)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--chain-dir", required=True)
    ap.add_argument("--run-dir", required=True, help="checkpoint search root")
    ap.add_argument("--out", required=True)
    ap.add_argument("--experiment", required=True, help="artifact 'experiment' field")
    ap.add_argument("--protocol", default=None, help="artifact 'protocol' field")
    ap.add_argument("--hardware", required=True, help="what the legs ran on, e.g. '1x TPU v5e'")
    ap.add_argument("--extra-log", action="append", default=[])
    ap.add_argument("--delta-cap", type=int, default=26000,
                    help="max |ckpt step - curve final step| before refusing")
    ap.add_argument("--eval-timeout", type=int, default=4800,
                    help="seconds; the default covers the 10-episode protocol "
                         "(5 greedy + 5 sampled) at ~8 min/episode")
    ap.add_argument("--eval-log", default=None,
                    help="persist the eval's full output here "
                         "(default: /tmp/<artifact-stem>_eval.log)")
    ap.add_argument("--expl-chain-dir", default=None,
                    help="optional exploration-phase chain (P2E): its stitched "
                         "task-reward trace is embedded as exploration_phase")
    ap.add_argument("--smooth", type=int, default=5,
                    help="reward-binning window passed to stitch()")
    args = ap.parse_args()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    artifact = stitch(args.chain_dir, args.extra_log, smooth=args.smooth)
    if not artifact["curve"]:
        print(f"ERROR: no reward points stitched from {args.chain_dir}", file=sys.stderr)
        return 1

    ckpt_step, ckpt = latest_ckpt(args.run_dir)
    if not ckpt:
        print(f"ERROR: no checkpoint found under {args.run_dir}", file=sys.stderr)
        return 1
    delta = abs(ckpt_step - artifact["final_step"])
    if delta > args.delta_cap:
        print(
            f"ERROR: newest ckpt step {ckpt_step} is {delta} steps from the "
            f"curve's final step {artifact['final_step']} — wrong chain's "
            "checkpoint?",
            file=sys.stderr,
        )
        return 1

    print(f"evaluating {ckpt}")
    eval_log = args.eval_log or os.path.join(
        "/tmp", os.path.splitext(os.path.basename(args.out))[0] + "_eval.log")
    env = {**os.environ, "MUJOCO_GL": os.environ.get("MUJOCO_GL", "egl")}
    # stream to a file (not PIPE): a hung/killed eval still leaves a
    # debuggable log on disk, and the artifact never publishes without it
    with open(eval_log, "w") as lf:
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(repo, "sheeprl_eval.py"),
                 f"checkpoint_path={ckpt}", "env.capture_video=False"],
                stdout=lf, stderr=lf, timeout=args.eval_timeout, cwd=repo, env=env,
            )
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = "timeout"
    with open(eval_log, errors="replace") as f:
        eval_txt = f.read()
    tail = "\n".join(eval_txt.strip().splitlines()[-15:])
    if rc != 0:
        print(
            f"ERROR: eval exited with {rc} — refusing to publish the artifact "
            f"from a failed eval run. Full log: {eval_log}; tail:\n{tail}",
            file=sys.stderr,
        )
        return 1
    headline, protocol = parse_eval_output(eval_txt)
    if headline is None:
        print(
            "ERROR: no 'Test - Reward:' line in the eval output — eval failed "
            "or its output format drifted; refusing to publish the artifact "
            f"without the greedy-eval number. Full log: {eval_log}; tail:\n{tail}",
            file=sys.stderr,
        )
        return 1
    print(f"Test - Reward: {headline}")

    # multi-episode protocol summary (greedy + sampled per-episode lists);
    # the final 'Test - Reward:' line is the protocol's greedy median, so
    # the legacy field below stays a robust statistic either way
    if protocol is not None:
        artifact["eval_protocol"] = protocol
    else:
        print(
            "WARNING: no 'Eval protocol:' line — single-episode eval output "
            "(pre-protocol checkpoint format?); publishing the last "
            "'Test - Reward:' as the only eval number.",
            file=sys.stderr,
        )
    artifact["greedy_eval_reward_at_final_ckpt"] = headline
    artifact["eval_ckpt_step"] = ckpt_step
    artifact["experiment"] = args.experiment
    artifact["hardware"] = args.hardware
    if args.protocol:
        artifact["protocol"] = args.protocol

    if args.expl_chain_dir:
        expl = stitch(args.expl_chain_dir, smooth=args.smooth)
        if not expl["curve"]:
            print(
                f"ERROR: --expl-chain-dir {args.expl_chain_dir} stitched to an "
                "empty curve — wrong chain dir layout? (expects leg_*.log + "
                "status.jsonl, as written by scripts/train_chain.py)",
                file=sys.stderr,
            )
            return 1
        vals = [p["reward_mean"] for p in expl["curve"]]
        artifact["exploration_phase"] = {
            "note": (
                "task-reward trace of the exploration phase (the policy "
                "optimizes ensemble disagreement, not task reward — near-zero "
                "rewards here are the point on a sparse task)"
            ),
            "summary": {
                "episodes_binned": expl["n_points"],
                "reward_mean": round(sum(vals) / len(vals), 3) if vals else None,
                "reward_max": max(p["reward_max"] for p in expl["curve"]) if expl["curve"] else None,
                "final_step": expl["final_step"],
            },
            "curve": expl["curve"],
        }

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(json.dumps({k: artifact.get(k) for k in (
        "final_step", "final_reward_mean", "best_reward_mean",
        "greedy_eval_reward_at_final_ckpt")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

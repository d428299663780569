"""The readings behind ``models/sdar_moe.py``'s ``SHORT_BUFFER_SHARES`` (PERF.md
section 6, PR 29): how many assignments the held experts of a layer get in one
minibatch step of the cell ``sdar_ep8_train``, over many seeds.

For every seed: the weights and the rollout as the cell draws them from
``--seed``, then one no-gradient pass an episode at the initial weights; a
layer's held assignments are counted per episode.  A minibatch is any 3 of the
12 episodes (the epoch's shuffle), so the step's load of a layer lies between
the sums of its 3 lightest and its 3 heaviest episodes.  One JSON line a seed:
per layer the mean minibatch and the heaviest possible one, in assignments and
in even shares (``tokens x top_k x experts_held / num_experts``), and the
short buffer's rows.  On the chip (published widths; ``--tiny`` rehearses on the CPU):

    python benchmarks/sdar_load_readings.py --workload sdar_ep8_train --seeds 40
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", default="sdar_ep8_train")
    ap.add_argument("--seeds", type=int, default=40, help="how many seeds, drawn from --first as the driver draws its own")
    ap.add_argument("--first", type=int, default=29)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at tiny widths; never a reading")
    args = ap.parse_args(argv)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import flops_sdar, harness, rollout_fill
    from chipbench.drivers import sdar_train
    from sheeprl_tpu.models.sdar_moe import SdarMoE, short_buffer_rows

    workload = harness.load_json("workloads", args.workload + ".json")
    ctx = harness.Context(
        name=args.workload, workload=workload, config=harness.load_json("configs", workload["config"] + ".json"),
        traffic=harness.load_json("traffic", workload["traffic"] + ".json"), seed=0, seconds=0.0, trace=False, tiny=args.tiny,
        t_process_start=0.0, run_dir=os.path.join(harness.OUT, "runs"))
    prog, shapes = sdar_train.build(ctx)  # the update as the cell builds it, held to the configuration's file
    policy, c = prog.policy, prog.policy.cfg
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    tokens = mb_eps * shapes.packed_positions
    even = flops_sdar.expected_assignments(shapes)  # a layer's held assignments a step under even routing
    rows_fit = short_buffer_rows(tokens, c.num_experts_per_tok, c.experts_held, c.num_experts)

    @jax.jit
    def held_per_layer(params, prompt, actions):  # one episode -> (layers,)
        packed, _ = policy.layout.pack(prompt, actions, c.mask_id)
        return policy.model.apply(params, packed, policy.layout, method=SdarMoE.hidden)[1]["load"].sum(-1)

    seeds = np.random.default_rng(args.first).integers(0, 2**31, args.seeds)
    worst = 0.0
    for seed in (int(s) for s in seeds):
        prog.cfg.seed = seed
        params = prog.fresh_params()[1]
        data = rollout_fill.fill(seed, n_eps, shapes.prompt, shapes.response, shapes.block, shapes.vocab - 1)  # ids without [MASK]
        prompt, actions = data["prompt"][0], jnp.swapaxes(data["actions"], 0, 1)
        per_episode = np.stack([np.asarray(held_per_layer(params, prompt[e:e + 1], actions[e:e + 1])) for e in range(n_eps)])
        del params
        heaviest = np.sort(per_episode, axis=0)[-mb_eps:].sum(0)  # per layer, the 3 heaviest episodes together
        mean = per_episode.mean(0) * mb_eps
        worst = max(worst, float(heaviest.max()))
        print(json.dumps({"seed": seed, "mean_minibatch": mean.round(1).tolist(), "heaviest_minibatch": heaviest.tolist(),
                          "heaviest_in_even_shares": (heaviest / even).round(3).tolist(), "fits": bool(heaviest.max() <= rows_fit)}),
              flush=True)
    print(json.dumps({"seeds": len(seeds), "even_share": even, "short_buffer_rows": rows_fit, "worst_case_rows": tokens * min(
        c.num_experts_per_tok, c.experts_held), "heaviest_seen": worst, "heaviest_seen_in_even_shares": worst / even}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

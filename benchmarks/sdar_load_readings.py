"""The readings behind ``models/sdar_moe.py``'s ``SHORT_BUFFER_SHARES`` (PERF.md
section 6, PR 29) and ``OVERFLOW_LIST_SHARE`` (PR 33): how many assignments the
held experts of a layer get in one minibatch step of a language-model train
cell, and how many of its choices a single token holds, over many seeds.

For every seed: the weights and the rollout as the cell draws them from
``--seed``, then one no-gradient pass an episode at the initial weights; a
layer's held assignments are counted per episode, and its tokens by the number
of their choices that are held (0 to top-k).  A minibatch is any
``minibatch_episodes`` of the episodes (the epoch's shuffle), so the step's
load of a layer lies between the sums of its lightest and its heaviest
episodes.  One JSON line a seed: per layer the mean minibatch and the heaviest
possible one, in assignments and in even shares (``tokens x top_k x
experts_held / num_experts``), and the heaviest minibatch's tokens that hold
more than ``c`` = 2, 3, 4 choices (the compact token-side sums read ``c`` rows
a token and list the tokens that hold more); a last line with the short
buffer's rows, ``compact_slots``' ``(c, r)`` (null where the short buffer is
too small for the compact form to pay), the pooled share of tokens by
held choices and the largest list seen at each ``c``.  On the chip (published
widths; ``--tiny`` rehearses on the CPU):

    python benchmarks/sdar_load_readings.py --workload sdar_ep8_train --seeds 40
    python benchmarks/sdar_load_readings.py --workload joyai_ep_train --seeds 40
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

    from chipbench import harness
    from sheeprl_tpu.models.sdar_moe import compact_slots, short_buffer_rows

    workload = harness.load_json("workloads", args.workload + ".json")
    traffic = harness.load_json("traffic", workload["traffic"] + ".json")
    ctx = harness.Context(
        name=args.workload, workload=workload, config=harness.load_json("configs", workload["config"] + ".json"),
        traffic=traffic, seed=0, seconds=0.0, trace=False, tiny=args.tiny,
        t_process_start=0.0, run_dir=os.path.join(harness.OUT, "runs"))
    if traffic["driver"] == "sdar_train":  # per kind: the driver, a step's even load, the rollout's fill
        from chipbench import flops_sdar as flops, rollout_fill
        from chipbench.drivers import sdar_train as driver

        fill = lambda seed, n_eps, s: rollout_fill.fill(seed, n_eps, s.prompt, s.response, s.block, s.vocab - 1)  # noqa: E731
    else:
        from chipbench import causal_rollout_fill, flops_joyai as flops
        from chipbench.drivers import causal_lm_train as driver

        fill = lambda seed, n_eps, s: causal_rollout_fill.fill(seed, n_eps, s.prompt, s.response, s.vocab)  # noqa: E731
    prog, shapes = driver.build(ctx)  # the update as the cell builds it, held to the configuration's file
    policy = prog.policy
    spec = policy.cfg.routed_spec
    k, held_n = spec.top_k, spec.experts_held
    n_eps, mb_eps = int(ctx.param("episodes")), int(ctx.param("minibatch_episodes"))
    even = flops.expected_assignments(shapes)  # a layer's held assignments a step under even routing
    cuts = (2, 3, 4)

    @jax.jit
    def tokens_by_held(params, prompt, actions):  # one episode -> (layers, k + 1): its tokens by held choices
        local = policy.evaluate_episodes(params, prompt, actions)[3]["top_i"] - spec.expert_offset
        held = ((local >= 0) & (local < held_n)).sum(-1).reshape(local.shape[0], -1)
        return (held[..., None] == jnp.arange(k + 1)).sum(1)

    seeds = np.random.default_rng(args.first).integers(0, 2**31, args.seeds)
    worst, pooled, longest, tokens = 0.0, 0, {c: 0 for c in cuts}, 0
    for seed in (int(s) for s in seeds):
        prog.cfg.seed = seed
        params = prog.fresh_params()[1]
        data = fill(seed, n_eps, shapes)
        prompt, actions = data["prompt"][0], jnp.swapaxes(data["actions"], 0, 1)
        by_held = np.stack([np.asarray(tokens_by_held(params, prompt[e:e + 1], actions[e:e + 1])) for e in range(n_eps)])
        del params
        tokens = mb_eps * int(by_held[0, 0].sum())
        per_episode = by_held @ np.arange(k + 1)  # (episodes, layers): held assignments
        heaviest = np.sort(per_episode, axis=0)[-mb_eps:].sum(0)  # per layer, the heaviest episodes together
        mean = per_episode.mean(0) * mb_eps
        over = {c: np.sort(by_held[..., c + 1:].sum(-1), axis=0)[-mb_eps:].sum(0) for c in cuts}
        worst, pooled = max(worst, float(heaviest.max())), pooled + by_held.sum((0, 1))
        longest = {c: max(longest[c], int(over[c].max())) for c in cuts}
        print(json.dumps({"seed": seed, "mean_minibatch": mean.round(1).tolist(), "heaviest_minibatch": heaviest.tolist(),
                          "heaviest_in_even_shares": (heaviest / even).round(3).tolist(),
                          **{f"tokens_over_{c}": over[c].tolist() for c in cuts}}), flush=True)
    print(json.dumps({"seeds": len(seeds), "tokens": tokens, "even_share": even,
                      "short_buffer_rows": short_buffer_rows(tokens, k, held_n, spec.num_experts),
                      "compact_slots": compact_slots(tokens, k, held_n, spec.num_experts, 2 * spec.hidden_size),  # rows in bf16
                      "worst_case_rows": tokens * min(k, held_n), "heaviest_seen": worst, "heaviest_seen_in_even_shares": worst / even,
                      "share_of_tokens_by_held_choices": (pooled / pooled.sum()).round(6).tolist(),
                      "longest_list_seen": longest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

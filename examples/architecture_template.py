"""Generalized decoupled topology template — N players / 1 learner.

Counterpart of the reference's examples/architecture_template.py (which
documents an N-player/M-trainer/1-buffer TorchCollective topology). The
TPU-native mapping collapses the M DDP trainer ranks into ONE SPMD learner
process driving the whole device mesh (data parallelism is a mesh axis, the
gradient all-reduce is an XLA collective), while players stay host
processes pinned to the CPU backend and exchange numpy pytrees over
multiprocessing queues — exactly the machinery behind
``sheeprl_tpu/algos/ppo/ppo_decoupled.py`` and ``sac/sac_decoupled.py``.

Topology::

    player-0 ─┐                      ┌─> resp_q[0] ─> player-0
    player-1 ─┼─ data_q ─> LEARNER ──┼─> resp_q[1] ─> player-1
    player-N ─┘   (TPU mesh, 1 jit)  └─> resp_q[N] ─> player-N

Protocol per player (mirrors the reference collective protocol):
  ("init", spaces...)          player -> learner   agent blueprint
  ("params", tree)             learner -> player   initial weights
  ("data", rollout, meta)      player -> learner   experience
  ("update", tree, metrics)    learner -> player   refreshed weights
  ("ckpt_req",)/("ckpt_state") on demand            checkpoint handoff
  ("stop",)                    player -> learner   shutdown sentinel

Run: python examples/architecture_template.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import multiprocessing as mp


def player_loop(player_id: int, cfg: dict, data_q: mp.Queue, resp_q: mp.Queue) -> None:
    """One env-interaction process, pinned to the host CPU backend."""
    import numpy as np

    rng = np.random.default_rng(player_id)
    # 1. handshake: ship the agent blueprint, receive initial weights
    data_q.put(("init", player_id, {"obs_dim": 4, "act_dim": 2}))
    tag, params = resp_q.get()
    assert tag == "params"

    for it in range(cfg["iters"]):
        # 2. collect a (tiny, fake) rollout with the current weights
        rollout = {
            "obs": rng.normal(size=(cfg["rollout"], 4)).astype(np.float32),
            "rew": rng.normal(size=(cfg["rollout"], 1)).astype(np.float32),
        }
        data_q.put(("data", player_id, rollout))
        # 3. refreshed weights back
        tag, params, metrics = resp_q.get()
        assert tag == "update"
    data_q.put(("stop", player_id))


def learner_loop(n_players: int, cfg: dict, data_q: mp.Queue, resp_qs: list) -> None:
    """The single SPMD learner: in a real algorithm this owns the device
    mesh and a jitted update (see MeshRuntime.setup_step)."""
    import numpy as np

    params = {"w": np.zeros((4, 2), np.float32)}
    # one uniform message loop: init handshakes, data and stop sentinels
    # interleave freely across players
    stopped = set()
    step = 0
    while len(stopped) < n_players:
        msg = data_q.get()
        if msg[0] == "init":
            resp_qs[msg[1]].put(("params", params))
        elif msg[0] == "stop":
            stopped.add(msg[1])
        else:
            _, pid, rollout = msg
            # one jitted gradient step over the mesh would go here
            params = {"w": params["w"] + 1e-3 * rollout["obs"].mean()}
            step += 1
            resp_qs[pid].put(("update", params, {"step": step}))
    print(f"learner done after {step} updates")


if __name__ == "__main__":
    N_PLAYERS = 3
    CFG = {"iters": 5, "rollout": 16}
    ctx = mp.get_context("spawn")
    data_q: mp.Queue = ctx.Queue()
    resp_qs = [ctx.Queue() for _ in range(N_PLAYERS)]
    procs = [
        ctx.Process(target=player_loop, args=(i, CFG, data_q, resp_qs[i])) for i in range(N_PLAYERS)
    ]
    # a chip belongs to one process: the players are pinned to the CPU by
    # exporting JAX_PLATFORMS around the spawn (a child copies the parent's
    # environ at start), while the learner keeps whatever JAX finds
    saved_platform = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        for p in procs:
            p.start()
    finally:
        if saved_platform is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved_platform
    learner_loop(N_PLAYERS, CFG, data_q, resp_qs)
    for p in procs:
        p.join()
    print("ok")

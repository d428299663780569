"""Chaos soak for the self-healing N-player topology AND the training
health sentinel.

``--mode topology`` (default) drives one decoupled run under a RANDOMIZED
kill/restart schedule built from the existing ``SHEEPRL_FAULTS`` sites
(player_exit entries at random iterations against random players,
optional net_drop/net_delay noise on the tcp transport), with the
supervisor armed so every kill turns into a backoff-restart-rejoin
cycle.  After the run it audits the lead's telemetry: the pool must
RECOVER to the launch size, every scheduled kill must appear as a death,
rejoins must match, the trainer must not have retraced XLA after warmup
(mask-padded fan-in), and the final reward must be finite.

``--mode health`` is the ISSUE 7 acceptance harness: with ``nan_inject``
armed (``--fault`` picks nan_inject/loss_spike/rb_corrupt), a coupled
SAC run and an N=2 decoupled PPO run must both detect the anomaly within
one update, skip it, trip the consecutive-skip budget, roll back to the
last good checkpoint, and finish rc=0 — with the ``health`` telemetry
key recording the verdicts and the rollback event (and the transport
stats recording the rollback broadcast round for the decoupled run).

Topology acceptance (ISSUE 6) runnable standalone::

    python scripts/chaos_soak.py --players 4 --transport tcp --kills 3 \
        --total-steps 19200 --seed 7

``--mode serve`` is the ISSUE 8 acceptance harness: an
``algo.inference=remote`` N-player run under a randomized server-kill
schedule (+ tcp net noise) — every kill must show breaker trip -> local
fallback -> supervisor respawn -> half-open re-promotion with a clean
request-id audit — plus a deterministic sub-leg offering the hot-swap
watcher a nan-POISONED checkpoint (must be refused) and a good one
(must swap).

Health acceptance (ISSUE 7)::

    python scripts/chaos_soak.py --mode health --seed 7

``--mode integrity`` is the ISSUE 10 acceptance harness: on each of the
three transports, a decoupled run under injected ``bit_flip`` faults
(data frames at both players + a lead-directed params broadcast) must
DETECT every flip at the receive boundary (``integrity`` telemetry:
corrupt_detected >= injected, silent_accepted == 0), recover via the
retransmit / digest-skip machinery (retrans_failed == 0) and finish
rc=0; plus an rb_insert leg (``rb_corrupt`` quarantined at ingest) and
a paired off-vs-crc leg whose final agent params must be bit-exact.

``--mode ckpt`` is the ISSUE 17 acceptance harness: an fsdp (4x2 mesh)
a2c run with ``checkpoint.sharded=true`` is SIGKILLed mid-shard-write
(``ckpt_shard_kill``) — the manifest never commits, so the directory
stays partial — then the SAME root is relaunched with
``checkpoint.resume_from=auto`` onto a DIFFERENT mesh (2x4): auto-resume
must refuse the partial directory, resume from the last COMPLETE
manifest, reshard the restored state onto the new fsdp axis, and finish
rc=0 — with the ``ckpt`` telemetry key carrying the per-shard write /
manifest stitch stats in both phases.

``--mode scale`` is the ISSUE 20 acceptance harness, two legs.  A
decoupled run starts its player pool at the autoscaler MINIMUM (1 of
3); forced gather pressure makes the telemetry-driven autoscaler grow
it through the real supervisor spawn path, and the initially-spawned
player is killed a few iterations in, while the pool is still scaling
up — the pool must still converge to the maximum with the kill
restarted, every decision a typed ``autoscale`` flight event.  Then a session swarm thrashes an elastic
serve pool whose session cache is smaller than the client count — every
client must ride out the ``session_lost`` storm by reopen-and-replay
with zero drops — and a nan-poisoned hot-swap candidate must be refused
by the session server.

Serve acceptance (ISSUE 8)::

    python scripts/chaos_soak.py --mode serve --seed 7

Integrity acceptance (ISSUE 10)::

    python scripts/chaos_soak.py --mode integrity --seed 7

Sharded-checkpoint acceptance (ISSUE 17)::

    python scripts/chaos_soak.py --mode ckpt --seed 7

Elastic-scale acceptance (ISSUE 20)::

    python scripts/chaos_soak.py --mode scale --seed 7

all wrapped by ``chaos``/``slow``-marked pytest soaks.  The schedules
are pure functions of ``--seed``, so a failing soak reproduces exactly.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import random
import sys

# runnable as `python scripts/chaos_soak.py`: sys.path[0] is scripts/,
# the package lives one level up
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def build_kill_schedule(
    rng: random.Random, players: int, kills: int, first_iter: int = 3, span: int = 60
):
    """Randomized but reproducible ``player_exit`` entries: ``kills``
    distinct (iteration, player) pairs.  Player 0 (the lead) is eligible
    too — a lead death exercises the logger/checkpoint re-mastering path.
    Iterations are spread out so each death can complete its
    restart-rejoin cycle before the next one lands."""
    entries = []
    used_pids = []
    for k in range(kills):
        pid = rng.randrange(players)
        at = first_iter + k * span + rng.randrange(span // 2)
        entries.append(f"player_exit:{at}:{pid}")
        used_pids.append(pid)
    return entries, used_pids


def build_net_noise(rng: random.Random, n_drops: int, n_delays: int):
    entries = []
    for _ in range(n_drops):
        entries.append(f"net_drop:{rng.randrange(5, 200)}")
    for _ in range(n_delays):
        entries.append(f"net_delay:{rng.randrange(5, 200)}:{rng.uniform(0.05, 0.3):.2f}")
    return entries


def read_telemetry(root_dir: str):
    """Every ``transport``-keyed record + reward/compile scalars from the
    run's telemetry JSONL files (shared reader: obs/reader.py)."""
    from sheeprl_tpu.obs.reader import iter_run_records

    transports, compiles = [], []
    for rec in iter_run_records(root_dir):
        if "transport" in rec:
            transports.append(rec["transport"])
        if rec.get("trainer_compiles") is not None:
            compiles.append(rec["trainer_compiles"])
    return transports, compiles


def audit(transports, compiles, *, players: int, kills: int, min_rejoins: int = 2) -> list:
    """Return a list of failure strings (empty = soak passed).

    Cumulative counters (supervisor restarts, rejoins) are taken as the
    MAX over all records: while the LEAD itself is dead there is a
    telemetry gap, so the final record can predate the last cycle.  The
    net-noise entries can kill players beyond the schedule (a reconnect
    that misses its window is a real death), so restarts >= kills is the
    every-kill-was-acted-on check, not an equality."""
    failures = []
    if not transports:
        return ["no transport telemetry found (did the lead die without re-mastering?)"]
    last = transports[-1]
    pool = last["live"] + last.get("joining", 0)
    if pool < players:
        failures.append(f"pool never recovered: live+joining={pool} < {players}")
    restarts = max((t.get("supervisor") or {}).get("restarts", 0) for t in transports)
    if restarts < kills:
        failures.append(f"only {restarts} restarts for {kills} scheduled kills")
    rejoins = max(t.get("rejoins", 0) for t in transports)
    if rejoins < min_rejoins:
        failures.append(f"only {rejoins} rejoins observed (need >= {min_rejoins})")
    # zero post-warmup recompiles: the compile counter must plateau (the
    # mask-padded fan-in absorbs every shrink/grow without a retrace)
    if len(compiles) >= 3 and compiles[-1] != compiles[1]:
        failures.append(
            f"trainer retraced XLA after warmup: compiles {compiles[1]} -> {compiles[-1]}"
        )
    return failures


def read_health(root_dir: str):
    """All ``health`` sections (top-level and transport-nested) plus
    transport rollback counters from a run's telemetry files."""
    from sheeprl_tpu.obs.reader import iter_run_records, key_path

    health, rollback_rounds = [], 0
    for rec in iter_run_records(root_dir):
        if rec.get("health"):
            health.append(rec["health"])
        if key_path(rec, "transport.health"):
            health.append(rec["transport"]["health"])
        rollback_rounds = max(rollback_rounds, key_path(rec, "transport.rollbacks", 0))
    return health, rollback_rounds


def audit_health(health, rollback_rounds, *, budget: int, decoupled: bool) -> list:
    failures = []
    if not health:
        return ["no health telemetry found (sentinel not wired?)"]
    last = max(health, key=lambda h: h.get("updates", 0))
    if last.get("skips", 0) < budget:
        failures.append(f"only {last.get('skips', 0)} skips for a {budget}-skip fault window")
    if last.get("rollbacks", 0) < 1:
        failures.append("no rollback recorded despite a tripped budget")
    if not last.get("last_ok", False):
        failures.append("run ended on an anomalous verdict (no recovery)")
    if decoupled and rollback_rounds < 1:
        failures.append("transport stats did not record the rollback broadcast round")
    return failures


def audit_alerts(leg_root: str, *, expect_rule: str = None) -> list:
    """ISSUE 15: with the live metrics plane armed, an injected fault
    must fire its matching alert rule (a typed ``alert`` fleet event in
    the flight streams AND a ``sheeprl.alert/1`` record in telemetry),
    and a clean leg must fire NOTHING — false alarms train operators to
    ignore the channel."""
    from sheeprl_tpu.obs.reader import read_alerts, read_flight

    flight_alerts = [
        r for r in read_flight(leg_root) if r.get("k") == "event" and r.get("name") == "alert"
    ]
    fired = sorted(
        {
            (r.get("a") or {}).get("rule")
            for r in flight_alerts
            if (r.get("a") or {}).get("state") == "firing"
        }
    )
    failures = []
    if expect_rule is None:
        # slo_* burn rules track latency objectives a loaded CI box can
        # legitimately breach — the zero-false-fires claim is about the
        # fault-shaped rules
        non_slo = [r for r in fired if not r.startswith("slo_")]
        if non_slo:
            failures.append(f"clean leg fired alert rules {non_slo} (expected none)")
        return failures
    if expect_rule not in fired:
        failures.append(f"fault leg never fired rule {expect_rule!r} (fired: {fired})")
    # the same transitions must be queryable post-hoc from the telemetry
    # stream (the sink interleaves alert records)
    tel_rules = {a.get("rule") for a in read_alerts(leg_root) if a.get("state") == "firing"}
    if expect_rule not in tel_rules:
        failures.append(
            f"rule {expect_rule!r} missing from the telemetry alert records ({sorted(tel_rules)})"
        )
    return failures


def _run_health_leg(
    args, faults: str, cli_args: list, leg_root: str, *, decoupled: bool, expect_alert: str = None
) -> list:
    import shutil

    shutil.rmtree(leg_root, ignore_errors=True)
    if faults:
        os.environ["SHEEPRL_FAULTS"] = faults
    from sheeprl_tpu.cli import run

    try:
        run(cli_args)
    finally:
        os.environ.pop("SHEEPRL_FAULTS", None)
    if not faults:
        # clean leg: only the zero-false-fires audit applies
        failures = audit_alerts(leg_root, expect_rule=None)
        print(json.dumps({"leg": os.path.basename(leg_root), "failures": failures}, indent=2))
        return failures
    health, rb_rounds = read_health(leg_root)
    failures = audit_health(health, rb_rounds, budget=3, decoupled=decoupled)
    failures += audit_alerts(leg_root, expect_rule=expect_alert)
    last = max(health, key=lambda h: h.get("updates", 0)) if health else {}
    print(
        json.dumps(
            {
                "leg": os.path.basename(leg_root),
                "skips": last.get("skips"),
                "rollbacks": last.get("rollbacks"),
                "last_rollback": last.get("last_rollback"),
                "ckpt_tags": last.get("ckpt_tags"),
                "transport_rollback_rounds": rb_rounds,
                "failures": failures,
            },
            indent=2,
        )
    )
    return failures


def run_health_mode(args) -> int:
    """ISSUE 7 acceptance: coupled SAC + N=2 decoupled PPO under the
    chosen update fault; both must skip, roll back and finish rc=0."""
    base = args.root_dir
    fault = args.fault
    sentinel = [
        "algo.sentinel.enabled=True",
        "algo.sentinel.warmup=6",
        "algo.sentinel.skip_budget=3",
        "algo.sentinel.good_after=4",
    ]
    common = [
        "env=dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.accelerator=cpu",
        "fabric.devices=1",
        "metric.log_level=1",
        "metric.log_every=64",
        # ISSUE 15: the live plane rides every health leg — the injected
        # fault must fire its alert rule, and the clean leg none
        "metric.live=on",
        "metric.tracing=sampled",
        "checkpoint.save_last=True",
        "buffer.memmap=False",
        f"seed={args.seed}",
        "algo.run_test=False",
    ]
    failures = _run_health_leg(
        args,
        f"{fault}:20:3" if fault != "rb_corrupt" else "rb_corrupt:20",
        common
        + sentinel
        + [
            "exp=sac",
            "env.id=dummy_continuous",
            "env.num_envs=4",
            f"metric.logger.root_dir={base}/sac/logs",
            "checkpoint.every=16",
            "algo.total_steps=512",
            "algo.learning_starts=16",
            "algo.per_rank_batch_size=8",
            "algo.hidden_size=8",
            "algo.mlp_keys.encoder=[state]",
            f"root_dir={base}/sac/run",
        ],
        f"{base}/sac",
        decoupled=False,
        expect_alert="sentinel_skip_streak",
    )
    failures += _run_health_leg(
        args,
        f"{fault}:12:3" if fault != "rb_corrupt" else "rb_corrupt:12",
        common
        + sentinel
        + [
            "exp=ppo_decoupled",
            "env.num_envs=4",
            f"metric.logger.root_dir={base}/dec/logs",
            "checkpoint.every=128",
            "algo.total_steps=1024",
            "algo.rollout_steps=4",
            "algo.num_players=2",
            f"algo.decoupled_transport={args.transport}",
            "algo.per_rank_batch_size=4",
            "algo.update_epochs=1",
            "algo.dense_units=8",
            "algo.mlp_layers=1",
            "algo.mlp_keys.encoder=[state]",
            f"root_dir={base}/dec/run",
        ],
        f"{base}/dec",
        decoupled=True,
        expect_alert="sentinel_skip_streak",
    )
    # clean leg (no faults): the sentinel stays armed and the live plane
    # must fire ZERO alert rules — the channel stays trustworthy
    failures += _run_health_leg(
        args,
        "",
        common
        + sentinel
        + [
            "exp=sac",
            "env.id=dummy_continuous",
            "env.num_envs=4",
            f"metric.logger.root_dir={base}/clean/logs",
            "checkpoint.every=64",
            "algo.total_steps=256",
            "algo.learning_starts=16",
            "algo.per_rank_batch_size=8",
            "algo.hidden_size=8",
            "algo.mlp_keys.encoder=[state]",
            f"root_dir={base}/clean/run",
        ],
        f"{base}/clean",
        decoupled=False,
    )
    if not args.keep:
        import shutil

        shutil.rmtree(base, ignore_errors=True)
    if failures:
        print("HEALTH CHAOS SOAK FAILED", file=sys.stderr)
        return 1
    print("health chaos soak passed")
    return 0


def read_serve(root_dir: str):
    """Last client-side ``serve`` record and server-side
    ``transport.serve`` record from a run's telemetry files."""
    from sheeprl_tpu.obs.reader import iter_run_records, key_path

    client, server = None, None
    for rec in iter_run_records(root_dir):
        if rec.get("serve"):
            client = rec["serve"]
        if key_path(rec, "transport.serve"):
            server = rec["transport"]["serve"]
    return client, server


def audit_serve(client, server, *, kills: int) -> list:
    failures = []
    if client is None or server is None:
        return ["no serve telemetry found (inference=remote not wired?)"]
    if client.get("breaker_trips", 0) < 1:
        failures.append("breaker never tripped despite the server kill")
    if client.get("local_fallbacks", 0) < 1:
        failures.append("no local fallbacks recorded")
    if client.get("breaker_promotions", 0) < 1:
        failures.append("breaker never re-promoted after the respawn")
    if client.get("breaker") != "closed":
        failures.append(f"run ended with the breaker {client.get('breaker')!r}")
    if client.get("unaccounted", 0) != 0:
        failures.append(f"request-id audit failed: {client.get('unaccounted')} unaccounted")
    if server.get("respawns", 0) < kills:
        failures.append(f"only {server.get('respawns', 0)} respawns for {kills} server kills")
    if not server.get("batches"):
        failures.append("server never dispatched a batch")
    return failures


def run_serve_hot_swap_leg(root: str) -> list:
    """Deterministic sub-leg: a nan-POISONED checkpoint offered for
    hot-swap must be refused (finite spot-check), a good one swapped."""
    import time

    import numpy as np

    from sheeprl_tpu.serve import InferenceServer, agent_params_loader
    from sheeprl_tpu.utils.ckpt_format import save_state

    ckpt_dir = os.path.join(root, "hot_swap", "checkpoint")
    os.makedirs(ckpt_dir, exist_ok=True)
    good = save_state(
        os.path.join(ckpt_dir, "ckpt_100_0.ckpt"),
        {"agent": {"w": np.full((4,), 2.0, np.float32)}},
    )
    time.sleep(0.02)
    save_state(
        os.path.join(ckpt_dir, "ckpt_200_0.ckpt"),
        {"agent": {"w": np.full((4,), np.nan, np.float32)}},  # poisoned, newer
    )
    loader = agent_params_loader("agent")
    srv = InferenceServer(lambda p, o, k: {"actions": o["x"] + p["w"][0]}, {"w": np.zeros(4)})
    srv.watch(os.path.join(root, "hot_swap"), loader, interval_s=1e6)
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        swapped = srv.poll_hot_swap()
    st = srv.stats()["swaps"]
    failures = []
    if st["refused_invalid"] < 1:
        failures.append("nan-poisoned checkpoint was NOT refused")
    if swapped != os.path.abspath(good) or st["applied"] != 1:
        failures.append(f"good checkpoint not swapped in (swapped={swapped}, stats={st})")
    srv.close()
    return failures


def run_serve_mode(args) -> int:
    """ISSUE 8 acceptance soak: a remote-inference N-player run under a
    randomized server-kill (+ tcp net noise) schedule — breakers must
    trip to the local fallback, the supervisor must respawn the server,
    breakers must re-promote, and the request-id audit must be clean —
    plus the poisoned-checkpoint hot-swap refusal sub-leg."""
    import shutil

    rng = random.Random(args.seed)
    kills = max(1, min(args.kills, 2))  # enough batches must fit between kills
    entries = []
    at = 0
    for _ in range(kills):
        at += rng.randrange(30, 80)
        entries.append(f"server_exit:{at}")
    if args.transport == "tcp":
        entries += build_net_noise(rng, args.net_drops, args.net_delays)
    faults = ",".join(entries)
    print(f"serve chaos schedule (seed {args.seed}): SHEEPRL_FAULTS={faults}")

    shutil.rmtree(args.root_dir, ignore_errors=True)
    os.environ["SHEEPRL_FAULTS"] = faults
    from sheeprl_tpu.cli import run

    try:
        run(
            [
                "exp=ppo_decoupled",
                "env=dummy",
                "env.sync_env=True",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "fabric.devices=1",
                "metric.log_level=1",
                "metric.log_every=64",
                f"metric.logger.root_dir={args.root_dir}/logs",
                "checkpoint.save_last=True",
                "buffer.memmap=False",
                f"seed={args.seed}",
                "algo.per_rank_batch_size=4",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
                "algo.mlp_keys.encoder=[state]",
                f"algo.total_steps={args.total_steps}",
                f"algo.num_players={args.players}",
                f"algo.decoupled_transport={args.transport}",
                "algo.run_test=False",
                "algo.inference=remote",
                "algo.serve.request_timeout_s=0.25",
                "algo.serve.max_retries=1",
                "algo.serve.breaker_threshold=2",
                "algo.serve.breaker_cooldown_s=1.0",
                "algo.serve.restart_backoff_s=0.2",
                f"algo.serve.restart_budget={kills + 1}",
                f"root_dir={args.root_dir}/run",
                "env.num_envs=4",
                "algo.rollout_steps=4",
                "algo.update_epochs=1",
            ]
        )
    finally:
        os.environ.pop("SHEEPRL_FAULTS", None)

    client, server = read_serve(os.path.join(args.root_dir, "run"))
    failures = audit_serve(client, server, kills=kills)
    failures += run_serve_hot_swap_leg(args.root_dir)
    print(
        json.dumps(
            {
                "client": client,
                "server": {k: v for k, v in (server or {}).items() if k != "batch_hist"},
                "failures": failures,
            },
            indent=2,
        )
    )
    if not args.keep:
        shutil.rmtree(args.root_dir, ignore_errors=True)
    if failures:
        print("SERVE CHAOS SOAK FAILED", file=sys.stderr)
        return 1
    print("serve chaos soak passed")
    return 0


# ------------------------------------------------------------- scale
def read_scale(root_dir: str):
    """Last transport record plus the run's ``autoscale`` flight events
    and player scale-up/retire events (obs/reader.py)."""
    from sheeprl_tpu.obs.reader import iter_run_records, read_flight

    last = None
    for rec in iter_run_records(root_dir):
        if "transport" in rec:
            last = rec["transport"]
    events = [r for r in read_flight(root_dir) if r.get("k") == "event"]
    scaling = [r for r in events if r.get("name") == "autoscale"]
    spawns = [r for r in events if r.get("name") == "player_scale_up"]
    deaths = [r for r in events if r.get("name") == "player_dead"]
    return last, scaling, spawns, deaths


def audit_scale(last, scaling, spawns, deaths, *, players: int, start_players: int) -> list:
    """The elastic-pool convergence audit: the pool must START at the
    autoscaler minimum, GROW on measured pressure (typed ``autoscale``
    flight events, not inference), absorb the mid-scale-up kill, and end
    converged at the configured maximum.  The kill can be healed by
    EITHER actuator — the supervisor's budgeted restart, or (usually,
    since the pool is under sustained pressure and the backoff-delayed
    restart loses the race) the autoscaler's next grow refilling the
    dead slot through the same join machinery; both count, what matters
    is a real death and a reconverged pool."""
    failures = []
    if last is None:
        return ["no transport telemetry found (did the lead die without re-mastering?)"]
    grows = [e for e in scaling if (e.get("a") or {}).get("action") == "grow"]
    need = players - start_players
    if len(grows) < need:
        failures.append(f"only {len(grows)} autoscale grow events for {need} needed slots")
    if len(spawns) < need:
        failures.append(f"only {len(spawns)} player_scale_up events for {need} needed slots")
    first_sizes = [int((e.get("a") or {}).get("size", -1)) for e in grows]
    if grows and start_players not in first_sizes:
        failures.append(
            f"no grow fired from the configured minimum {start_players} "
            f"(sizes seen: {first_sizes}) — pool did not start small"
        )
    pool = last.get("live", 0) + last.get("joining", 0)
    if pool < players:
        failures.append(f"pool never converged: live+joining={pool} < {players}")
    if not deaths:
        failures.append("no player_dead flight event — the scheduled kill never landed")
    restarts = (last.get("supervisor") or {}).get("restarts", 0)
    if restarts < 1 and len(spawns) <= need:
        failures.append(
            f"the kill was never healed: supervisor restarts={restarts} and only "
            f"{len(spawns)} scale-up spawns for {need} vacant slots (no refill)"
        )
    scale_stats = last.get("autoscale") or {}
    if scale_stats.get("grows", 0) < need:
        failures.append(f"telemetry autoscale.grows={scale_stats.get('grows')} < {need}")
    return failures


def run_scale_serve_leg(root: str, seed: int) -> list:
    """Deterministic serving sub-leg: a session swarm against an elastic
    pool whose session cache is DELIBERATELY smaller than the client
    count — every client must survive the resulting ``session_lost``
    storm by reopen-and-replay with zero dropped requests — plus the
    nan-poisoned hot-swap candidate a session server must refuse."""
    import time
    import warnings as _warnings

    import numpy as np

    from scripts.swarm import run_pool_swarm, synthetic_session_parts
    from sheeprl_tpu.serve import SessionInferenceServer, agent_params_loader
    from sheeprl_tpu.utils.ckpt_format import save_state

    failures = []
    clients = 12
    report, stats = run_pool_swarm(
        clients=clients,
        steps=8,
        rows=1,
        think_mean_ms=2.0,
        think_sigma=1.0,
        pool_min=1,
        pool_max=2,
        seed=seed,
        session_capacity=clients // 3,  # thrash: forced LRU evictions
        slo_target_ms=10_000.0,  # latency is not this leg's subject
    )
    d = report.as_dict()
    if d["dropped"] != 0:
        failures.append(f"{d['dropped']} requests dropped under session-cache thrash")
    if d["session_losses"] < 1:
        failures.append("tiny session cache never evicted a live session (session_lost unexercised)")
    if d["session_reopens"] < d["session_losses"]:
        failures.append(
            f"{d['session_losses']} session losses but only {d['session_reopens']} reopens"
        )
    sess = (stats.get("sessions") or {})
    if sess.get("evictions_lru", 0) < 1:
        failures.append(f"no LRU evictions recorded: {sess}")

    # hot-swap refusal on a SESSION server: the newer-but-poisoned
    # candidate is refused, the older finite one applied (PR-8 contract
    # carried over the session decorator)
    params, session_fn, init_fn, _, _ = synthetic_session_parts(seed)
    ckpt_dir = os.path.join(root, "scale_hot_swap", "checkpoint")
    os.makedirs(ckpt_dir, exist_ok=True)
    flat_params = {"agent": {"w": np.full((4,), 2.0, np.float32)}}
    good = save_state(os.path.join(ckpt_dir, "ckpt_100_0.ckpt"), flat_params)
    time.sleep(0.02)
    poisoned = {"agent": {"w": np.full((4,), np.nan, np.float32)}}
    save_state(os.path.join(ckpt_dir, "ckpt_200_0.ckpt"), poisoned)
    srv = SessionInferenceServer(
        None, params, session_policy_fn=session_fn, init_state_fn=init_fn, capacity=8
    )
    srv.watch(os.path.join(root, "scale_hot_swap"), agent_params_loader("agent"), interval_s=1e6)
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore")
        swapped = srv.poll_hot_swap()
    st = srv.stats()["swaps"]
    if st["refused_invalid"] < 1:
        failures.append("nan-poisoned checkpoint was NOT refused by the session server")
    if swapped != os.path.abspath(good) or st["applied"] != 1:
        failures.append(f"good checkpoint not swapped in (swapped={swapped}, stats={st})")
    srv.close()
    print(
        json.dumps(
            {
                "swarm": {
                    k: d[k]
                    for k in (
                        "dropped",
                        "session_losses",
                        "session_reopens",
                        "actions_per_s",
                        "latency_ms",
                    )
                },
                "sessions": sess,
                "pool_autoscale": stats.get("autoscale"),
                "hot_swap": st,
            },
            indent=2,
        )
    )
    return failures


def run_scale_mode(args) -> int:
    """ISSUE 20 acceptance soak, two legs.  TRAINING: a decoupled run
    whose player pool starts at the autoscaler minimum (1), is grown by
    the telemetry-driven autoscaler under forced gather pressure, loses
    its initially-spawned player a few iterations in — while the pool is
    still scaling up — and must still converge to the configured
    maximum with the kill restarted — all asserted from typed flight
    events and telemetry.  SERVING: the session-cache-thrash swarm plus
    the poisoned hot-swap refusal (:func:`run_scale_serve_leg`)."""
    import shutil

    players = max(2, min(args.players, 3))
    # the kill targets player 0 — the one slot spawned at startup.  The
    # autoscaled slots come up through supervisor._launch, which strips
    # their own player_exit entries (a respawned player must not re-fire
    # its predecessor's kill), so only the initial spawn can die; its
    # 6th own-iteration lands while the pool is still growing
    faults = "player_exit:6:0"
    print(f"scale chaos schedule (seed {args.seed}): SHEEPRL_FAULTS={faults}")

    shutil.rmtree(args.root_dir, ignore_errors=True)
    os.environ["SHEEPRL_FAULTS"] = faults
    from sheeprl_tpu.cli import run

    try:
        run(
            [
                "exp=ppo_decoupled",
                "env=dummy",
                "env.sync_env=True",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "fabric.devices=1",
                "metric.log_level=1",
                "metric.log_every=64",
                "metric.tracing=full",  # the audit reads typed flight events
                f"metric.logger.root_dir={args.root_dir}/logs",
                "checkpoint.save_last=True",
                "buffer.memmap=False",
                f"seed={args.seed}",
                "algo.per_rank_batch_size=4",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
                "algo.mlp_keys.encoder=[state]",
                f"algo.total_steps={args.total_steps}",
                f"algo.num_players={players}",
                f"algo.decoupled_transport={args.transport}",
                "algo.run_test=False",
                "algo.supervisor.enabled=True",
                "algo.supervisor.backoff_base=0.1",
                "algo.supervisor.restart_budget=3",
                "algo.autoscaler.enabled=True",
                "algo.autoscaler.min_players=1",
                "algo.autoscaler.up_window_s=0.01",
                "algo.autoscaler.up_cooldown_s=0.1",
                "algo.autoscaler.down_window_s=600",
                # always-pressure: every gather wait >= 0 — the pool must
                # march from 1 to num_players through the real spawn path
                "algo.autoscaler.gather_wait_pressure_s=0.0",
                f"root_dir={args.root_dir}/run",
                "env.num_envs=4",
                "algo.rollout_steps=4",
                "algo.update_epochs=1",
            ]
        )
    finally:
        os.environ.pop("SHEEPRL_FAULTS", None)

    last, scaling, spawns, deaths = read_scale(os.path.join(args.root_dir, "run"))
    failures = audit_scale(last, scaling, spawns, deaths, players=players, start_players=1)
    print(
        json.dumps(
            {
                "pool": {
                    "live": (last or {}).get("live"),
                    "joining": (last or {}).get("joining"),
                    "deaths": (last or {}).get("deaths"),
                    "rejoins": (last or {}).get("rejoins"),
                },
                "autoscale": (last or {}).get("autoscale"),
                "supervisor": (last or {}).get("supervisor"),
                "events": {
                    "autoscale": [e.get("a") for e in scaling],
                    "player_scale_up": len(spawns),
                    "player_dead": len(deaths),
                },
                "failures": failures,
            },
            indent=2,
        )
    )
    failures += run_scale_serve_leg(args.root_dir, args.seed)
    if not args.keep:
        shutil.rmtree(args.root_dir, ignore_errors=True)
    if failures:
        print("SCALE CHAOS SOAK FAILED", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print("scale chaos soak passed")
    return 0


# ------------------------------------------------------------- integrity
def _ppo_integrity_args(args, root: str, integrity: str, transport: str, total_steps: int):
    return [
        "exp=ppo_decoupled",
        "env=dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        "fabric.accelerator=cpu",
        "fabric.devices=1",
        "metric.log_level=1",
        "metric.log_every=64",
        f"metric.logger.root_dir={root}/logs",
        "checkpoint.save_last=True",
        "buffer.memmap=False",
        f"seed={args.seed}",
        "algo.per_rank_batch_size=4",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.mlp_keys.encoder=[state]",
        f"algo.total_steps={total_steps}",
        "algo.num_players=2",
        f"algo.decoupled_transport={transport}",
        f"algo.transport_integrity={integrity}",
        "algo.run_test=False",
        f"root_dir={root}/run",
        "env.num_envs=4",
        "algo.rollout_steps=4",
        "algo.update_epochs=1",
    ]


def read_integrity(root_dir: str):
    """Last lead ``integrity`` record + the trainer-side counters that
    ride ``transport.integrity`` / ``replay.integrity``, + the last
    ``replay`` record (for the ingest-quarantine leg)."""
    from sheeprl_tpu.obs.reader import iter_run_records

    lead, trainer, replay = {}, {}, {}
    for rec in iter_run_records(root_dir):
        if "integrity" in rec:
            lead = rec["integrity"]
        tr = rec.get("transport") or {}
        if "integrity" in tr:
            trainer = tr["integrity"]
        rp = rec.get("replay") or {}
        if rp:
            replay = rp
            if "integrity" in rp:
                trainer = rp["integrity"]
    return lead, trainer, replay


def audit_integrity(lead, trainer, *, data_flips: int, params_flips: int, transport: str) -> list:
    """Every injected flip must be DETECTED somewhere (data flips at the
    trainer's receive boundary, the lead-directed params flip at the
    lead's), every retransmission must have recovered, and nothing may
    have been silently accepted: detections >= injections, with the
    injection counters themselves riding the same telemetry."""
    failures = []
    if not lead or not trainer:
        return [f"[{transport}] integrity telemetry missing (lead={bool(lead)}, trainer={bool(trainer)})"]
    if trainer.get("frames_corrupt", 0) < data_flips:
        failures.append(
            f"[{transport}] trainer detected {trainer.get('frames_corrupt')} corrupt data "
            f"frames for {data_flips} injected"
        )
    lead_detected = lead.get("frames_corrupt", 0) + lead.get("params_digest_mismatch", 0)
    if lead_detected < params_flips:
        failures.append(
            f"[{transport}] lead detected {lead_detected} corrupt params broadcasts "
            f"for {params_flips} injected"
        )
    for side, rec in (("lead", lead), ("trainer", trainer)):
        if rec.get("retrans_failed", 0):
            failures.append(f"[{transport}] {side} gave up on {rec['retrans_failed']} retransmissions")
    detected = trainer.get("corrupt_detected", 0) + lead.get("corrupt_detected", 0)
    injected = data_flips + params_flips
    silent = injected - detected
    if silent > 0:
        failures.append(f"[{transport}] silent_accepted={silent} (injected {injected}, detected {detected})")
    return failures


def _load_agent_tree(root: str):
    """Newest checkpoint's agent subtree as a flat list of arrays (file
    md5s are useless here: the zip layer stamps wall-clock timestamps)."""
    import numpy as np

    from sheeprl_tpu.utils.ckpt_format import load_state

    ckpts = sorted(
        glob.glob(os.path.join(root, "**", "ckpt_*.ckpt"), recursive=True),
        key=os.path.getmtime,
    )
    if not ckpts:
        return None
    state = load_state(ckpts[-1], select=("agent",))
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(state["agent"])]


def run_integrity_mode(args) -> int:
    """ISSUE 10 acceptance soak: on every transport backend, a decoupled
    run under injected ``bit_flip`` faults must DETECT every flip at the
    receive boundary, recover through the retransmit/digest-skip paths,
    and finish rc=0 with the ``integrity`` telemetry proving it.  Plus:
    an rb_insert leg (remote-replay SAC + ``rb_corrupt`` must be
    quarantined at ingest, not silently absorbed) and a paired
    off-vs-crc leg whose final agent params must be BIT-EXACT (crc mode
    perturbs nothing; off mode constructs the pre-integrity objects)."""
    import shutil

    import numpy as np

    from sheeprl_tpu.cli import run

    from sheeprl_tpu.resilience.integrity import reset_integrity_stats

    total_steps = 2560 if args.total_steps == 19200 else args.total_steps
    failures = []
    # one data flip at each player's Nth and Mth shard, one params flip
    # on the trainer's odd-numbered params send — with 2 players the odd
    # sends go to player 0, so the detection lands in the LEAD's
    # telemetry (FanIn.broadcast iterates live pids in order).  The hit
    # counts DIFFER per leg on purpose: the trainer process hosts every
    # leg, and the fault injector is a process-wide singleton keyed on
    # the spec string — an identical spec would stay consumed.
    for idx, transport in enumerate(("queue", "shm", "tcp")):
        faults = f"bit_flip@data:{4 + idx},bit_flip@data:{8 + idx},bit_flip@params:{5 + 2 * idx}"
        root = os.path.join(args.root_dir, transport)
        shutil.rmtree(root, ignore_errors=True)
        print(f"integrity leg [{transport}]: SHEEPRL_FAULTS={faults}")
        reset_integrity_stats()  # trainer-side counters are per-process
        os.environ["SHEEPRL_FAULTS"] = faults
        try:
            run(_ppo_integrity_args(args, root, "digest", transport, total_steps))
        except SystemExit as e:
            if e.code not in (0, None):
                failures.append(f"[{transport}] run exited rc={e.code}")
        finally:
            os.environ.pop("SHEEPRL_FAULTS", None)
        lead, trainer, _ = read_integrity(os.path.join(root, "run"))
        failures += audit_integrity(
            lead, trainer, data_flips=4, params_flips=1, transport=transport
        )
        print(json.dumps({"transport": transport, "lead": lead, "trainer": trainer}))

    # ---- rb_insert leg: rb_corrupt must be detected at ingest
    root = os.path.join(args.root_dir, "rb")
    shutil.rmtree(root, ignore_errors=True)
    print("integrity leg [rb_insert]: SHEEPRL_FAULTS=rb_corrupt:12")
    reset_integrity_stats()
    os.environ["SHEEPRL_FAULTS"] = "rb_corrupt:12"
    try:
        run(
            [
                "exp=sac_decoupled",
                "env=dummy",
                "env.id=dummy_continuous",
                "env.num_envs=2",
                "env.sync_env=True",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "fabric.devices=1",
                "metric.log_level=1",
                "metric.log_every=64",
                f"metric.logger.root_dir={root}/logs",
                "checkpoint.save_last=True",
                "buffer.memmap=False",
                "buffer.remote_replay=True",
                "buffer.prioritized=True",
                "algo.num_players=2",
                "algo.per_rank_batch_size=4",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
                "algo.mlp_keys.encoder=[state]",
                "algo.total_steps=640",
                "algo.learning_starts=8",
                "algo.decoupled_transport=queue",
                "algo.transport_integrity=crc",
                "algo.run_test=False",
                f"seed={args.seed}",
                f"root_dir={root}/run",
            ]
        )
    except SystemExit as e:
        if e.code not in (0, None):
            failures.append(f"[rb_insert] run exited rc={e.code}")
    finally:
        os.environ.pop("SHEEPRL_FAULTS", None)
    _, _, replay = read_integrity(os.path.join(root, "run"))
    if replay.get("inserts_quarantined", 0) < 1:
        failures.append(
            f"[rb_insert] rb_corrupt was not quarantined at ingest "
            f"(inserts_quarantined={replay.get('inserts_quarantined')})"
        )
    print(json.dumps({"leg": "rb_insert", "inserts_quarantined": replay.get("inserts_quarantined")}))

    # ---- paired off/crc leg: crc mode must be bit-exact with off mode
    trees = {}
    for integrity in ("off", "crc"):
        root = os.path.join(args.root_dir, f"exact_{integrity}")
        shutil.rmtree(root, ignore_errors=True)
        try:
            run(_ppo_integrity_args(args, root, integrity, "queue", 640))
        except SystemExit as e:
            if e.code not in (0, None):
                failures.append(f"[bit-exact/{integrity}] run exited rc={e.code}")
        trees[integrity] = _load_agent_tree(root)
    if trees.get("off") is None or trees.get("crc") is None:
        failures.append("[bit-exact] a paired run produced no checkpoint")
    elif not all(np.array_equal(a, b) for a, b in zip(trees["off"], trees["crc"])):
        failures.append("[bit-exact] transport_integrity=crc changed the trained agent params")
    else:
        print(json.dumps({"leg": "bit-exact", "leaves": len(trees["off"]), "equal": True}))

    if not args.keep:
        import shutil as _sh

        _sh.rmtree(args.root_dir, ignore_errors=True)
    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        print("INTEGRITY CHAOS SOAK FAILED", file=sys.stderr)
        return 1
    print("integrity chaos soak passed")
    return 0


# ------------------------------------------------------------------- ckpt
def _ckpt_cli_code(root: str, mesh_shape: str, seed: int, total_steps: int, resume: bool) -> str:
    """The a2c fsdp leg as a ``python -c`` payload: phase 1 must run in a
    SUBPROCESS because ``ckpt_shard_kill`` SIGKILLs the writing process —
    in-process it would take the soak harness down with it."""
    cli = [
        "exp=a2c",
        "env=dummy",
        "env.sync_env=True",
        "env.capture_video=False",
        "env.num_envs=8",
        "fabric.accelerator=cpu",
        "fabric.devices=8",
        "fabric.strategy=fsdp",
        f"fabric.mesh_shape={mesh_shape}",
        "metric.log_level=1",
        "metric.log_every=64",
        f"metric.logger.root_dir={root}/logs",
        "checkpoint.save_last=True",
        "checkpoint.every=64",
        "checkpoint.sharded=True",
        "buffer.memmap=False",
        f"seed={seed}",
        f"algo.total_steps={total_steps}",
        "algo.rollout_steps=8",
        "algo.per_rank_batch_size=8",
        "algo.dense_units=8",
        "algo.mlp_layers=1",
        "algo.mlp_keys.encoder=[state]",
        "algo.run_test=False",
        f"root_dir={root}/run",
    ]
    if resume:
        cli.append("checkpoint.resume_from=auto")
    return "import sys; sys.path.insert(0, {!r})\nfrom sheeprl_tpu.cli import run\nrun({!r})".format(
        _REPO_ROOT, cli
    )


def _scan_dckpts(run_root: str):
    """(complete, partial) sharded-checkpoint directories under a run
    root: complete == the manifest committed (the rename is the atomicity
    point), partial == a writer died before it."""
    dckpts = sorted(glob.glob(os.path.join(run_root, "**", "ckpt_*.dckpt"), recursive=True))
    complete = [d for d in dckpts if os.path.exists(os.path.join(d, "MANIFEST.json"))]
    return complete, [d for d in dckpts if d not in complete]


def read_ckpt_stats(root_dir: str):
    """Every ``ckpt``-keyed telemetry record under a run root (the
    CheckpointManager stats the PR-1 sink interleaves)."""
    from sheeprl_tpu.obs.reader import iter_run_records

    out = []
    for rec in iter_run_records(root_dir):
        if rec.get("ckpt"):
            out.append(rec["ckpt"])
    return out


def run_ckpt_mode(args) -> int:
    """ISSUE 17 acceptance: kill-mid-shard-write must leave a PARTIAL
    directory auto-resume walks past, and the relaunch must reshard the
    last COMPLETE manifest onto a different mesh and finish rc=0."""
    import shutil
    import subprocess

    total_steps = 1280 if args.total_steps == 19200 else args.total_steps
    base = args.root_dir
    shutil.rmtree(base, ignore_errors=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    env.pop("SHEEPRL_FAULTS", None)
    failures = []

    # ---- phase 1: 4x2 mesh, killed during the SECOND checkpoint's shard
    # writes (hits 1-2 are checkpoint #1's two shards; hit 3 is #2's first)
    print("ckpt leg phase 1 (4x2): SHEEPRL_FAULTS=ckpt_shard_kill:3")
    p1 = subprocess.run(
        [sys.executable, "-c", _ckpt_cli_code(base, "4x2", args.seed, total_steps, resume=False)],
        env=dict(env, SHEEPRL_FAULTS="ckpt_shard_kill:3"),
        capture_output=True,
        text=True,
        timeout=600,
    )
    if p1.returncode != -9:
        failures.append(f"phase 1 exited rc={p1.returncode}, expected SIGKILL (-9)")
    complete, partial = _scan_dckpts(os.path.join(base, "run"))
    if not complete:
        failures.append("phase 1 left no COMPLETE manifest before the kill")
    if not partial:
        failures.append("phase 1 left no partial directory (kill landed outside a save?)")
    stats1 = read_ckpt_stats(os.path.join(base, "run"))
    if not any(s.get("sharded") and s.get("shards") == 2 for s in stats1):
        failures.append("phase 1 telemetry never carried 2-shard ckpt stats")
    runs1 = set(glob.glob(os.path.join(base, "run", "*")))

    # ---- phase 2: same root, DIFFERENT mesh (2x4 -> fsdp 2 becomes 4),
    # resume_from=auto must refuse the partial dir and reshard the rest
    print("ckpt leg phase 2 (2x4): checkpoint.resume_from=auto")
    p2 = subprocess.run(
        [sys.executable, "-c", _ckpt_cli_code(base, "2x4", args.seed, total_steps, resume=True)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    if p2.returncode != 0:
        failures.append(
            f"phase 2 exited rc={p2.returncode}: {p2.stdout[-1500:]}{p2.stderr[-1500:]}"
        )
    expect = f"auto-resume: resuming from {complete[-1]}" if complete else "auto-resume:"
    if expect not in p2.stdout:
        failures.append(f"phase 2 did not resume from the last complete manifest {complete[-1:]}")
    if "skipping corrupt checkpoint" not in (p2.stdout + p2.stderr):
        failures.append("phase 2 never reported walking past the partial directory")

    # the relaunch re-sharded onto the new mesh: its committed manifests
    # carry fsdp_size 4, and its telemetry a 4-shard ckpt section
    complete2, _ = _scan_dckpts(os.path.join(base, "run"))
    new_manifests = [d for d in complete2 if d not in complete]
    if not new_manifests:
        failures.append("phase 2 committed no new manifest")
    else:
        for d in new_manifests:
            with open(os.path.join(d, "MANIFEST.json")) as f:
                doc = json.load(f)
            if int(doc["fsdp_size"]) != 4:
                failures.append(f"{os.path.basename(d)} has fsdp_size {doc['fsdp_size']}, not 4")
        from sheeprl_tpu.utils.ckpt_format import validate_checkpoint

        validate_checkpoint(new_manifests[-1], check_finite=True, check_digests=True)
    runs2 = sorted(set(glob.glob(os.path.join(base, "run", "*"))) - runs1)
    stats2 = []
    for rd in runs2:
        stats2 += read_ckpt_stats(rd)
    if not any(s.get("sharded") for s in stats2):
        failures.append("phase 2 telemetry never carried sharded ckpt stats")

    print(
        json.dumps(
            {
                "phase1_rc": p1.returncode,
                "complete": [os.path.basename(d) for d in complete],
                "partial": [os.path.basename(d) for d in partial],
                "phase2_rc": p2.returncode,
                "new_manifests": [os.path.basename(d) for d in new_manifests],
                "last_ckpt_stats": (stats2 or stats1 or [None])[-1],
                "failures": failures,
            },
            indent=2,
        )
    )
    if not args.keep:
        shutil.rmtree(base, ignore_errors=True)
    if failures:
        print("CKPT CHAOS SOAK FAILED", file=sys.stderr)
        return 1
    print("ckpt chaos soak passed")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--mode",
        default="topology",
        choices=("topology", "health", "serve", "integrity", "ckpt", "scale"),
        help=(
            "topology: kill/rejoin soak (ISSUE 6); health: training sentinel proof "
            "(ISSUE 7); serve: inference-service failure envelope (ISSUE 8); "
            "integrity: bit_flip detection/recovery on all three transports + "
            "rb_insert quarantine + off-vs-crc bit-exactness (ISSUE 10); "
            "ckpt: sharded-checkpoint kill-mid-shard + auto-resume onto a "
            "different mesh (ISSUE 17); scale: elastic-pool autoscaler "
            "convergence under a mid-scale-up kill + session-cache-thrash "
            "swarm + poisoned hot-swap refusal (ISSUE 20)"
        ),
    )
    ap.add_argument(
        "--fault",
        default="nan_inject",
        choices=("nan_inject", "loss_spike", "rb_corrupt"),
        help="health mode: which update fault arms the sentinel's adversary",
    )
    ap.add_argument("--players", type=int, default=4)
    ap.add_argument(
        "--transport",
        default=None,
        choices=("queue", "shm", "tcp"),
        help="default: tcp for topology mode, queue for health mode",
    )
    ap.add_argument("--kills", type=int, default=3)
    ap.add_argument("--net-drops", type=int, default=1)
    ap.add_argument("--net-delays", type=int, default=1)
    ap.add_argument("--total-steps", type=int, default=19200)
    ap.add_argument("--kill-span", type=int, default=60, help="iterations between kills")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--root-dir", default="/tmp/sheeprl_chaos_soak")
    ap.add_argument("--keep", action="store_true", help="keep the run dir for inspection")
    args = ap.parse_args(argv)

    if args.mode == "health":
        if args.root_dir == "/tmp/sheeprl_chaos_soak":
            args.root_dir = "/tmp/sheeprl_chaos_health"
        args.transport = args.transport or "queue"
        return run_health_mode(args)
    if args.mode == "integrity":
        if args.root_dir == "/tmp/sheeprl_chaos_soak":
            args.root_dir = "/tmp/sheeprl_chaos_integrity"
        return run_integrity_mode(args)
    if args.mode == "ckpt":
        if args.root_dir == "/tmp/sheeprl_chaos_soak":
            args.root_dir = "/tmp/sheeprl_chaos_ckpt"
        return run_ckpt_mode(args)
    if args.mode == "scale":
        if args.root_dir == "/tmp/sheeprl_chaos_soak":
            args.root_dir = "/tmp/sheeprl_chaos_scale"
        args.transport = args.transport or "queue"
        if args.players == 4:
            args.players = 3
        if args.total_steps == 19200:
            args.total_steps = 4800
        return run_scale_mode(args)
    if args.mode == "serve":
        if args.root_dir == "/tmp/sheeprl_chaos_soak":
            args.root_dir = "/tmp/sheeprl_chaos_serve"
        args.transport = args.transport or "queue"
        if args.players == 4:
            args.players = 2  # the serve envelope needs breadth, not depth
        if args.total_steps == 19200:
            args.total_steps = 9600
        return run_serve_mode(args)
    args.transport = args.transport or "tcp"

    rng = random.Random(args.seed)
    kill_entries, _ = build_kill_schedule(
        rng, args.players, args.kills, span=args.kill_span
    )
    entries = list(kill_entries)
    if args.transport == "tcp":
        entries += build_net_noise(rng, args.net_drops, args.net_delays)
    faults = ",".join(entries)
    print(f"chaos schedule (seed {args.seed}): SHEEPRL_FAULTS={faults}")

    import shutil

    shutil.rmtree(args.root_dir, ignore_errors=True)
    os.environ["SHEEPRL_FAULTS"] = faults
    from sheeprl_tpu.cli import run

    try:
        run(
            [
                "exp=ppo_decoupled",
                "env=dummy",
                "env.sync_env=True",
                "env.capture_video=False",
                "fabric.accelerator=cpu",
                "fabric.devices=1",
                "metric.log_level=1",
                "metric.log_every=64",
                f"metric.logger.root_dir={args.root_dir}/logs",
                "checkpoint.save_last=True",
                "buffer.memmap=False",
                f"seed={args.seed}",
                "algo.per_rank_batch_size=4",
                "algo.dense_units=8",
                "algo.mlp_layers=1",
                "algo.mlp_keys.encoder=[state]",
                f"algo.total_steps={args.total_steps}",
                f"algo.num_players={args.players}",
                f"algo.decoupled_transport={args.transport}",
                "algo.run_test=False",
                "algo.vtrace.enabled=True",
                "algo.supervisor.enabled=True",
                "algo.supervisor.backoff_base=0.1",
                f"algo.supervisor.restart_budget={args.kills + 2}",
                f"root_dir={args.root_dir}/run",
                "env.num_envs=4",
                "algo.rollout_steps=4",
                "algo.update_epochs=1",
            ]
        )
    finally:
        os.environ.pop("SHEEPRL_FAULTS", None)

    transports, compiles = read_telemetry(os.path.join(args.root_dir, "run"))
    failures = audit(transports, compiles, players=args.players, kills=args.kills)
    last = transports[-1] if transports else {}
    print(
        json.dumps(
            {
                "pool": {
                    "live": last.get("live"),
                    "joining": last.get("joining"),
                    "deaths": last.get("deaths"),
                    "rejoins": last.get("rejoins"),
                },
                "lag_hist": last.get("lag_hist"),
                "supervisor": last.get("supervisor"),
                "trainer_compiles": compiles[-1] if compiles else None,
                "failures": failures,
            },
            indent=2,
        )
    )
    if not args.keep:
        shutil.rmtree(args.root_dir, ignore_errors=True)
    if failures:
        print("CHAOS SOAK FAILED", file=sys.stderr)
        return 1
    print("chaos soak passed")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

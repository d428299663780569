#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that this tree still starts on the chip.

One process.  With no arguments, on one TPU chip:

1. DreamerV3-S at its published S widths (B=16, T=64, 64x64x3 pixels from
   ``env=dummy``) through ``sheeprl_tpu.cli.run`` — the function
   ``sheeprl.py`` calls: collect with the player, HBM replay
   (``buffer.device_cache=auto`` must admit), >= 32 gradient steps of the
   whole jitted update under ``fabric.precision=bf16-mixed``, async
   checkpoint, clean exit.  Only step counts, buffer size, env count and
   log/checkpoint switches are set down.
2. Every kernel option that exists, turned on for a few steps the same way;
   the lowered TPU program must hold a ``tpu_custom_call``.
3. The serving plane on the same chip: ``scripts/serve_policy.build_server``
   on that checkpoint answers a few dozen session requests, each checked
   against the in-process step of the same policy (see ``phase_serve``).

``--chips 4`` runs only the path across chips and what it is compared with:
the same update on one chip, on a ``dp`` mesh of four and on an ``fsdp``
mesh of four, each through ``cli.run``, then each on one seeded batch.
``--tiny`` is the CPU rehearsal of the same code at the widths of
``_dv3_tiny_args()`` in tests/test_algos/test_algos.py; it never reports a
pass.

Each phase prints one JSON line; the last line is the result and nothing
more: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase, or no TPU, gives ``"ok": false`` and a non-zero exit.
Timings printed here are smoke timings, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import io
import json
import math
import os
import shutil
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "runs", "chip_smoke")  # /runs/ is git-ignored

# four chips vs one chip on one seeded batch: same update, other reduction
# order; |got - one chip| <= RTOL * (|one chip| + FLOOR) for every loss (the
# floor keeps a loss that sits near zero, as the first policy loss does,
# from being held to its own rounding)
MESH_LOSS_RTOL = 2e-2
MESH_LOSS_FLOOR = 5e-2

# the widths of _dv3_tiny_args() (tests/test_chip_smoke.py holds the two equal)
TINY_WIDTHS = [
    "algo.per_rank_batch_size=2",
    "algo.per_rank_sequence_length=1",
    "algo.horizon=3",
    "algo.learning_starts=0",
    "algo.dense_units=8",
    "algo.mlp_layers=1",
    "algo.world_model.encoder.cnn_channels_multiplier=2",
    "algo.world_model.recurrent_model.recurrent_state_size=8",
    "algo.world_model.representation_model.hidden_size=8",
    "algo.world_model.transition_model.hidden_size=8",
    "algo.world_model.stochastic_size=4",
    "algo.world_model.discrete_size=4",
    "algo.world_model.reward_model.bins=15",
    "algo.critic.bins=15",
    "env.screen_size=16",
]

# every kernel option in sheeprl_tpu/ops that a config can turn on
KERNEL_OPTIONS = {
    "fused": ["algo.world_model.recurrent_model.fused=True"],
    "fused_seq": [
        "algo.world_model.decoupled_rssm=True",
        "algo.world_model.recurrent_model.fused_seq=True",
    ],
}


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


class SmokeFailure(Exception):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------- spy
class Spy:
    """Records what ``cli.run`` builds, by wrapping the two names
    ``dreamer_v3.main`` looks up at call time: the train-step builder and
    the replay-cache factory.  The program itself gains no hook."""

    def __init__(self, keep_first_state: bool = False):
        self.runtime = self.train_fn = self.cache = None
        self._keep_first_state = keep_first_state
        self.first = None  # avals (and, if asked, host copies) of the first update's arguments
        self.metrics = []  # per gradient step, device scalars (fetched at the end)
        self.last = None  # (state, batch, key) after the last update

    def __enter__(self):
        import sheeprl_tpu.algos.dreamer_v3.dreamer_v3 as dv3

        self._dv3 = dv3
        self._orig = (dv3.make_train_fn, dv3.maybe_create_for)
        dv3.make_train_fn = self._make_train_fn
        dv3.maybe_create_for = self._maybe_create_for
        return self

    def __exit__(self, *exc):
        self._dv3.make_train_fn, self._dv3.maybe_create_for = self._orig

    def _maybe_create_for(self, *args, **kwargs):
        self.cache = self._orig[1](*args, **kwargs)
        return self.cache

    def _make_train_fn(self, runtime, world_model, actor, critic, txs, cfg, is_continuous, actions_dim):
        import jax
        import numpy as np

        fn = self._orig[0](runtime, world_model, actor, critic, txs, cfg, is_continuous, actions_dim)
        self.runtime, self.train_fn = runtime, fn

        def stepped(params, opt_states, moments, batch, key):
            if self.first is None:
                args = (params, opt_states, moments, batch)
                self.first = {
                    # the state is donated by the call: copy it out first
                    "host": jax.tree_util.tree_map(np.asarray, args) if self._keep_first_state else None,
                    "avals": jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding), args
                    ),
                    "key": np.asarray(key),
                }
            out = fn(params, opt_states, moments, batch, key)
            self.metrics.append(out[3])
            self.last = (out[:3], batch, key)
            return out

        stepped.health = fn.health
        return stepped

    # ---- what the checks read
    def losses(self, name: str = "Loss/world_model_loss"):
        import jax

        return [float(v) for v in jax.device_get([m[name] for m in self.metrics])]

    def lowered(self):
        """The run's own update, lowered again for the shapes and shardings
        of its first call (``.as_text()``: what Pallas put in;
        ``.compile().as_text()``: what the partitioner put in)."""
        import jax

        with jax.set_mesh(self.runtime.mesh):
            return self.train_fn._jitted.lower(*self.first["avals"], self.first["key"])

    def step_on(self, host_args, key):
        """One update of THIS run's compiled step on the given host state
        and batch, placed exactly as the run placed its own."""
        import jax

        shardings = jax.tree_util.tree_map(lambda a: a.sharding, self.first["avals"])
        placed = jax.device_put(host_args, shardings)
        out = self.train_fn(*placed, key)
        return {k: float(v) for k, v in jax.device_get(out[3]).items()}


def run_cli(overrides, name: str, keep_first_state: bool = False):
    """``cli.run`` under a spy, its stdout kept (it carries the decisions
    the loop prints once: player device, replay cache)."""
    from sheeprl_tpu.cli import run

    spy = Spy(keep_first_state)
    captured = io.StringIO()
    t0 = time.perf_counter()
    with spy, contextlib.redirect_stdout(captured):
        run(list(overrides) + [f"root_dir={OUT}", f"run_name={name}"])
    text = captured.getvalue()
    sys.stderr.write(text[-4000:])
    return spy, text, time.perf_counter() - t0


def base_overrides(tiny: bool):
    ov = [
        "exp=dreamer_v3",
        "env=dummy",
        "fabric.precision=bf16-mixed",
        "buffer.checkpoint=False",
        "checkpoint.every=1000000",
    ]
    if tiny:
        # buffer.device_cache=auto is defined never to admit on a CPU
        return ov + TINY_WIDTHS + ["buffer.device_cache=True", "env.sync_env=True"]
    return ov


def steps_overrides(tiny: bool, grad_steps: int, num_envs: int = 4):
    starts = 16 if tiny else 512
    return [
        f"env.num_envs={num_envs}",
        f"algo.learning_starts={starts}",
        f"algo.total_steps={starts + grad_steps}",
        f"buffer.size={512 if tiny else 8192}",
        f"metric.log_every={16 if tiny else 64}",
    ]


def last_telemetry(run_name: str):
    from sheeprl_tpu.obs import read_records

    paths = glob.glob(os.path.join(OUT, run_name, "**", "telemetry.jsonl"), recursive=True)
    require(paths, f"{run_name}: no telemetry.jsonl written")
    records = list(read_records(paths[0]))
    require(records, f"{run_name}: telemetry.jsonl is empty")
    return records[-1]


def spread(name: str, array, devices) -> int:
    """``array`` must hold an addressable shard on every one of ``devices``
    (code that has only seen one chip may put everything on devices[0])."""
    held = {s.device for s in array.addressable_shards}
    missing = [str(d) for d in devices if d not in held]
    require(not missing, f"{name} has no shard on {missing} (holds {sorted(map(str, held))})")
    return len(held)


# ------------------------------------------------------------------ phases
def phase_train(tiny: bool, platform: str, monitor):
    import jax
    import numpy as np

    grad_steps = 64 if tiny else 256
    before = monitor.snapshot()
    spy, text, wall = run_cli(base_overrides(tiny) + steps_overrides(tiny, grad_steps), "train")
    after = monitor.snapshot()

    wm = spy.losses()
    require(len(wm) >= 32, f"only {len(wm)} gradient steps ran")
    every = {k: spy.losses(k) for k in spy.metrics[0] if k.startswith("Loss/")}
    bad = [k for k, v in every.items() if not np.all(np.isfinite(v))]
    require(not bad, f"non-finite losses: {bad}")
    require(wm[-1] < wm[0], f"world-model loss did not fall: first {wm[0]} last {wm[-1]}")

    cache = spy.cache
    require(cache is not None and cache.active and cache._bufs, "DeviceReplayCache stayed on the host path")
    ring_platforms = sorted({d.platform for v in cache._bufs.values() for d in v.devices()})
    require(ring_platforms == [platform], f"replay ring lives on {ring_platforms}, not {platform}")

    player = [ln for ln in text.splitlines() if ln.startswith("Player device:")]
    require(player, "the run did not print its player device")

    record = last_telemetry("train")
    post_warmup = record["compiles"]["post_warmup"]
    require(post_warmup == 0, f"{post_warmup} compiles after warm-up (telemetry)")

    ckpts = sorted(glob.glob(os.path.join(OUT, "train", "**", "ckpt_*.ckpt"), recursive=True))
    require(ckpts, "no checkpoint written")
    from sheeprl_tpu.utils.callback import load_checkpoint

    loaded = load_checkpoint(ckpts[-1])
    final_params = spy.last[0][0]
    for part in ("world_model", "actor", "critic"):
        want = jax.tree_util.tree_leaves(jax.device_get(final_params[part]))
        got = jax.tree_util.tree_leaves(loaded[part])
        require(
            len(want) == len(got) and all(np.array_equal(a, np.asarray(b)) for a, b in zip(want, got)),
            f"reloaded checkpoint differs from the trained {part}",
        )

    # steady step time: the run's own compiled step on its last batch,
    # chained, with one trailing block_until_ready
    state, batch, key = spy.last
    n_timed = 20
    t0 = time.perf_counter()
    for _ in range(n_timed):
        *state, _ = spy.train_fn(*state, batch, key)
    jax.block_until_ready(state)
    steady_ms = (time.perf_counter() - t0) / n_timed * 1e3

    emit(
        phase="train",
        ok=True,
        config="dreamer_v3 S B=16 T=64 64x64x3 bf16-mixed dyn_bptt" if not tiny else "dreamer_v3 tiny widths",
        gradient_steps=len(wm),
        wm_loss_first=wm[0],
        wm_loss_last=wm[-1],
        player_device=player[0][len("Player device: "):],
        replay_cache={
            "class": type(cache).__name__,
            "admitted": True,
            "ring_platform": ring_platforms[0],
            "capacity": cache.capacity,
            "n_envs": cache.n_envs,
        },
        compile_s=round(after["compile_time_s"] - before["compile_time_s"], 2),
        cache_hits=after["cache_hits"] - before["cache_hits"],
        cache_misses=after["cache_misses"] - before["cache_misses"],
        post_warmup_compiles=post_warmup,
        phase_wall_s=round(wall, 1),
        smoke_steady_step_ms=round(steady_ms, 2),
        checkpoint=os.path.relpath(ckpts[-1], REPO),
        checkpoint_reloaded_equal=True,
    )
    return ckpts[-1]


def phase_kernels(tiny: bool, platform: str):
    for name, option in KERNEL_OPTIONS.items():
        spy, _, wall = run_cli(
            base_overrides(tiny) + steps_overrides(tiny, 16) + option + ["algo.run_test=False"], name
        )
        wm = spy.losses()
        require(len(wm) >= 4, f"{name}: only {len(wm)} gradient steps ran")
        require(all(math.isfinite(v) for v in wm), f"{name}: non-finite world-model loss")
        held = None
        if platform == "tpu":
            held = "tpu_custom_call" in spy.lowered().as_text()
            require(held, f"{name}: the lowered TPU program holds no tpu_custom_call")
        emit(
            phase=f"kernel:{name}",
            ok=True,
            option=option,
            gradient_steps=len(wm),
            wm_loss_first=wm[0],
            wm_loss_last=wm[-1],
            tpu_custom_call=held,
            phase_wall_s=round(wall, 1),
        )


def phase_serve(ckpt: str, monitor):
    """``serve_policy.py --selftest``'s path, with every reply checked.

    Each served step is compared with the in-process step of the same
    adapter, taken from the server's own session state before it.  The
    server pads a batch up to a power-of-two bucket, one compiled program
    per bucket, and a padded bucket and a single row are not bit-equal on
    the chip as they are in the CPU tests (measured on a v5e: recurrent
    state off by 4e-3 between 1 row and 2, 4 or 8, under bf16-mixed).
    Within one program a row's result depends neither on its position nor
    on the other rows (measured bit-exact, chip and CPU).  So the reference
    is taken at every bucket, padded the way the server pads, and the
    served step — greedy action, sampled latent, recurrent state, key —
    must equal one of them bit for bit.  Tolerance: none."""
    import multiprocessing as mp

    import jax
    import numpy as np

    from scripts.serve_policy import build_server
    from scripts.swarm import warmup_buckets
    from sheeprl_tpu.parallel.transport import make_transport
    from sheeprl_tpu.serve import SessionClient, SessionInferenceServer

    n_clients, n_steps, max_batch = 4, 16, 8
    buckets = [b for b in (1, 2, 4, 8) if b <= max_batch]
    server, _, obs_keys, obs_space = build_server(ckpt, greedy=True, max_batch=max_batch)
    require(isinstance(server, SessionInferenceServer), "Dreamer did not get the session tier")
    session_fn, init_fn, params = server._session_policy_fn, server._init_state_fn, server._params
    served_on = sorted({d.platform for leaf in jax.tree_util.tree_leaves(params) for d in leaf.devices()})

    def zeros(rows):
        return {k: np.zeros((rows,) + tuple(obs_space[k].shape), np.float32) for k in obs_keys}

    warmup_buckets(session_fn, init_fn, params, zeros, max_batch)
    pad_obs = {b: zeros(b - 1) for b in buckets if b > 1}
    pad_state = {b: init_fn(b - 1, 0, params) for b in buckets if b > 1}
    compiles_before = monitor.snapshot()["total"]

    def reference(obs, before, bucket):
        """Row 0 of the in-process step at ``bucket`` rows: zero
        observations and throwaway init-state rows behind the real one."""
        if bucket > 1:
            obs = {k: np.concatenate([v, pad_obs[bucket][k]]) for k, v in obs.items()}
            before = {k: np.concatenate([before[k], pad_state[bucket][k]]) for k in before}
        out, after = session_fn(params, obs, before)
        return {"flat_actions": np.asarray(out["flat_actions"])[:1], **{k: np.asarray(v)[:1] for k, v in after.items()}}

    ctx = mp.get_context("spawn")
    hub, specs = make_transport(ctx, "queue", n_clients, window=4, min_bytes=0)
    clients = [
        SessionClient(specs[i].player_channel(), i, seed=100 + i, request_timeout_s=60.0)
        for i in range(n_clients)
    ]
    for i in range(n_clients):
        server.attach(i, hub.channel(i, timeout=5))
    server.start()

    matched = []  # per served step: the bucket whose reference it equals, or None
    off_single = {}  # per state key: the largest |served - single-row reference|
    errors = []

    def drive(cid: int) -> None:
        try:
            rng = np.random.default_rng(cid)
            before = init_fn(1, 100 + cid, params)
            for _ in range(n_steps):
                obs = {
                    k: rng.normal(size=(1,) + tuple(obs_space[k].shape)).astype(np.float32)
                    for k in obs_keys
                }
                out, src = clients[cid].step(list(obs.items()), 1)
                require(src == "remote" and out is not None, f"client {cid}: reply came from {src}")
                session = server.sessions.lookup(clients[cid].session_id)
                served = {"flat_actions": np.asarray(out["flat_actions"]).reshape(1, -1)}
                served.update({k: np.array(v) for k, v in session.state.items()})
                refs = {b: reference(obs, before, b) for b in buckets}
                matched.append(
                    next(
                        (b for b in reversed(buckets) if all(np.array_equal(served[k], refs[b][k]) for k in served)),
                        None,
                    )
                )
                for k, v in served.items():
                    diff = float(np.max(np.abs(v.astype(np.float64) - refs[1][k].astype(np.float64))))
                    off_single[k] = max(off_single.get(k, 0.0), diff)
                before = {k: v for k, v in served.items() if k != "flat_actions"}
            clients[cid].close_session()
        except Exception as e:  # a thread hands its failure to the phase
            errors.append(f"client {cid}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=drive, args=(i,)) for i in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    wall = time.perf_counter() - t0
    alive = [t.name for t in threads if t.is_alive()]
    stats = server.stats()
    server.close()
    for c in clients:
        c.close()
    hub.close()
    require(not alive, f"serving clients still running after 600 s: {alive}")
    require(not errors, "; ".join(errors))

    n = len(matched)
    unmatched = matched.count(None)
    ok = unmatched == 0 and n == n_clients * n_steps
    emit(
        phase="serve",
        ok=ok,
        params_on=served_on,
        requests=n,
        clients=n_clients,
        bit_equal_to_reference_at_bucket={str(b): matched.count(b) for b in buckets},
        unmatched=unmatched,
        max_abs_diff_vs_single_row=off_single,
        # the server builds throwaway init-state rows for each pad count it
        # meets (sessions.py), which compiles on first meeting: reported
        compiles_after_bucket_warmup=monitor.snapshot()["total"] - compiles_before,
        served=stats["requests"],
        batches=stats["batches"],
        batch_hist=stats["batch_hist"],
        phase_wall_s=round(wall, 2),
    )
    require(ok, f"{n} of {n_clients * n_steps} requests answered, {unmatched} served steps equal no in-process reference: {off_single}")


def phase_mesh(tiny: bool, platform: str):
    """One chip, dp4, fsdp4: each through cli.run, then on one seeded batch."""
    import jax
    import numpy as np

    devices = jax.devices()[:4]
    require(len({d.id for d in devices}) == 4, f"need 4 devices, JAX reports {len(jax.devices())}")
    require(all(d.platform == platform for d in devices), "the four devices are not one platform")

    common = base_overrides(tiny) + ["buffer.device_cache=True", "algo.run_test=False"]
    global_batch = 4 if tiny else 16  # B=16 is the S config's own; the tiny one must divide by four
    one, _, wall = run_cli(
        common + steps_overrides(tiny, 16) + ["fabric.devices=1", f"algo.per_rank_batch_size={global_batch}"],
        "one_chip",
        keep_first_state=True,
    )
    seeded = one.first["host"]
    key = one.first["key"]
    reference = one.step_on(seeded, key)
    emit(phase="mesh:one_chip", ok=True, first_step=reference, phase_wall_s=round(wall, 1))

    for strategy, collective in (("dp", "all-reduce"), ("fsdp", "all-gather")):
        spy, _, wall = run_cli(
            common
            + steps_overrides(tiny, 16, num_envs=1)
            + [
                "fabric.devices=4",
                f"fabric.strategy={strategy}",
                f"algo.per_rank_batch_size={global_batch // 4}",
            ],
            strategy,
        )
        mesh_devices = list(spy.runtime.mesh.devices.ravel())
        require(len({d.id for d in mesh_devices}) == 4, f"{strategy}: mesh holds {mesh_devices}")
        require(all(d.platform == platform for d in mesh_devices), f"{strategy}: mesh is not all {platform}")

        wm = spy.losses()
        require(len(wm) >= 4 and all(np.isfinite(wm)), f"{strategy}: losses {wm}")
        cache = spy.cache
        require(type(cache).__name__ == "ShardedDeviceReplayCache", f"{strategy}: replay cache is {type(cache).__name__}")
        require(cache.active and cache._bufs, f"{strategy}: sharded replay cache stayed on the host path")
        ring_key, ring = max(cache._bufs.items(), key=lambda kv: kv[1].nbytes)
        spread(f"{strategy} replay ring '{ring_key}'", ring, mesh_devices)
        batch_key, batch_leaf = max(spy.last[1].items(), key=lambda kv: kv[1].nbytes)
        spread(f"{strategy} batch '{batch_key}'", batch_leaf, mesh_devices)
        largest = max(jax.tree_util.tree_leaves(spy.last[0][0]), key=lambda x: x.nbytes)
        spread(f"{strategy} largest parameter", largest, mesh_devices)
        shard_shape = tuple(largest.addressable_shards[0].data.shape)
        if strategy == "fsdp":
            require(
                int(np.prod(shard_shape)) * 4 == int(np.prod(largest.shape)),
                f"fsdp: largest parameter {largest.shape} is held as {shard_shape} per device",
            )

        require(collective in spy.lowered().compile().as_text(), f"{strategy}: the compiled update holds no {collective}")

        # the seeded batch: the global batch is the one chip's (the per-rank
        # batch was divided by four), so the host trees carry over as they are
        got = spy.step_on(seeded, key)
        worst = max(
            abs(got[k] - reference[k]) / (abs(reference[k]) + MESH_LOSS_FLOOR)
            for k in reference
            if k.startswith("Loss/")
        )
        require(worst <= MESH_LOSS_RTOL, f"{strategy}: first-step losses off by {worst:.3g} relative: {got} vs {reference}")
        emit(
            phase=f"mesh:{strategy}4",
            ok=True,
            mesh={k: int(v) for k, v in spy.runtime.mesh.shape.items()},
            devices=[str(d) for d in mesh_devices],
            gradient_steps=len(wm),
            ring_shards=len(ring.addressable_shards),
            batch_shards=len(batch_leaf.addressable_shards),
            largest_param={"shape": list(largest.shape), "per_device": list(shard_shape)},
            collective=collective,
            first_step=got,
            worst_rel_loss_diff=worst,
            loss_rtol=MESH_LOSS_RTOL,
            loss_floor=MESH_LOSS_FLOOR,
            phase_wall_s=round(wall, 1),
        )


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal at tiny widths; never a pass")
    args = ap.parse_args(argv)

    # the device is read before anything else touches a backend
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}

    def result(ok: bool, reason: str = "") -> int:
        line = {"ok": ok, "device": device}
        if reason:
            line["reason"] = reason
        print(json.dumps(line), flush=True)
        return 0 if ok else 1

    if dev.platform != "tpu" and not args.tiny:
        return result(False, "no tpu")
    if len(jax.devices()) < args.chips:
        return result(False, f"--chips {args.chips} on {len(jax.devices())} device(s)")

    import jaxlib

    from sheeprl_tpu.obs import RecompileMonitor
    from sheeprl_tpu.parallel.mesh import configure_compilation_cache

    cache_dir = configure_compilation_cache()
    monitor = RecompileMonitor(name="chip_smoke", warn=False).install()
    shutil.rmtree(OUT, ignore_errors=True)
    try:
        import libtpu

        libtpu_version = getattr(libtpu, "__version__", None)
    except ImportError:
        libtpu_version = None
    emit(
        phase="device",
        ok=True,
        jax=jax.__version__,
        jaxlib=jaxlib.__version__,
        libtpu=libtpu_version,
        compilation_cache_dir=cache_dir,
        cache_dir_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        mode=("tiny " if args.tiny else "") + f"{args.chips} chip(s)",
        **device,
    )

    failed = ""
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(args.tiny, dev.platform)
        else:
            ckpt = phase_train(args.tiny, dev.platform, monitor)
            phase_kernels(args.tiny, dev.platform)
            phase_serve(ckpt, monitor)
    except SmokeFailure as e:
        failed = str(e)
    except Exception as e:  # the boundary: any failed phase is a failed smoke, with its traceback
        import traceback

        traceback.print_exc()
        failed = f"{type(e).__name__}: {e}"
    finally:
        monitor.uninstall()
    if failed:
        emit(phase="failed", ok=False, error=failed)
    stats = dev.memory_stats() or {}
    emit(
        phase="totals",
        ok=not failed,
        wall_s=round(time.perf_counter() - t0, 1),
        compiles=monitor.snapshot(),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
    )
    if failed:
        return result(False, failed)
    if dev.platform != "tpu":
        return result(False, "no tpu")
    if args.tiny:
        return result(False, "tiny rehearsal")
    return result(True)


if __name__ == "__main__":
    sys.exit(main())

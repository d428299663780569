"""Remote replay service for the decoupled N-player topology.

In the PR-4 decoupled SAC, each player owns a shard of the replay buffer
and ships SAMPLED BATCHES to the trainer — the experience path is
whatever the rollout transport does, the trainer has no say in what it
trains on, and prioritization is impossible (no process sees the whole
buffer).  Reverb's architecture (Cassirer et al., 2021) inverts this:
the buffer lives WITH the learner, actors stream raw experience into it,
and the learner samples under its own policy.  This module is that
inversion over the existing ``queue|shm|tcp`` transports:

- :class:`ReplayWriter` — the player-side endpoint: ships each env
  step's ``(T, n_envs, *)`` block as one ``rb_insert`` frame and blocks
  on INSERT CREDITS granted by the trainer (the rate limiter's reach
  across the transport: a trainer that falls behind simply stops
  granting, and the player's stall shows up in telemetry);
- :class:`ReplayServer` — the trainer-side endpoint: drains insert
  frames from all N players into a trainer-resident
  ``EnvIndependentReplayBuffer`` (+ the prioritized ``DeviceReplayCache``
  when ``buffer.prioritized``), routes each player's columns to its env
  shard, seeds priorities on write (max-priority insert), feeds the
  limiter, and grants credits while the SPI budget allows.

The experience path becomes player → replay-writer → prioritized-sampler
instead of player-side uniform sampling.  Everything runs on the trainer
MAIN thread (``pump`` is a bounded drain, not a daemon), so the buffer
needs no locks and the ``replay_server_exit`` fault site can model a
crash of the whole service between two pumps.
"""

from __future__ import annotations

import os
import queue as queue_mod
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from sheeprl_tpu.obs import flight
from sheeprl_tpu.resilience.integrity import FrameCorruptError
from sheeprl_tpu.resilience.peer import PeerDiedError

# wire tags of the replay service (the transport treats tags opaquely;
# transport.py re-exports these so the frame vocabulary is documented in
# one place next to data/params/stop)
RB_INSERT_TAG = "rb_insert"
RB_CREDIT_TAG = "rb_credit"

__all__ = [
    "RB_CREDIT_TAG",
    "RB_INSERT_TAG",
    "ReplayServer",
    "ReplayWriter",
    "remote_replay_setting",
]


def remote_replay_setting(cfg) -> bool:
    """Resolve ``buffer.remote_replay`` (env override
    ``SHEEPRL_REMOTE_REPLAY``) to a bool."""
    val = cfg.buffer.get("remote_replay", False)
    env = os.environ.get("SHEEPRL_REMOTE_REPLAY")
    if env is not None:
        val = env
    return str(val).lower() in ("1", "true", "on", "yes")


class ReplayWriter:
    """Player-side insert endpoint over one transport :class:`Channel`.

    ``append`` consumes one insert credit per frame and blocks (pumping
    the channel) when the trainer has stopped granting — that block IS
    the samples-per-insert limiter acting on this player.  Non-credit
    frames drained while pumping (params broadcasts, checkpoint replies)
    land in :attr:`frames` for the caller.
    """

    def __init__(self, channel, n_envs: int, *, initial_credits: int = 2):
        self._chan = channel
        self.n_envs = int(n_envs)
        self.credits = int(initial_credits)
        self.seq = 0
        self.inserts = 0  # transitions shipped
        self.stalls = 0
        self.stall_s = 0.0
        self.frames: deque = deque()  # non-credit frames for the caller

    def pump(self, timeout: float = 0.01) -> None:
        """Drain whatever the channel has within ``timeout``: credits are
        applied, everything else queues for the caller."""
        deadline = time.monotonic() + timeout
        while True:
            remaining = max(deadline - time.monotonic(), 0.01)
            try:
                frame = self._chan.recv(timeout=remaining)
            except queue_mod.Empty:
                return
            if frame.tag == RB_CREDIT_TAG:
                self.credits += int(frame.extra[0]) if frame.extra else 1
                frame.release()
            else:
                self.frames.append(frame)
            if time.monotonic() > deadline:
                return

    def append(
        self,
        step_data: Dict[str, np.ndarray],
        timeout: float = 600.0,
        summary: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Ship one ``(T, n_envs, *)`` block as an ``rb_insert`` frame;
        blocks while no credit is available (limiter throttle).
        ``summary`` (ISSUE 15) piggybacks this player's compact
        live-metrics dict on the frame's extra — the server folds it into
        its fleet view."""
        t_len = next(iter(step_data.values())).shape[0]
        if self.credits <= 0:
            self.stalls += 1
            t0 = time.monotonic()
            deadline = t0 + timeout
            try:
                while self.credits <= 0:
                    if time.monotonic() > deadline:
                        raise queue_mod.Full(
                            f"replay writer starved of insert credits for {timeout:.0f}s "
                            "(trainer stalled or rate limiter budget misconfigured)"
                        )
                    self.pump(0.2)  # PeerDiedError propagates from the channel
            finally:
                self.stall_s += time.monotonic() - t0
        self.credits -= 1
        self.seq += 1
        self._chan.send(
            RB_INSERT_TAG,
            arrays=[(k, v) for k, v in step_data.items()],
            extra=(t_len * self.n_envs,) + ((summary,) if summary is not None else ()),
            seq=self.seq,
            timeout=timeout,
        )
        self.inserts += t_len * self.n_envs

    def stats(self) -> Dict[str, Any]:
        return {
            "inserts": self.inserts,
            "credits": self.credits,
            "insert_stalls": self.stalls,
            "insert_stall_s": round(self.stall_s, 3),
        }


class ReplayServer:
    """Trainer-side replay service: buffer + sampler + credit granting.

    ``channels`` / ``env_shards`` come from ``spawn_players``; the server
    routes player ``p``'s columns into env indices
    ``[offset_p, offset_p + count_p)`` of one trainer-resident
    ``EnvIndependentReplayBuffer`` (per-env rings tolerate players
    inserting at different speeds).  With ``prioritized`` a
    :class:`~sheeprl_tpu.data.device_buffer.DeviceReplayCache` mirrors the
    buffer on the training device and sampling goes through its sum-tree;
    otherwise sampling is the host buffer's uniform path.
    """

    def __init__(
        self,
        buffer_size: int,
        env_shards: Sequence[Tuple[int, int]],
        channels: Dict[int, Any],
        *,
        obs_keys: Sequence[str] = ("observations",),
        limiter=None,
        prioritized: bool = False,
        per_alpha: float = 0.6,
        per_eps: float = 1e-6,
        device=None,
        memmap: bool = False,
        memmap_dir: Optional[str] = None,
        credit_window: int = 2,
        integrity: str = "off",
        ingest_max_abs: float = 1e6,
    ):
        from sheeprl_tpu.data.buffers import EnvIndependentReplayBuffer
        from sheeprl_tpu.data.device_buffer import DeviceReplayCache

        self.env_shards = list(env_shards)
        total_envs = sum(count for _, count in self.env_shards)
        self.total_envs = total_envs
        self.buffer_size = int(buffer_size)
        self.rb = EnvIndependentReplayBuffer(
            self.buffer_size,
            total_envs,
            obs_keys=tuple(obs_keys),
            memmap=memmap,
            memmap_dir=memmap_dir,
        )
        self.prioritized = bool(prioritized)
        self.cache: Optional[DeviceReplayCache] = (
            DeviceReplayCache(
                self.buffer_size,
                total_envs,
                device=device,
                prioritized=True,
                per_alpha=per_alpha,
                per_eps=per_eps,
            )
            if self.prioritized
            else None
        )
        self.limiter = limiter
        self.channels = dict(channels)
        self.credit_window = max(1, int(credit_window))
        # credits in flight per player (granted, not yet consumed by an
        # ingested frame) — the writer starts with the same initial window
        self._outstanding = {pid: self.credit_window for pid in self.channels}
        self.stopped: set = set()
        self.dead: Dict[int, str] = {}
        # elastic-pool bookkeeping (the supervisor's expected surface —
        # remote players are stateless writers, so ``joining`` is
        # transient: a revived pid is fully live the moment it reconnects)
        self.joining: Dict[int, float] = {}
        self.last_seen: Dict[int, float] = {}
        self._awaiting_first_frame: set = set()
        self.rejoins = 0
        self.events: List[Dict[str, Any]] = []
        self.total_inserts = 0  # transitions (the trainer's policy-step clock)
        self.inserts_by_player = {pid: 0 for pid in self.channels}
        # per-player live-metrics summaries piggybacked on rb_insert
        # frames (ISSUE 15); rides stats() to the lead's /status
        self.fleet: Dict[int, Dict[str, Any]] = {}
        self.credit_stall_players = 0  # grant attempts refused by the limiter
        # insert -> first-sample freshness (ISSUE 16): arrival times of
        # inserts no sample() has run since; the next sample() reads the
        # oldest as the first_sample_age_s SLO gauge and drains the list
        self._unsampled_insert_ts: deque = deque(maxlen=1024)
        self.first_sample_age_s: Optional[float] = None
        # training-sentinel quarantine bookkeeping: ring rows written per
        # env since the last verdict-clean horizon (mark_health_horizon)
        self._rows_since_mark = np.zeros(total_envs, dtype=np.int64)
        self.quarantines = 0
        self.quarantined_rows = 0
        # ingest validation (algo.transport_integrity != off): schema +
        # bounds + finiteness checks on every rb_insert BEFORE it can
        # reach the learner (resilience/integrity.py) — the boundary
        # where the rb_corrupt fault class is *detected* instead of
        # silently absorbed
        self._ingest_guard = None
        if str(integrity) != "off":
            from sheeprl_tpu.resilience.integrity import IngestGuard

            self._ingest_guard = IngestGuard(max_abs=ingest_max_abs)
        self.inserts_quarantined = 0

    # ------------------------------------------------------------ liveness
    @property
    def live(self) -> List[int]:
        return sorted(p for p in self.channels if p not in self.dead and p not in self.stopped)

    @property
    def all_stopped(self) -> bool:
        return not self.live

    def _mark_dead(self, pid: int, reason: str) -> None:
        if pid in self.dead or pid in self.stopped:
            return
        ch = self.channels.get(pid)
        detail = ""
        if ch is not None and getattr(ch, "detail_fn", None) is not None:
            try:
                detail = ch.detail_fn() or ""
            except Exception:
                detail = ""
        # a clean exit means the player finished; its stop frame may have
        # been destroyed by a TCP reset (see FanIn.mark_dead)
        self._awaiting_first_frame.discard(pid)
        if "exitcode=0" in detail.replace(" ", ""):
            self.stopped.add(pid)
            return
        self.dead[pid] = reason
        self.events.append(
            {"event": "player_dead", "player": pid, "reason": reason, "live": len(self.live)}
        )
        if not self.live and not self.stopped and not self.joining:
            raise PeerDiedError(
                "player", "; ".join(f"player[{p}]: {r}" for p, r in self.dead.items())
            )

    # the supervisor calls the public name (FanIn parity)
    def mark_dead(self, pid: int, reason: str) -> None:
        self._mark_dead(pid, reason)

    def begin_join(self, pid: int, channel=None, steps_per_frame: Optional[int] = None) -> None:
        """Re-admit a restarted player (the supervisor's revival hook).

        The stale credit window died with the old process: a fresh
        :class:`ReplayWriter` comes up believing it holds the full initial
        window, so ``_outstanding`` is RESET to match — without this the
        server would under-grant forever (it thinks credits are still in
        flight) and a rejoined player would deadlock on its first stall."""
        if channel is not None:
            self.channels[pid] = channel
        self.dead.pop(pid, None)
        self.stopped.discard(pid)
        self._outstanding[pid] = self.credit_window
        self.inserts_by_player.setdefault(pid, 0)
        # until its first frame lands, sends to a tcp joiner would stall
        # on a socket it has not dialed yet — broadcasts skip it
        self._awaiting_first_frame.add(pid)
        self.rejoins += 1
        self.events.append({"event": "player_rejoin", "player": pid, "live": len(self.live)})

    # ---------------------------------------------------------------- pump
    def pump(self, budget_s: float = 0.05, on_control: Optional[Callable] = None) -> int:
        """Drain available ``rb_insert`` frames from every live player and
        re-grant credits; returns transitions ingested.  Control frames
        (``ckpt_req`` etc.) go to ``on_control``; runs on the caller's
        thread — bounded by ``budget_s``, never blocks on an idle player."""
        got = 0
        t_pump = time.time()
        deadline = time.monotonic() + budget_s
        while True:
            any_frame = False
            for pid in list(self.live):
                ch = self.channels[pid]
                try:
                    frame = ch.recv(timeout=0.01)
                except queue_mod.Empty:
                    continue
                except PeerDiedError as e:
                    self._mark_dead(pid, str(e))
                    continue
                except FrameCorruptError as e:
                    # unrecoverable frame corruption (integrity layer
                    # give-up): the frame is lost, the channel and the
                    # service keep running — FanIn.gather parity
                    self.events.append(
                        {"event": "frame_corrupt_dropped", "player": pid, "detail": str(e)}
                    )
                    continue
                any_frame = True
                self.last_seen[pid] = time.monotonic()
                self._awaiting_first_frame.discard(pid)
                if frame.tag == "stop":
                    self.stopped.add(pid)
                    frame.release()
                elif frame.tag == RB_INSERT_TAG:
                    got += self._ingest(pid, frame)
                elif on_control is not None:
                    on_control(pid, frame)
                else:
                    frame.release()
            self.grant_credits()
            if not any_frame or time.monotonic() > deadline:
                break
        if got:
            rec = flight.get_recorder()
            if rec is not None:
                rec.span_done("replay_pump", t_pump, time.time(), {"transitions": got})
                rec.sampled_event("replay_insert", "rb_insert", total=self.total_inserts)
        return got

    def _ingest(self, pid: int, frame) -> int:
        offset, count = self.env_shards[pid]
        extra = getattr(frame, "extra", ()) or ()
        if len(extra) > 1 and isinstance(extra[1], dict):
            # the player's piggybacked live-metrics summary (ISSUE 15)
            self.fleet[pid] = dict(extra[1])
        arrays = frame.arrays_copy()  # transport buffers go back on release
        frame.release()
        t_len = next(iter(arrays.values())).shape[0]
        # fault site (resilience/faults.py): a poisoned replay batch
        # entering the service — scribble this insert frame's payload
        from sheeprl_tpu.resilience.faults import fault_arg, fault_point

        if fault_point("rb_corrupt"):
            scale = fault_arg("rb_corrupt") or 1e8
            arrays = {
                k: (
                    np.random.default_rng(0).standard_normal(v.shape).astype(v.dtype)
                    * v.dtype.type(scale)
                    if v.dtype.kind == "f"
                    else v
                )
                for k, v in arrays.items()
            }
        # ingest validation AFTER the fault site, so rb_corrupt (and real
        # SDC that slipped past the wire checksum) is DETECTED here:
        # schema violations cannot be stored at all; value violations
        # (non-finite / absurd magnitude) are quarantined — on the
        # prioritized path they are written but immediately floored to
        # the epsilon priority (the sampler effectively never draws
        # them; the ring overwrites them in time), on the uniform path
        # (no per-row mask) they are dropped outright
        reason = None
        if self._ingest_guard is not None:
            from sheeprl_tpu.resilience.integrity import integrity_stats

            st = integrity_stats()
            st.inserts_checked += 1
            reason = self._ingest_guard.check(arrays)
            if reason is not None:
                st.inserts_quarantined += 1
                self.inserts_quarantined += 1
                self._outstanding[pid] = max(0, self._outstanding[pid] - 1)
                self.events.append(
                    {"event": "insert_quarantined", "player": pid, "reason": reason}
                )
                flight.fleet_event("insert_quarantined", player=pid, reason=reason)
                if self.cache is None or "schema" in reason or "dtype" in reason or "shape" in reason or "key set" in reason:
                    return 0  # unstorable / uniform path: drop the frame
        indices = list(range(offset, offset + count))
        self.rb.add(arrays, indices=indices)
        if self.cache is not None:
            self.cache.add(arrays, indices=indices)
        n = t_len * count
        if reason is not None and self.cache is not None:
            # epsilon-priority-floor quarantine (same mechanism as
            # quarantine_recent): the rows were written to keep the ring
            # clocks consistent, but their priorities drop to the floor
            import jax.numpy as jnp

            cap = self.cache.capacity
            n_envs = self.total_envs
            idx_list = []
            for env in range(offset, offset + count):
                pos = int(self.cache._pos[env])
                recent = (pos - 1 - np.arange(min(t_len, cap))) % cap
                idx_list.append(recent * n_envs + env)
            idx = np.concatenate(idx_list)
            self.cache.update_priorities(jnp.asarray(idx), jnp.zeros(len(idx), jnp.float32))
            self.quarantined_rows += t_len * count
        self.total_inserts += n
        self.inserts_by_player[pid] += n
        self._unsampled_insert_ts.append(time.time())
        self._rows_since_mark[offset : offset + count] += t_len
        if self.limiter is not None:
            self.limiter.insert(n)
        self._outstanding[pid] = max(0, self._outstanding[pid] - 1)
        return n

    def grant_credits(self) -> None:
        """Top every live player back up to ``credit_window`` outstanding
        frames — but only while the limiter's insert budget (including
        credits already in flight) allows.  Withholding here is what makes
        a stalled trainer throttle its players."""
        for pid in list(self.live):
            if pid in self._awaiting_first_frame:
                continue  # revived player still dialing back in
            offset, count = self.env_shards[pid]
            while self._outstanding[pid] < self.credit_window:
                if self.limiter is not None:
                    pending = sum(
                        self._outstanding[p] * self.env_shards[p][1] for p in self.live
                    )
                    if not self.limiter.can_insert(pending + count):
                        self.credit_stall_players += 1
                        return
                try:
                    self.channels[pid].send(RB_CREDIT_TAG, extra=(1,), timeout=10.0)
                except (PeerDiedError, queue_mod.Full, OSError) as e:
                    self._mark_dead(pid, f"credit grant failed: {e}")
                    break
                self._outstanding[pid] += 1

    # -------------------------------------------------------------- sample
    def data_ready(self, need_per_env: int = 1) -> bool:
        """True once every env ring holds ``need_per_env`` rows (a lagging
        player delays readiness — by design: the batch must cover the
        whole env population, same as the coupled loop's prefill)."""
        for sub in self.rb.buffer:
            stored = sub.buffer_size if sub.full else sub._pos
            if stored < need_per_env:
                return False
        return True

    def sample(
        self,
        g: int,
        batch_size: int,
        key,
        beta: float,
        sample_next_obs: bool = False,
        obs_keys: Sequence[str] = ("observations",),
    ):
        """Draw ``g`` gradient-step batches; returns ``(data, idx)`` where
        ``data`` is the (g, batch, *) float32 pytree (plus ``is_weights``
        when prioritized) and ``idx`` feeds :meth:`update_priorities`
        (None on the uniform path)."""
        import jax.numpy as jnp

        idx = None
        if self.cache is not None and self.cache.can_sample_transitions(sample_next_obs):
            sampled, idx = self.cache.sample_transitions_per(
                g, batch_size, key, beta, sample_next_obs=sample_next_obs, obs_keys=obs_keys
            )
            data = {k: v.astype(jnp.float32) for k, v in sampled.items()}
        else:
            sample = self.rb.sample(batch_size=g * batch_size, sample_next_obs=sample_next_obs)
            data = {
                k: np.asarray(v, np.float32).reshape(g, batch_size, *v.shape[2:])
                for k, v in sample.items()
            }
            if self.prioritized:
                # cache not ready/disabled: unweighted uniform fallback
                data["is_weights"] = np.ones((g, batch_size, 1), np.float32)
        if self.limiter is not None:
            self.limiter.sample(g * batch_size)
        if self._unsampled_insert_ts:
            # freshness gauge: how stale was the OLDEST insert this is
            # the first sample to cover (the replay_age SLO input)
            self.first_sample_age_s = round(time.time() - self._unsampled_insert_ts[0], 4)
            self._unsampled_insert_ts.clear()
        flight.sampled_event("replay_sample", "replay_sample", total=self.total_inserts)
        return data, idx

    def update_priorities(self, idx, td_abs) -> None:
        if self.cache is not None and idx is not None:
            self.cache.update_priorities(idx, td_abs)

    # ------------------------------------------------------- health hooks
    def mark_health_horizon(self) -> None:
        """Sentinel hook: the latest update dispatched on this buffer was
        verdict-clean, so everything written up to now is trusted — resets
        the quarantine window."""
        self._rows_since_mark[:] = 0

    def quarantine_recent(self) -> int:
        """Rollback hook: the inserts newer than the last verdict-clean
        horizon are suspect (they fed — or were concurrent with — the
        anomalous updates).  On the prioritized path their sum-tree
        priorities drop to the epsilon floor, so the sampler effectively
        never draws them again (the ring overwrites them in time).  The
        uniform path has no per-row mask — the event is still recorded so
        the telemetry shows the exposure.  Returns rows quarantined."""
        rows = 0
        if self.cache is not None and getattr(self.cache, "_tree", None) is not None:
            import jax.numpy as jnp

            n_envs = self.total_envs
            cap = self.cache.capacity
            idx_list = []
            for env in range(n_envs):
                r = int(min(self._rows_since_mark[env], cap))
                if r <= 0:
                    continue
                pos = int(self.cache._pos[env])
                recent = (pos - 1 - np.arange(r)) % cap
                idx_list.append(recent * n_envs + env)
                rows += r
            if idx_list:
                idx = np.concatenate(idx_list)
                # |TD| = 0 -> priority (0 + eps)^alpha: the floor
                self.cache.update_priorities(jnp.asarray(idx), jnp.zeros(len(idx), jnp.float32))
        else:
            rows = int(self._rows_since_mark.sum())
        self.quarantines += 1
        self.quarantined_rows += rows
        self._rows_since_mark[:] = 0
        self.events.append(
            {"event": "replay_quarantine", "rows": rows, "prioritized": self.prioritized}
        )
        return rows

    # --------------------------------------------------------- checkpoint
    def state_dict(self) -> Dict[str, Any]:
        """Tree + limiter + clock (plain numpy/dicts).  The buffer itself
        is NOT nested here: the checkpoint snapshot machinery only
        materializes a buffer at the TOP-LEVEL ``rb`` key, so the caller
        ships ``self.rb`` separately (see sac_decoupled's remote ckpt)."""
        state: Dict[str, Any] = {"total_inserts": self.total_inserts}
        if self.cache is not None:
            state["replay_priority"] = self.cache.priority_state()
        if self.limiter is not None:
            state["rate_limiter"] = self.limiter.state_dict()
        return state

    def load_state_dict(self, state: Dict[str, Any], rb_state=None) -> None:
        from sheeprl_tpu.utils.callback import restore_buffer

        if rb_state is not None:
            restored = restore_buffer(rb_state, memmap=False)
            if restored.n_envs != self.total_envs or restored.buffer_size != self.buffer_size:
                raise RuntimeError(
                    f"restored replay service buffer ({restored.n_envs} envs x "
                    f"{restored.buffer_size}) does not match this topology "
                    f"({self.total_envs} x {self.buffer_size})"
                )
            self.rb = restored
            if self.cache is not None:
                self.cache.load_from(self.rb)
        if self.cache is not None:
            self.cache.load_priority_state(state.get("replay_priority"))
        if self.limiter is not None and state.get("rate_limiter"):
            self.limiter.load_state_dict(state["rate_limiter"])
        self.total_inserts = int(state.get("total_inserts", 0))

    # ---------------------------------------------------------- telemetry
    def stats(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "remote": True,
            "prioritized": self.prioritized,
            "inserts": self.total_inserts,
            "players": {
                str(p): {
                    "inserts": self.inserts_by_player.get(p, 0),
                    "credits_outstanding": self._outstanding.get(p, 0),
                    "alive": p in self.live,
                }
                for p in self.channels
            },
            "live": len(self.live),
            "deaths": len(self.dead),
            "rejoins": self.rejoins,
            "credit_grant_stalls": self.credit_stall_players,
            "first_sample_age_s": self.first_sample_age_s,
            "quarantines": self.quarantines,
            "quarantined_rows": self.quarantined_rows,
            "inserts_quarantined": self.inserts_quarantined,
        }
        if self.limiter is not None:
            rec["limiter"] = self.limiter.stats()
        if self.fleet:
            rec["fleet"] = {str(pid): dict(s) for pid, s in sorted(self.fleet.items())}
        return rec

    @property
    def broadcast_targets(self):
        """Live players safe to push params at (a revived tcp player that
        has not dialed back yet is excluded — a send would stall on its
        dead socket until the reconnect)."""
        return [p for p in self.live if p not in self._awaiting_first_frame]

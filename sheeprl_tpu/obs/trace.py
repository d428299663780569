"""jax.profiler integration: phase annotations + on-demand trace capture.

Two complementary pieces:

- ``trace_scope(name)`` — a near-zero-cost ``TraceAnnotation`` wrapper the
  algo loops put around their host-side phases (env interaction,
  host->device feed, train dispatch, block-until-ready, decoupled IPC
  waits). When no trace is being captured the annotation is a no-op at the
  C++ level; when one is, the phases show up as named spans on the host
  timeline of the XLA trace, which is what lets a TensorBoard reader
  attribute wall-clock to "waiting on envs" vs "waiting on the device" vs
  "waiting on the link" (the decoupled topology's stalls, ISSUE 1).
- ``ProfileScheduler`` — config-driven windowed capture
  (``metric.profile_every_n`` / ``metric.profile_num_iters`` /
  ``metric.profile_dir``): every N training iterations it starts a
  ``jax.profiler`` trace and stops it ``profile_num_iters`` iterations
  later, so a TensorBoard-readable XLA trace can be pulled from ANY
  long-running job without restarting it with ``metric.profile=True``
  (whole-run traces grow with wall-clock; windows stay small).

Traces are written under ``<profile_dir>`` in the TensorBoard profile
plugin layout; view with ``tensorboard --logdir <profile_dir>``.
"""

from __future__ import annotations

import os
from contextlib import ExitStack, contextmanager
from typing import Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation


def trace_scope(name: str):
    """Context manager annotating the enclosed host-side phase in any
    active jax.profiler trace. No-op-cheap when nothing is tracing.

    Under ``SHEEPRL_SANITIZE=1`` the scope additionally carries the
    transfer-guard policy for its name (analysis/sanitizers.py): phases
    that must stay transfer-silent (``host_to_device`` uploads, IPC
    serialization) run under ``jax.transfer_guard("disallow")`` so an
    implicit device→host sync fails loudly at its source; the allowlisted
    fetch phases (``block_until_ready`` & friends) re-allow explicitly.
    Sanitize off: the guard import never happens — the annotation is the
    whole cost, exactly as before."""
    if os.environ.get("SHEEPRL_SANITIZE", "").strip().lower() in ("1", "true", "yes", "on"):
        return _sanitized_scope(name)
    return _TraceAnnotation(name)


@contextmanager
def _sanitized_scope(name: str):
    from sheeprl_tpu.analysis.sanitizers import transfer_sanitizer

    with ExitStack() as stack:
        stack.enter_context(_TraceAnnotation(name))
        stack.enter_context(transfer_sanitizer(name))
        yield


_ACTIVE_TRACE_DIR: Optional[str] = None


def start_trace(trace_dir: str) -> bool:
    """Start a jax.profiler trace into ``trace_dir`` (created if missing).

    Returns False when a trace is already active (windows cannot nest).
    A window that was asked for and cannot start is an error: the
    profiler's exception propagates."""
    global _ACTIVE_TRACE_DIR
    if _ACTIVE_TRACE_DIR is not None:
        return False
    import jax

    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    _ACTIVE_TRACE_DIR = trace_dir
    return True


def stop_trace() -> Optional[str]:
    """Stop the active trace; returns its directory (None if none active)."""
    global _ACTIVE_TRACE_DIR
    if _ACTIVE_TRACE_DIR is None:
        return None
    import jax

    out, _ACTIVE_TRACE_DIR = _ACTIVE_TRACE_DIR, None
    jax.profiler.stop_trace()
    return out


def trace_active() -> bool:
    return _ACTIVE_TRACE_DIR is not None


class ProfileScheduler:
    """Windowed on-demand trace capture driven by the iteration counter.

    ``on_iteration`` is called once per training iteration; capture starts
    at iterations ``every_n, 2*every_n, ...`` (never the first iteration,
    whose XLA compiles would bloat the trace with one-time work) and stops
    ``num_iters`` iterations later. Disabled when ``every_n <= 0``.
    """

    def __init__(self, trace_dir: str, every_n: int, num_iters: int = 2):
        self.trace_dir = trace_dir
        self.every_n = int(every_n)
        self.num_iters = max(1, int(num_iters))
        self._iter = 0
        self._stop_at: Optional[int] = None
        self.captures = 0

    def on_iteration(self) -> None:
        if self.every_n <= 0:
            return
        self._iter += 1
        if self._stop_at is not None:
            if self._iter >= self._stop_at:
                stop_trace()
                self._stop_at = None
            return
        if self._iter % self.every_n == 0 and start_trace(self.trace_dir):
            self.captures += 1
            self._stop_at = self._iter + self.num_iters

    def close(self) -> None:
        if self._stop_at is not None:
            stop_trace()
            self._stop_at = None

"""Low-overhead JSONL run-telemetry sink.

Every algo loop appends one record per log interval to
``<log_dir>/telemetry.jsonl``: step counters, wall-clock throughput,
timer sums AND percentiles (p50/p95 — a single slow outlier iteration is
invisible in the sums the TensorBoard metrics carry), device
``memory_stats()`` HBM usage, host RSS, and cumulative XLA compile
counts. The file is machine-parseable (one JSON object per line) so a
perf investigation can diff two runs with ``jq`` instead of spelunking
TensorBoard, and the driver's bench harness appends its own summary
records to the same format.

Writes happen once per log interval (default every 5000 policy steps) on
an already-open fd with line buffering — the overhead is one json.dumps +
one write syscall, measured <<1% of even a tiny CPU A2C loop. Rotation
caps disk usage on long runs: when the file would exceed ``max_bytes``
it is renamed to ``telemetry.jsonl.1`` (one backup generation) and a
fresh file is started.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

TELEMETRY_SCHEMA_VERSION = 2
# versioned schema stamp carried by EVERY record (ISSUE 13): readers
# route on the string ("sheeprl.telemetry/2", "sheeprl.flight/1",
# "sheeprl.alert/1", ...) instead of guessing from key shapes; bump the
# suffix on breaking layout changes.  "v" stays for pre-13 consumers.
# v2 (ISSUE 15): "hbm" is ABSENT on backends that report no memory
# stats (it was a null that broke naive consumers), and alert records
# ("sheeprl.alert/1", obs/metrics.py) may interleave in the stream.
TELEMETRY_SCHEMA = f"sheeprl.telemetry/{TELEMETRY_SCHEMA_VERSION}"

# field -> allowed python types after json round-trip (None = nullable)
_NUM = (int, float)
TELEMETRY_REQUIRED_FIELDS: Dict[str, tuple] = {
    "schema": (str,),
    "v": (int,),
    "ts": _NUM,
    "step": (int,),
    "train_step": (int,),
    "sps": _NUM + (type(None),),
    "sps_env": _NUM + (type(None),),
    "sps_train": _NUM + (type(None),),
    "timers_s": (dict,),
    "timer_percentiles_s": (dict,),
    "host_rss_mb": _NUM + (type(None),),
    "compiles": (dict,),
}
# present-if-reported fields (validated when present, never required)
TELEMETRY_OPTIONAL_FIELDS: Dict[str, tuple] = {
    "hbm": (dict,),
    # streaming time-ledger breakdown (obs/ledger.py, metric.ledger=on)
    "where": (dict,),
}


def validate_record(record: Any) -> List[str]:
    """Schema check for one telemetry record; returns a list of problems
    (empty = valid). Used by the unit tests and the CI smoke test."""
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, expected dict"]
    errors = []
    for field, types in TELEMETRY_REQUIRED_FIELDS.items():
        if field not in record:
            errors.append(f"missing field '{field}'")
        elif not isinstance(record[field], types):
            errors.append(
                f"field '{field}' has type {type(record[field]).__name__}, "
                f"expected one of {tuple(t.__name__ for t in types)}"
            )
    for field, types in TELEMETRY_OPTIONAL_FIELDS.items():
        if field in record and not isinstance(record[field], types):
            errors.append(
                f"field '{field}' has type {type(record[field]).__name__}, "
                f"expected one of {tuple(t.__name__ for t in types)}"
            )
    if not errors and record["v"] != TELEMETRY_SCHEMA_VERSION:
        errors.append(f"schema version {record['v']} != {TELEMETRY_SCHEMA_VERSION}")
    if not errors and record["schema"] != TELEMETRY_SCHEMA:
        errors.append(f"schema {record['schema']!r} != {TELEMETRY_SCHEMA!r}")
    return errors


def read_records(path: str) -> List[Dict[str, Any]]:
    """Parse a telemetry JSONL file (skipping blank lines)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


class TelemetrySink:
    """Append-only JSONL writer with single-generation size rotation."""

    def __init__(self, path: str, max_bytes: int = 32 * 1024 * 1024):
        self.path = path
        self.max_bytes = int(max_bytes)
        self._file = None
        self._size = 0
        self.records_written = 0

    def _open(self) -> None:
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        self._file = open(self.path, "a", buffering=1)
        try:
            self._size = os.fstat(self._file.fileno()).st_size
        except OSError:
            self._size = 0

    def write(self, record: Dict[str, Any]) -> None:
        line = json.dumps(record, separators=(",", ":"), default=_json_default) + "\n"
        if self._file is None:
            self._open()
        if self.max_bytes > 0 and self._size + len(line) > self.max_bytes and self._size > 0:
            self._rotate()
        self._file.write(line)
        self._size += len(line)
        self.records_written += 1

    def _rotate(self) -> None:
        self._file.close()
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass
        self._file = None
        self._open()

    def flush(self) -> None:
        """Crash-safe flush: push buffered lines through the kernel to
        disk (``fsync``).  Called on the preemption/emergency-checkpoint
        paths so a post-mortem never loses the tail records — the ones
        that explain the crash."""
        if self._file is None:
            return
        try:
            self._file.flush()
            os.fsync(self._file.fileno())
        except (OSError, ValueError):
            pass

    def close(self) -> None:
        if self._file is not None:
            self._file.close()
            self._file = None


def _json_default(obj: Any) -> Any:
    """Last-resort conversion for numpy / jax scalars ending up in records."""
    try:
        return obj.item()
    except AttributeError:
        return str(obj)


# ----------------------------------------------------------------- probes
def host_rss_mb() -> Optional[float]:
    """Current resident set size of this process in MB (linux /proc; falls
    back to peak RSS from getrusage elsewhere)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        # ru_maxrss is KB on linux, bytes on macOS; report the linux unit
        return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)
    except Exception:
        return None


_HBM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_free_block_bytes")


def device_memory_stats(device: Any = None) -> Optional[Dict[str, int]]:
    """HBM usage of the training device via PJRT ``memory_stats()``; None
    on backends that do not report (CPU)."""
    if device is None:
        import jax

        try:
            device = jax.devices()[0]
        except Exception:
            return None
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    # CPU backends return None or {} — and a backend may report a key
    # with a None VALUE; the record must carry the key
    # as ABSENT, never as a null a downstream consumer trips over
    if not stats:
        return None
    out = {}
    for k in _HBM_KEYS:
        v = stats.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = int(v)
    return out or None


def make_record(
    *,
    step: int,
    train_step: int,
    sps: Optional[float] = None,
    sps_env: Optional[float] = None,
    sps_train: Optional[float] = None,
    timers_s: Optional[Dict[str, float]] = None,
    timer_percentiles_s: Optional[Dict[str, Dict[str, float]]] = None,
    hbm: Optional[Dict[str, int]] = None,
    host_rss: Optional[float] = None,
    compiles: Optional[Dict[str, Any]] = None,
    extra: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Assemble a schema-valid telemetry record (single source of truth for
    the field set — keep in sync with TELEMETRY_REQUIRED_FIELDS)."""
    record: Dict[str, Any] = {
        "schema": TELEMETRY_SCHEMA,
        "v": TELEMETRY_SCHEMA_VERSION,
        "ts": round(time.time(), 3),
        "step": int(step),
        "train_step": int(train_step),
        "sps": None if sps is None else round(float(sps), 2),
        "sps_env": None if sps_env is None else round(float(sps_env), 2),
        "sps_train": None if sps_train is None else round(float(sps_train), 2),
        "timers_s": {k: round(float(v), 6) for k, v in (timers_s or {}).items()},
        "timer_percentiles_s": timer_percentiles_s or {},
        "host_rss_mb": host_rss,
        "compiles": compiles or {},
    }
    # v2: no-HBM backends OMIT the key (a null here broke naive
    # downstream consumers computing used fractions)
    if hbm is not None:
        record["hbm"] = hbm
    if extra:
        record.update(extra)
    return record

"""JSONL telemetry sink: schema, rotation, probes (ISSUE 1 tentpole)."""

import json
import os

from sheeprl_tpu.obs.telemetry import (
    TELEMETRY_REQUIRED_FIELDS,
    TelemetrySink,
    host_rss_mb,
    make_record,
    read_records,
    validate_record,
)


def _record(step=1, **kw):
    return make_record(
        step=step,
        train_step=step,
        sps=100.0,
        timers_s={"Time/train_time": 0.5},
        timer_percentiles_s={"Time/train_time": {"p50": 0.01, "p95": 0.02, "n": 8}},
        compiles={"total": 3, "post_warmup": 0},
        **kw,
    )


def test_make_record_is_schema_valid():
    rec = _record()
    assert validate_record(rec) == []
    # json round trip preserves validity (what readers actually see)
    assert validate_record(json.loads(json.dumps(rec))) == []


def test_validate_record_catches_problems():
    assert validate_record("not a dict")
    rec = _record()
    del rec["sps"]
    assert any("sps" in e for e in validate_record(rec))
    rec = _record()
    rec["step"] = "nope"
    assert any("step" in e for e in validate_record(rec))


def test_schema_covers_issue_fields():
    """The acceptance criteria name step/sps/HBM/compile-count records.
    v2 (ISSUE 15): hbm moved to the optional set — backends that report
    no memory stats OMIT the key instead of writing a null."""
    from sheeprl_tpu.obs.telemetry import TELEMETRY_OPTIONAL_FIELDS

    for field in ("step", "sps", "compiles", "timer_percentiles_s", "host_rss_mb"):
        assert field in TELEMETRY_REQUIRED_FIELDS
    assert "hbm" in TELEMETRY_OPTIONAL_FIELDS


def test_sink_append_and_read(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    sink = TelemetrySink(path)
    for i in range(5):
        sink.write(_record(step=i))
    sink.close()
    recs = read_records(path)
    assert [r["step"] for r in recs] == list(range(5))
    assert all(validate_record(r) == [] for r in recs)


def test_sink_reopens_appending(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    s1 = TelemetrySink(path)
    s1.write(_record(step=0))
    s1.close()
    s2 = TelemetrySink(path)
    s2.write(_record(step=1))
    s2.close()
    assert [r["step"] for r in read_records(path)] == [0, 1]


def test_sink_rotation(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    one_line = len(json.dumps(_record(), separators=(",", ":"))) + 1
    sink = TelemetrySink(path, max_bytes=int(one_line * 2.5))  # rotate after 2 records
    for i in range(6):
        sink.write(_record(step=i))
    sink.close()
    assert os.path.exists(path + ".1"), "rotation must keep one backup generation"
    tail = read_records(path)
    backup = read_records(path + ".1")
    # no record lost across the most recent rotation boundary
    assert [r["step"] for r in backup + tail] == list(range(6))[-len(backup) - len(tail):]
    assert os.path.getsize(path) <= one_line * 3


def test_host_rss_probe():
    rss = host_rss_mb()
    assert rss is None or rss > 0


# ----------------------------------------------- ISSUE 13 satellites
def test_records_carry_versioned_schema():
    """Every record is stamped with the versioned schema string, and the
    validator rejects a wrong stamp (readers route on it)."""
    from sheeprl_tpu.obs.telemetry import TELEMETRY_SCHEMA

    rec = _record()
    assert rec["schema"] == TELEMETRY_SCHEMA == "sheeprl.telemetry/2"
    rec["schema"] = "sheeprl.telemetry/999"
    assert any("schema" in e for e in validate_record(rec))


_CHILD_SCRIPT = """
import os, sys
sys.path.insert(0, {repo!r})
from sheeprl_tpu.obs.telemetry import TelemetrySink, make_record
sink = TelemetrySink({path!r}, max_bytes={max_bytes})
for i in range({n}):
    sink.write(make_record(step=i, train_step=i))
sink.flush()  # the preemption/emergency path: fsync BEFORE dying
os._exit(1)   # hard exit with NO close(): only fsynced bytes survive
"""


def test_sink_rotation_and_fsync_survive_hard_exit(tmp_path):
    """Multi-process sink semantics under the decoupled lead (ISSUE 13
    satellite): a child process writes past the rotation bound, runs the
    preemption-forced ``flush()``, then hard-exits without ``close()`` —
    every record must be durable on disk (fsync) across BOTH rotation
    generations, and all must be schema-valid."""
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    path = str(tmp_path / "telemetry.jsonl")
    one_line = len(json.dumps(make_record(step=0, train_step=0), separators=(",", ":"))) + 1
    n = 7
    proc = subprocess.run(
        [_sys.executable, "-c", _CHILD_SCRIPT.format(repo=repo, path=path, max_bytes=one_line * 3, n=n)],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 1, proc.stderr  # the scripted hard exit
    assert os.path.exists(path + ".1"), "rotation must have produced a backup generation"
    backup, tail = read_records(path + ".1"), read_records(path)
    steps = [r["step"] for r in backup + tail]
    # single-generation rotation: the oldest generation is legitimately
    # gone, but what survives must be the CONTIGUOUS newest tail ending
    # at the final record — fsync made the buffered tail durable, and no
    # record was torn or lost inside the surviving window
    assert steps == list(range(n))[-len(steps):], f"non-contiguous survivors: {steps}"
    assert steps[-1] == n - 1, "the fsynced tail record is missing"
    assert all(validate_record(r) == [] for r in backup + tail)


def test_sink_flush_tolerates_closed_file(tmp_path):
    sink = TelemetrySink(str(tmp_path / "t.jsonl"))
    sink.flush()  # never opened: no-op, no raise
    sink.write(_record())
    sink.close()
    sink.flush()  # closed: no-op, no raise


# ----------------------------------------------- ISSUE 15 satellites
def test_device_memory_stats_guards_none_and_junk_values():
    """CPU backends: memory_stats() may return None, {}, raise, or
    report None-valued keys — the probe must yield None (the v2 record
    then OMITS the hbm key) instead of leaking a null downstream."""
    from sheeprl_tpu.obs.telemetry import device_memory_stats

    class Dev:
        def __init__(self, ret=None, raise_=False):
            self._ret, self._raise = ret, raise_

        def memory_stats(self):
            if self._raise:
                raise RuntimeError("unsupported")
            return self._ret

    assert device_memory_stats(Dev(None)) is None
    assert device_memory_stats(Dev({})) is None
    assert device_memory_stats(Dev(raise_=True)) is None
    # a plugin reporting a None VALUE must not produce int(None)
    assert device_memory_stats(Dev({"bytes_in_use": None})) is None
    out = device_memory_stats(Dev({"bytes_in_use": 7, "bytes_limit": None, "junk": 1}))
    assert out == {"bytes_in_use": 7}


def test_record_omits_hbm_when_absent_and_validates():
    rec = _record()
    assert "hbm" not in rec  # no device handed in -> no key, not a null
    assert validate_record(rec) == []
    rec2 = _record(hbm={"bytes_in_use": 5})
    assert rec2["hbm"] == {"bytes_in_use": 5}
    assert validate_record(rec2) == []
    rec2["hbm"] = "junk"
    assert any("hbm" in e for e in validate_record(rec2))


def test_rotation_boundary_with_tailing_reader(tmp_path):
    """ISSUE 15 satellite: a reader tailing the stream while the sink
    rotates mid-write must see NO dropped and NO duplicated records in
    any scan that includes the backup generation."""
    from sheeprl_tpu.obs.reader import iter_jsonl, telemetry_files

    run_dir = tmp_path / "v0"
    os.makedirs(run_dir)
    path = str(run_dir / "telemetry.jsonl")
    one_line = len(json.dumps(_record(), separators=(",", ":"))) + 1
    # rotate every ~4 records; 10 writes => exactly one rotation boundary
    # inside the window both generations still cover
    sink = TelemetrySink(path, max_bytes=int(one_line * 4.5))
    seen_scans = []
    for i in range(10):
        sink.write(_record(step=i))
        # the tailing reader re-scans after EVERY write — including the
        # writes that triggered the rename — through the same
        # backup-aware file discovery the hub/report consumers use
        steps = []
        for f in telemetry_files(str(tmp_path), include_backups=True):
            steps += [r["step"] for r in iter_jsonl(f)]
        seen_scans.append(steps)
    sink.close()
    for scan in seen_scans:
        # each scan is duplicate-free and a CONTIGUOUS tail-window of
        # what had been written (single-generation rotation may age out
        # the oldest records, never tear the middle)
        assert len(scan) == len(set(scan)), f"duplicates across rotation: {scan}"
        assert scan == sorted(scan), f"out-of-order read: {scan}"
        assert scan == list(range(scan[0], scan[-1] + 1)), f"hole in scan: {scan}"
    # the final scan ends at the last write and covers both generations
    assert seen_scans[-1][-1] == 9
    assert len(seen_scans[-1]) > 4

"""Durable segment storage (DESIGN.md §12): record format round-trip,
torn-tail crash recovery, WAL roll/seal/prune, sharding, and restart
byte-identity of /trend, /weekly and /job/{id} via the store backends."""
import dataclasses
import math
import os

import pytest
from hypothesis import given, strategies as st

from repro.core.metrics import ClusterSnapshot, JobRecord, NodeSnapshot
from repro.daemon import protocol
from repro.daemon.store import HistoryStore, JobHistoryStore, TierSpec
from repro.storage import (SegmentLog, SegmentWriter, open_storage,
                           safe_key, scan_segment, unsafe_key)
from repro.storage.segment import FRAME, frame_record, header_bytes


def _snap(ts, load_a=10.0, load_b=40.0, gpu=0.5, cluster="tx"):
    nodes = {
        "a": NodeSnapshot("a", cores_total=48, cores_used=48, load=load_a,
                          mem_total_gb=192.0, mem_used_gb=50.0),
        "b": NodeSnapshot("b", cores_total=48, cores_used=48, load=load_b,
                          mem_total_gb=192.0, mem_used_gb=60.0,
                          gpus_total=2, gpus_used=2, gpu_load=gpu,
                          gpu_mem_total_gb=64.0, gpu_mem_used_gb=8.0),
    }
    jobs = [JobRecord(1, "ua", "ja", ["a"], cores_per_node=48),
            JobRecord(2, "ub", "jb", ["b"], cores_per_node=48,
                      gpus_per_node=2)]
    return ClusterSnapshot(cluster, ts, nodes, jobs)


def _snaps(n, t0=1_700_000_000.0, step=300.0):
    return [_snap(t0 + step * i, load_a=5.0 + (i % 7) * 3.0,
                  load_b=20.0 + (i % 5) * 8.0, gpu=0.1 * (i % 9))
            for i in range(n)]


# ------------------------------------------------------------ record format


@given(records=st.lists(st.tuples(
    st.floats(allow_nan=False, allow_infinity=False, width=64),
    st.binary(min_size=0, max_size=200)), min_size=0, max_size=30))
def test_segment_roundtrip_property(records, tmp_path_factory):
    """Any (timestamp, payload) sequence survives the write → scan round
    trip exactly, in order, with no torn tail."""
    path = str(tmp_path_factory.mktemp("seg") / "seg-00000000.log")
    w = SegmentWriter(path)
    for t, payload in records:
        w.append(t, payload)
    w.close()
    scan = scan_segment(path)
    assert not scan.torn
    assert scan.records == records
    assert scan.valid_bytes == os.path.getsize(path)


@given(payload=st.binary(min_size=1, max_size=64),
       cut=st.integers(min_value=1, max_value=20))
def test_torn_tail_truncation_property(payload, cut, tmp_path_factory):
    """Cutting any number of bytes off the final frame loses only that
    frame: every earlier record scans back intact."""
    path = str(tmp_path_factory.mktemp("seg") / "seg-00000000.log")
    frames = [frame_record(float(i), payload + bytes([i]))
              for i in range(3)]
    with open(path, "wb") as f:
        f.write(header_bytes() + b"".join(frames))
    size = os.path.getsize(path)
    torn_size = size - min(cut, len(frames[-1]) - 1)
    with open(path, "r+b") as f:
        f.truncate(torn_size)
    scan = scan_segment(path)
    assert scan.torn
    assert [p for _, p in scan.records] == \
        [payload + bytes([0]), payload + bytes([1])]
    # a writer reopening the torn segment truncates to the last valid
    # boundary and appends cleanly after it
    w = SegmentWriter(path)
    assert w.torn_dropped == 1
    w.append(9.0, b"after")
    w.close()
    scan2 = scan_segment(path)
    assert not scan2.torn
    assert [p for _, p in scan2.records][-1] == b"after"
    assert len(scan2.records) == 3


def test_corrupt_middle_record_stops_scan(tmp_path):
    path = str(tmp_path / "seg-00000000.log")
    w = SegmentWriter(path)
    for i in range(4):
        w.append(float(i), b"rec%d" % i)
    w.close()
    # flip one payload byte of the second record: CRC catches it and the
    # scan keeps everything before it
    with open(path, "r+b") as f:
        data = bytearray(f.read())
        first_end = len(header_bytes()) + FRAME.size + 4
        data[first_end + FRAME.size] ^= 0xFF
        f.seek(0)
        f.write(data)
    scan = scan_segment(path)
    assert scan.torn
    assert [p for _, p in scan.records] == [b"rec0"]


# ------------------------------------------------------------- segment log


def test_segment_log_rolls_seals_replays(tmp_path):
    log = SegmentLog(str(tmp_path), max_records=4)
    for i in range(10):
        log.append(float(i), b"p%d" % i)
    infos = log.segments()
    assert [s.sealed for s in infos] == [True, True, False]
    assert [s.count for s in infos] == [4, 4, 2]
    assert infos[0].t_min == 0.0 and infos[0].t_max == 3.0
    assert [p for _, p in log.replay()] == [b"p%d" % i for i in range(10)]
    assert [s for s, _, _ in log.replay(with_seq=True)] == \
        [0] * 4 + [1] * 4 + [2] * 2
    log.close()
    # reopen resumes the tail; sealed segments are untouched
    log2 = SegmentLog(str(tmp_path), max_records=4)
    log2.append(10.0, b"p10")
    assert [p for _, p in log2.replay()][-1] == b"p10"
    log2.close()


def test_segment_log_prune_keeps_tail_and_ring(tmp_path):
    log = SegmentLog(str(tmp_path), max_records=4)
    for i in range(20):
        log.append(float(i), b"x")
    # prune everything older than t=100 but keep >= 6 trailing records
    removed = log.prune_before(100.0, keep_records=6)
    assert removed > 0
    assert sum(s.count for s in log.segments()) >= 6
    # the unsealed tail is never deleted even with no keep floor
    log.prune_before(math.inf)
    assert any(not s.sealed for s in log.segments())
    # max_seq fences pruning at the compaction cursor
    log2 = SegmentLog(str(tmp_path / "fence"), max_records=2)
    for i in range(8):
        log2.append(float(i), b"x")
    assert log2.prune_before(math.inf, max_seq=0) == 1
    log2.close()
    log.close()


# ---------------------------------------------------------------- sharding


@given(st.text(min_size=0, max_size=40))
def test_safe_key_roundtrip_property(key):
    safe = safe_key(key)
    assert unsafe_key(safe) == key
    assert "/" not in safe and safe not in ("..", ".")


def test_shard_layout_is_traversal_safe(tmp_path):
    rt = open_storage(str(tmp_path / "data"))
    log = rt.jobs.raw.log_for("../../etc/passwd")
    assert os.path.realpath(log.root).startswith(
        os.path.realpath(str(tmp_path)))
    rt.close()


# ------------------------------------------------- history restart identity


def _history_pair(tmp_path, n=40, segment_records=8):
    data = str(tmp_path / "data")
    rt = open_storage(data, segment_records=segment_records,
                      compact_interval_s=9999.0)
    store = HistoryStore(backend=rt.history)
    for snap in _snaps(n):
        store.append(snap)
    rt.compact_once()
    return data, rt, store


def test_history_restart_is_byte_identical(tmp_path):
    data, rt, store = _history_pair(tmp_path)
    before = {
        tier: protocol.dumps(store.trend_wire(tier))
        for tier in ("raw", "15min", "hourly")}
    weekly_before = store.weekly_report()
    sizes_before = store.sizes()
    rt.close()

    rt2 = open_storage(data, compact_interval_s=9999.0)
    store2 = HistoryStore(backend=rt2.history)
    counts = store2.recover()
    assert counts["checkpoint"] == 1
    for tier, body in before.items():
        assert protocol.dumps(store2.trend_wire(tier)) == body
    assert store2.weekly_report() == weekly_before
    assert store2.sizes() == sizes_before
    # appends continue seamlessly after recovery
    store2.append(_snap(1_700_000_000.0 + 300.0 * 41))
    assert store2.sizes()["appended"] == sizes_before["appended"] + 1
    rt2.close()


def test_history_recovery_tolerates_torn_tail(tmp_path):
    """Truncate the tail raw segment mid-record: recovery keeps every
    record before the tear and /trend tier selection is unchanged."""
    data, rt, store = _history_pair(tmp_path)
    tier_sel = store.select_tier(3600.0)
    n_appended = store.sizes()["appended"]
    rt.close()

    raw_dir = os.path.join(data, "history", "raw")
    tails = sorted(f for f in os.listdir(raw_dir) if f.endswith(".log")
                   and not os.path.exists(os.path.join(raw_dir, f + ".idx")))
    tail = os.path.join(raw_dir, tails[-1])
    with open(tail, "r+b") as f:
        f.truncate(os.path.getsize(tail) - 3)   # mid final record

    rt2 = open_storage(data, compact_interval_s=9999.0)
    store2 = HistoryStore(backend=rt2.history)
    store2.recover()
    # exactly the torn final record is gone; everything before survives
    assert store2.sizes()["appended"] == n_appended - 1
    times = [s.timestamp for s in store2.raw()]
    assert times == [1_700_000_000.0 + 300.0 * i
                     for i in range(len(times))]
    assert store2.select_tier(3600.0) == tier_sel
    # the reopened writer truncated the tear: new appends are clean
    store2.append(_snap(1_700_000_000.0 + 300.0 * 60))
    rt2.close()
    rt3 = open_storage(data, compact_interval_s=9999.0)
    store3 = HistoryStore(backend=rt3.history)
    store3.recover()
    # 40 originals - 1 torn + 1 post-recovery append
    assert store3.sizes()["appended"] == n_appended
    rt3.close()


def test_history_compaction_survives_raw_pruning(tmp_path):
    """Once compacted, tier history no longer depends on raw segments:
    aggressive raw retention cannot lose downsampled points."""
    data = str(tmp_path / "data")
    rt = open_storage(data, segment_records=8, compact_interval_s=9999.0,
                      retain_raw_s=600.0)       # keep only 2 raw steps
    store = HistoryStore(backend=rt.history, raw_capacity=4)
    for snap in _snaps(64):
        store.append(snap)
    rt.compact_once()
    before_15 = protocol.dumps(store.trend_wire("15min"))
    before_h = protocol.dumps(store.trend_wire("hourly"))
    stats = rt.history.stats()
    assert stats["raw"]["pruned_segments"] > 0
    rt.close()

    rt2 = open_storage(data, compact_interval_s=9999.0)
    store2 = HistoryStore(backend=rt2.history, raw_capacity=4)
    store2.recover()
    assert protocol.dumps(store2.trend_wire("15min")) == before_15
    assert protocol.dumps(store2.trend_wire("hourly")) == before_h
    # the ring refilled from the retained raw tail despite pruning
    assert len(store2.raw()) == 4
    rt2.close()


def test_duplicate_timestamps_dropped_entirely(tmp_path):
    """An exact repeat of the previous timestamp (frozen-clock source,
    re-delivered snapshot) is dropped before the ring and the WAL."""
    data = str(tmp_path / "data")
    rt = open_storage(data, compact_interval_s=9999.0)
    store = HistoryStore(backend=rt.history)
    snap = _snap(1_700_000_000.0)
    for _ in range(5):
        store.append(snap)
    sizes = store.sizes()
    assert sizes["appended"] == 1
    assert sizes["duplicate_dropped"] == 4
    assert rt.history.raw_log.stats()["appended"] == 1
    rt.close()


def test_weekly_window_answers_from_disk_after_memory_ages_out(tmp_path):
    """A /weekly window older than the in-memory finest tier is served
    from the user-keyed flag shards compaction wrote."""
    t0 = 1_700_000_000.0
    data = str(tmp_path / "data")
    rt = open_storage(data, segment_records=8, compact_interval_s=9999.0)
    # finest tier retains only 4 buckets in memory; ingest 16 buckets
    tiers = [TierSpec("15min", 900.0, capacity=4)]
    store = HistoryStore(backend=rt.history, tiers=tiers)
    for snap in _snaps(64, t0=t0, step=225.0):  # 4 samples per bucket
        store.append(snap)
    rt.compact_once()

    full = store.weekly_report(start=t0, end=t0 + 225.0 * 64)
    # the same flags replayed through a memory-only store with room for
    # every bucket give the ground truth
    ref = HistoryStore(tiers=[TierSpec("15min", 900.0, capacity=64)])
    for snap in _snaps(64, t0=t0, step=225.0):
        ref.append(snap)
    expected = ref.weekly_report(start=t0, end=t0 + 225.0 * 64)
    assert full == expected
    rt.close()


# ----------------------------------------------------- job shards + reload


def test_jobstore_restart_and_cold_reload(tmp_path):
    data = str(tmp_path / "data")
    rt = open_storage(data, compact_interval_s=9999.0)
    jobs = JobHistoryStore(backend=rt.jobs)
    for snap in _snaps(30):
        jobs.observe(snap)
    before_raw = {jid: jobs.raw_points(jid) for jid in jobs.job_ids()}
    before_life = {jid: jobs.lifetime(jid) for jid in jobs.job_ids()}
    rt.compact_once()
    rt.close()

    rt2 = open_storage(data, compact_interval_s=9999.0)
    jobs2 = JobHistoryStore(backend=rt2.jobs)
    rec = jobs2.recover()
    assert rec["jobs"] == len(before_raw)
    for jid, samples in before_raw.items():
        assert jobs2.raw_points(jid) == samples
        assert jobs2.lifetime(jid) == before_life[jid]
    rt2.close()


def test_jobstore_eviction_reloads_from_disk(tmp_path):
    """max_jobs=2 with 3 jobs: the evicted job's history answers from
    its shard on the next read, and counts as a reload."""
    data = str(tmp_path / "data")
    rt = open_storage(data, compact_interval_s=9999.0)
    jobs = JobHistoryStore(backend=rt.jobs, max_jobs=2)
    t0 = 1_700_000_000.0
    for i in range(6):
        snap = _snap(t0 + 300.0 * i)
        # jobs 1 and 2 come from _snap; add job 3 on node a
        snap.jobs.append(JobRecord(3, "uc", "jc", ["a"],
                                   cores_per_node=48))
        jobs.observe(snap)
    assert jobs.sizes()["evicted"] > 0
    assert len(jobs.job_ids()) == 2
    evicted_id = next(jid for jid in (1, 2, 3)
                      if jid not in jobs.job_ids())
    reloads_before = jobs.sizes()["reloaded"]
    samples = jobs.raw_points(evicted_id)
    assert len(samples) == 6                    # reloaded from its shard
    assert jobs.sizes()["reloaded"] == reloads_before + 1
    assert len(jobs.job_ids()) == 2             # population stays bounded
    rt.close()


def test_jobstore_without_backend_unchanged(tmp_path):
    jobs = JobHistoryStore(max_jobs=2)
    for snap in _snaps(4):
        jobs.observe(snap)
    assert jobs.raw_points(999) == []
    sizes = jobs.sizes()
    assert sizes["reloaded"] == 0 and sizes["jobs"] == 2


# ------------------------------------------------------------ daemon level


def test_daemon_stats_reports_storage_and_jobstore_counters(tmp_path):
    from repro.daemon.server import LLloadDaemon
    from repro.monitor import build_source

    rt = open_storage(str(tmp_path / "data"), compact_interval_s=9999.0)
    daemon = LLloadDaemon(build_source("sim"), ttl_s=3600.0, storage=rt)
    try:
        daemon.backfill(_snaps(10))
        rt.compact_once()
        status, _, body = daemon.handle("/stats")
        assert status == 200
        stats = protocol.loads(body)
        assert stats["storage"]["history"]["raw"]["records"] == 10
        assert stats["storage"]["compactor"]["cycles"] == 1
        assert "segments" in stats["storage"]["history"]["raw"]
        js = stats["jobstore"]
        for key in ("jobs", "raw_samples", "buckets", "evicted",
                    "reloaded"):
            assert key in js
        assert stats["store"]["duplicate_dropped"] == 0
    finally:
        daemon.close()


def test_daemon_without_data_dir_has_no_storage_section():
    from repro.daemon.server import LLloadDaemon
    from repro.monitor import build_source

    daemon = LLloadDaemon(build_source("sim"), ttl_s=3600.0)
    try:
        status, _, body = daemon.handle("/stats")
        assert status == 200
        assert "storage" not in protocol.loads(body)
    finally:
        daemon.close()


def test_backfill_sources_accepts_file_and_directory(tmp_path):
    from repro.core.archive import SnapshotArchive
    from repro.daemon.server import backfill_sources

    archive = SnapshotArchive(str(tmp_path), cluster="tx")
    for snap in _snaps(6):
        archive.append(snap)
    files = archive.files()
    assert files

    # a single TSV file replays exactly its rows
    pairs = backfill_sources(files[0])
    assert len(pairs) == 1 and pairs[0][0] == files[0]
    store = HistoryStore()
    n_file = store.backfill(pairs[0][1])
    assert n_file > 0

    # the archive root (one subdir per cluster) replays everything
    pairs = backfill_sources(str(tmp_path))
    labels = [label for label, _ in pairs]
    assert labels == [os.path.join(str(tmp_path), "tx")]
    store2 = HistoryStore()
    total = sum(store2.backfill(replayable) for _, replayable in pairs)
    assert total == 6

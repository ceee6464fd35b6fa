"""TelemetryBus: cache TTL, ring buffer, deltas, subscribers, sampler,
and the watch loop's cached-read property."""
import io
import random
import threading
import time

import pytest

from repro.cluster.workloads import make_llsc_sim, paper_scenario
from repro.core.archive import ArchiveSubscriber, SnapshotArchive
from repro.core.metrics import ClusterSnapshot, NodeSnapshot
from repro.monitor import TelemetryBus, publish_step_utilization, watch
from repro.core.collector import JaxJobRegistry


def _sim(cluster="txgreen", until=1800.0):
    sim = make_llsc_sim(6, 4, cluster=cluster)
    paper_scenario(sim, random.Random(0))
    sim.run_until(until)
    return sim


class CountingSource:
    """Wraps a source, counting snapshot() calls (the collection cost)."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.interval_hint = None
        self.calls = 0

    def snapshot(self):
        self.calls += 1
        return self.inner.snapshot()


# ----------------------------------------------------------------- caching


def test_cached_reads_within_ttl():
    src = CountingSource(_sim().as_source())
    bus = TelemetryBus(ttl_s=60.0)
    bus.register(src)

    snaps = [bus.read() for _ in range(10)]
    assert src.calls == 1, "nine of ten reads must be served from cache"
    assert all(s is snaps[0] for s in snaps)
    st = bus.stats()
    assert st.reads == 10 and st.cache_hits == 9 and st.collections == 1


def test_ttl_expiry_forces_recollection():
    src = CountingSource(_sim().as_source(advance_s=900.0))
    bus = TelemetryBus(ttl_s=0.0)          # nothing is ever fresh
    bus.register(src)
    t0 = bus.read().timestamp
    t1 = bus.read().timestamp
    assert src.calls == 2
    assert t1 > t0


def test_max_age_overrides_ttl():
    src = CountingSource(_sim().as_source())
    bus = TelemetryBus(ttl_s=1e9)
    bus.register(src)
    bus.read()
    bus.read(max_age_s=0.0)
    assert src.calls == 2


def test_multi_source_read_requires_name():
    bus = TelemetryBus()
    bus.register(_sim("a").as_source())
    bus.register(_sim("b").as_source())
    with pytest.raises(ValueError):
        bus.read()
    assert bus.read("a").cluster == "a"
    assert bus.sources() == ["a", "b"]


def test_duplicate_registration_rejected():
    bus = TelemetryBus()
    bus.register(_sim("a").as_source())
    with pytest.raises(ValueError):
        bus.register(_sim("a").as_source())


def test_concurrent_cold_reads_collect_once():
    """Readers racing on an expired cache must not double-collect (a
    stateful source would skip frames / double-advance sim time)."""
    inner = _sim().as_source(advance_s=60.0)

    class Slow(CountingSource):
        def snapshot(self):
            time.sleep(0.05)
            return super().snapshot()

    src = Slow(inner)
    bus = TelemetryBus(ttl_s=60.0)
    bus.register(src)
    threads = [threading.Thread(target=bus.read) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert src.calls == 1, "racing readers must serialize on one collection"
    st = bus.stats()
    assert st.reads == 8 and st.collections == 1 and st.cache_hits == 7


def test_watch_stats_are_per_run_not_cumulative():
    bus = TelemetryBus(ttl_s=60.0)
    bus.register(_sim().as_source())
    for _ in range(5):                 # pre-watch bus activity
        bus.read()
    ws = watch(bus, lambda s: "", interval_s=0.01, max_frames=2,
               out=io.StringIO(), sleep=lambda s: None)
    assert ws.frames == 2
    assert ws.reads == 2               # not 7
    assert ws.collections <= 1


def test_multi_cluster_hung_child_does_not_stack_threads():
    """Repeated polls while a child is hung must reuse the in-flight
    future instead of spawning a new worker each poll."""
    import time as _time
    from repro.monitor import MultiClusterSource, SimSource

    class Hang:
        name = "hang"
        interval_hint = None
        concurrent_calls = 0
        max_concurrent = 0

        def snapshot(self):
            Hang.concurrent_calls += 1
            Hang.max_concurrent = max(Hang.max_concurrent,
                                      Hang.concurrent_calls)
            try:
                _time.sleep(0.5)
                raise RuntimeError("always failing after hang")
            finally:
                Hang.concurrent_calls -= 1

    multi = MultiClusterSource(
        [SimSource(_sim("ok")), Hang()], timeout_s=0.05)
    for _ in range(4):                 # polls arrive faster than the hang
        snap = multi.snapshot()
        assert "ok" in snap.cluster or snap.cluster == "ok"
    assert Hang.max_concurrent == 1
    assert isinstance(multi.last_error("hang"), TimeoutError)


def test_watch_restores_bus_ttl():
    bus = TelemetryBus(ttl_s=0.5)
    bus.register(_sim().as_source())
    watch(bus, lambda s: "", interval_s=5.0, max_frames=1,
          out=io.StringIO(), sleep=lambda s: None)
    assert bus.ttl_s == 0.5


# ------------------------------------------------------- ring buffer/deltas


def test_ring_buffer_and_load_trend():
    bus = TelemetryBus(ttl_s=0.0, history=4)
    bus.register(_sim().as_source(advance_s=900.0))
    for _ in range(6):
        bus.poll()
    ring = bus.history_of()
    assert len(ring) == 4                       # bounded
    assert ring[-1].timestamp - ring[0].timestamp == 3 * 900.0
    # trend is finite and computed over the ring window
    trend = bus.load_trend()
    assert isinstance(trend, float)


def test_gpu_duty_ewma_tracks_users():
    bus = TelemetryBus(ttl_s=0.0, ewma_alpha=0.5)
    bus.register(_sim().as_source(advance_s=900.0))
    bus.poll()
    ewma1 = bus.gpu_duty_ewma()
    assert ewma1, "scenario has GPU users"
    assert all(0.0 <= v <= 1.5 for v in ewma1.values())
    bus.poll()
    ewma2 = bus.gpu_duty_ewma()
    assert set(ewma2) >= set(ewma1)


# ------------------------------------------------------------- subscribers


def test_subscribers_see_every_collection():
    bus = TelemetryBus(ttl_s=0.0)
    bus.register(_sim().as_source())
    got = []
    bus.subscribe(lambda name, snap: got.append((name, snap.timestamp)))
    bus.poll()
    bus.poll()
    assert len(got) == 2
    assert got[0][0] == "txgreen"
    bus.unsubscribe(bus._subscribers[0])


def test_archive_subscriber_respects_cadence(tmp_path):
    bus = TelemetryBus(ttl_s=0.0)
    bus.register(_sim().as_source(advance_s=300.0))   # 5 sim-min per poll
    archive = SnapshotArchive(str(tmp_path), cluster="txgreen")
    sub = ArchiveSubscriber(archive, interval_s=900.0)
    bus.subscribe(sub)
    for _ in range(7):                                # 30 sim-minutes
        bus.poll()
    rows = archive.rows()
    stamps = sorted({r["timestamp"] for r in rows})
    assert len(stamps) == 3                           # t0, +15min, +30min
    assert stamps[1] - stamps[0] >= 900.0


# ------------------------------------------------------------ sampler/watch


def test_background_sampler_collects_without_readers():
    src = CountingSource(_sim().as_source(advance_s=60.0))
    src.interval_hint = 0.02
    bus = TelemetryBus(ttl_s=10.0)
    bus.register(src)
    bus.start()
    try:
        deadline = time.monotonic() + 5.0
        while src.calls < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        bus.stop()
    assert src.calls >= 3


def test_watch_serves_cached_reads_between_polls():
    """Acceptance: >= 3 refreshed frames; the underlying source is
    snapshotted fewer times than the bus is read."""
    src = CountingSource(_sim().as_source(advance_s=60.0))
    bus = TelemetryBus(ttl_s=10.0)
    bus.register(src)
    out = io.StringIO()
    ws = watch(bus, lambda s: f"cluster={s.cluster}", interval_s=0.01,
               max_frames=5, out=out)
    assert ws.frames >= 3
    assert ws.reads >= 5
    assert src.calls < ws.reads, (src.calls, ws.reads)
    text = out.getvalue()
    assert text.count("LLload watch | frame") == ws.frames
    assert "cluster=txgreen" in text


def test_watch_cli_end_to_end(capsys):
    from repro.core import cli

    rc = cli.main(["--watch", "--interval", "0.05", "--frames", "3",
                   "--source", "sim", "-t", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    frames = [l for l in out.splitlines() if "LLload watch | frame" in l]
    assert len(frames) == 3
    summary = [l for l in out.splitlines() if l.startswith("watch:")][0]
    # "watch: F frames, R reads, C collections" — cached reads between polls
    parts = summary.replace(",", "").split()
    n_reads, n_collections = int(parts[3]), int(parts[5])
    assert n_collections < n_reads


# ---------------------------------------------------------------- publish


def test_publish_hook_feeds_registry():
    reg = JaxJobRegistry()
    publish_step_utilization("job-a", model_flops_per_step=1e9,
                             step_time_s=0.01, peak_flops=1e12,
                             n_devices=2, registry=reg)
    agg = reg.aggregate()
    assert agg.n_devices == 2
    assert agg.duty_cycle == pytest.approx(1e9 / 0.01 / (1e12 * 2))


def test_publish_hook_returns_what_it_published():
    reg = JaxJobRegistry()
    util = publish_step_utilization("job-b", model_flops_per_step=2e9,
                                    step_time_s=0.5, peak_flops=1e10,
                                    registry=reg)
    assert reg.entries()["job-b"] is util
    assert util.duty_cycle == pytest.approx(0.4)

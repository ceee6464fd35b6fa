"""TelemetryBus — cached, incremental, streaming snapshot distribution.

The bus sits between :class:`~repro.monitor.source.MetricSource`s and
every consumer (CLI/watch, archiver, analysis) — DESIGN.md §5:

  * **cached reads** — ``read(name)`` serves the last snapshot while it is
    younger than ``ttl_s``; N readers cost one collection (the paper's
    "don't hammer the scheduler" rule, generalized).
  * **ring buffer** — the last ``history`` snapshots per source, for
    trend queries and late subscribers.
  * **incremental deltas** — per-source normalized-load trend and a
    per-user GPU duty-cycle EWMA, updated once per collection instead of
    recomputed from scratch by each consumer.
  * **background sampler** — ``start()`` polls each source at its
    ``interval_hint`` (or the bus default) on a daemon thread, so watch
    mode and subscribers stream without any consumer driving collection.
  * **subscribers** — callables invoked as ``fn(source_name, snapshot)``
    on every *new* collection (the 15-minute archiver, the daemon's
    HistoryStore, and the insight engine's streaming evaluator —
    DESIGN.md §8 — are all subscribers).

Job-side publishing (``publish_step_utilization``) also lives here: the
trainer/server call this monitor-layer hook, which feeds the in-process
:class:`~repro.core.collector.JaxJobRegistry`; the ``live``/``jobs``
sources read the registry, so published steps reach any bus those
sources are registered on at its next collection.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional

from repro.core.metrics import ClusterSnapshot

Subscriber = Callable[[str, ClusterSnapshot], None]


@dataclasses.dataclass
class SourceStats:
    """Per-source bus counters (reads vs. actual collections)."""
    reads: int = 0
    cache_hits: int = 0
    collections: int = 0
    errors: int = 0


@dataclasses.dataclass
class _Entry:
    source: object
    ring: Deque[ClusterSnapshot]
    stats: SourceStats
    collected_at: Optional[float] = None   # monotonic
    duty_ewma: Dict[str, float] = dataclasses.field(default_factory=dict)
    # serializes collection per source: without it, a reader at TTL expiry
    # and the sampler would both call snapshot(), double-advancing stateful
    # sources (archive replay frames, sim time)
    collect_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock)


class TelemetryBus:
    def __init__(self, *, ttl_s: float = 5.0, history: int = 64,
                 ewma_alpha: float = 0.3):
        self.ttl_s = ttl_s
        self.history = history
        self.ewma_alpha = ewma_alpha
        self._entries: Dict[str, _Entry] = {}        # guarded-by: _lock
        self._subscribers: List[Subscriber] = []     # guarded-by: _lock
        self._lock = threading.RLock()
        # llcheck: ignore[LL001] lifecycle field: start()/stop() are only called from the owning thread
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # --------------------------------------------------------------- wiring
    def register(self, source):
        """Register a source; returns it for chaining."""
        with self._lock:
            if source.name in self._entries:
                raise ValueError(f"source {source.name!r} already registered")
            self._entries[source.name] = _Entry(
                source=source,
                ring=collections.deque(maxlen=self.history),
                stats=SourceStats())
        return source

    def sources(self) -> List[str]:
        with self._lock:
            return sorted(self._entries)

    def subscribe(self, fn: Subscriber) -> Subscriber:
        with self._lock:
            self._subscribers.append(fn)
        return fn

    def unsubscribe(self, fn: Subscriber):
        with self._lock:
            if fn in self._subscribers:
                self._subscribers.remove(fn)

    def _entry(self, name: Optional[str]) -> _Entry:
        with self._lock:
            if name is None:
                if len(self._entries) != 1:
                    raise ValueError(
                        "bus has %d sources; pass name= (one of %s)"
                        % (len(self._entries), self.sources()))
                return next(iter(self._entries.values()))
            return self._entries[name]

    # --------------------------------------------------------------- reads
    def read(self, name: Optional[str] = None, *,
             max_age_s: Optional[float] = None) -> ClusterSnapshot:
        """Cached read: re-collect only when the cached snapshot is older
        than ``max_age_s`` (default: the bus TTL)."""
        ttl = self.ttl_s if max_age_s is None else max_age_s
        entry = self._entry(name)
        with self._lock:
            entry.stats.reads += 1
            if self._fresh(entry, ttl):
                entry.stats.cache_hits += 1
                return entry.ring[-1]
        return self._collect(entry, skip_if_fresh_within=ttl,
                             count_hit=True)

    def poll(self, name: Optional[str] = None) -> ClusterSnapshot:
        """Force a collection now."""
        return self._collect(self._entry(name))

    def history_of(self, name: Optional[str] = None) -> List[ClusterSnapshot]:
        with self._lock:
            return list(self._entry(name).ring)

    def stats(self, name: Optional[str] = None) -> SourceStats:
        with self._lock:
            return dataclasses.replace(self._entry(name).stats)

    # -------------------------------------------------------------- deltas
    def load_trend(self, name: Optional[str] = None) -> float:
        """d(mean normalized load)/dt over the ring buffer (1/s).  Positive
        means the cluster is heating up; 0 with <2 snapshots."""
        with self._lock:
            ring = list(self._entry(name).ring)
        if len(ring) < 2:
            return 0.0
        first, last = ring[0], ring[-1]
        dt = last.timestamp - first.timestamp
        if dt <= 0:
            return 0.0

        def mean_norm(snap: ClusterSnapshot) -> float:
            if not snap.nodes:
                return 0.0
            return sum(n.norm_load for n in snap.nodes.values()) \
                / len(snap.nodes)

        return (mean_norm(last) - mean_norm(first)) / dt

    def gpu_duty_ewma(self, name: Optional[str] = None) -> Dict[str, float]:
        """Per-user EWMA of mean GPU duty cycle across their GPU nodes,
        updated incrementally at each collection."""
        with self._lock:
            return dict(self._entry(name).duty_ewma)

    # ------------------------------------------------------------- collect
    def _fresh(self, entry: _Entry, ttl: float) -> bool:
        return bool(entry.collected_at is not None and entry.ring
                    and time.monotonic() - entry.collected_at < ttl)

    def _collect(self, entry: _Entry,
                 skip_if_fresh_within: Optional[float] = None,
                 count_hit: bool = False) -> ClusterSnapshot:
        with entry.collect_lock:
            if skip_if_fresh_within is not None:
                # another thread may have collected while we waited
                with self._lock:
                    if self._fresh(entry, skip_if_fresh_within):
                        if count_hit:
                            entry.stats.cache_hits += 1
                        return entry.ring[-1]
            try:
                snap = entry.source.snapshot()
            except Exception:
                with self._lock:
                    entry.stats.errors += 1
                raise
            with self._lock:
                entry.ring.append(snap)
                entry.collected_at = time.monotonic()
                entry.stats.collections += 1
                self._update_ewma(entry, snap)
                subscribers = list(self._subscribers)
        for fn in subscribers:   # outside the locks: subscribers may be slow
            fn(entry.source.name, snap)
        return snap

    def _update_ewma(self, entry: _Entry, snap: ClusterSnapshot):
        a = self.ewma_alpha
        for user, hosts in snap.nodes_by_user().items():
            gpu_nodes = [snap.nodes[h] for h in hosts
                         if h in snap.nodes and snap.nodes[h].gpus_total > 0]
            if not gpu_nodes:
                continue
            duty = sum(n.gpu_load for n in gpu_nodes) / len(gpu_nodes)
            prev = entry.duty_ewma.get(user)
            entry.duty_ewma[user] = (duty if prev is None
                                     else a * duty + (1 - a) * prev)

    # ------------------------------------------------------------- sampler
    def start(self, interval_s: Optional[float] = None):
        """Start the background sampler.  Each source is polled at its
        ``interval_hint`` when set, else ``interval_s`` (default: TTL)."""
        if self._thread is not None and self._thread.is_alive():
            return
        default = interval_s if interval_s is not None else self.ttl_s
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                now = time.monotonic()
                with self._lock:
                    entries = list(self._entries.values())
                next_due = default
                for entry in entries:
                    hint = getattr(entry.source, "interval_hint", None)
                    period = hint if hint is not None else default
                    age = (now - entry.collected_at
                           if entry.collected_at is not None else None)
                    if age is None or age >= period:
                        try:
                            self._collect(entry, skip_if_fresh_within=period)
                        except Exception:
                            pass      # counted in stats.errors; keep sampling
                        age = 0.0
                    next_due = min(next_due, max(period - age, 0.0))
                self._stop.wait(max(next_due, 0.01))

        self._thread = threading.Thread(target=loop, name="telemetry-bus",
                                        daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


# --------------------------------------------------------------------------
# Job-side publish hook (trainer / server -> registry -> live/jobs sources)
# --------------------------------------------------------------------------

# The profiler spans that ``Trainer.run`` and ``ServeEngine.run`` open at
# their layer boundaries (``jax.profiler.TraceAnnotation``; the two step
# spans are ``StepTraceAnnotation``s).  They cost about a microsecond each
# and record nothing unless a profiler session runs, so they are always on:
# a job run under ``jax.profiler.trace(dir)`` gets them in its trace.
SPAN_NAMES = (
    "llload.train.init", "llload.train.step", "llload.train.feed",
    "llload.train.dispatch", "llload.train.sync", "llload.train.checkpoint",
    "llload.serve.init", "llload.serve.step", "llload.serve.admit",
    "llload.serve.prefill", "llload.serve.first_token", "llload.serve.splice",
    "llload.serve.decode", "llload.serve.sample", "llload.serve.bookkeep",
    "llload.monitor.publish",
)


def publish_step_utilization(job_name: str, *, model_flops_per_step: float,
                             step_time_s: float, peak_flops: float,
                             n_devices: int = 1, hbm_used_gb: float = 0.0,
                             hbm_total_gb: float = 0.0, registry=None):
    """Hook called by the trainer/server after each (timed) step.

    Publishes the step's achieved utilization into the in-process job
    registry (which the ``live`` and ``jobs`` sources read), so jobs
    self-report instead of being probed via privileged ssh+nvidia-smi —
    the paper's latency complaint, solved at the source.  Returns the
    :class:`~repro.core.collector.DeviceUtilization` it published.
    """
    from repro.core.collector import DeviceUtilization, JaxJobRegistry

    duty = 0.0
    if step_time_s > 0 and peak_flops > 0:
        duty = model_flops_per_step / step_time_s / (peak_flops * n_devices)
    reg = registry or JaxJobRegistry.global_registry()
    util = DeviceUtilization(
        n_devices=n_devices, n_active=n_devices, duty_cycle=duty,
        hbm_total_gb=hbm_total_gb, hbm_used_gb=hbm_used_gb,
        step_time_s=step_time_s,
        achieved_flops=model_flops_per_step / max(step_time_s, 1e-9))
    reg.publish(job_name, util)
    return util

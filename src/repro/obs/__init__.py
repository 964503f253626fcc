"""Unified telemetry plane: metrics, span tracing, and exporters.

The store's runtime signals — previously scattered over half a dozen
ad-hoc stat dicts (``StoreStats``, ``PipelineStats``, the assembler /
device-cache module counters, WAL and clock integers) — are unified on
three layers:

1. :mod:`repro.obs.metrics` — thread-safe **counters**, **gauges**
   (direct or callback-backed), and **log2-bucketed latency histograms**
   (p50/p99/max) in a :class:`~repro.obs.metrics.MetricsRegistry`.  The
   legacy stat surfaces are kept as backward-compatible *views* over
   registry counters: ``store.stats["commits"]``,
   ``view_assembler.stats.splices``, ``device_cache.stats.uploads`` and
   ``WritePipeline.stats.writes`` all still read exactly as before, but
   every increment now goes through one locked counter — no racy
   read-modify-write remains.
2. :mod:`repro.obs.trace` — a fixed-size, lock-striped **span ring
   buffer**.  Spans cover the commit lifecycle (enqueue → route →
   prepare → wal_sync → link → publish → commit → reclaim), the read
   lifecycle (read → assemble → view_splice → tier_repad), the
   compactor (``compactor_fold``, and ``quiesce``: the whole time
   submitters are held off, its ``drain_s`` arg the flush), and every
   backend compile (``compile``: its ``site`` arg is the innermost
   :meth:`~repro.obs.trace.Tracer.site` region open on the compiling
   thread — ``view_splice``, ``tier_repad``, ``leaf_gather``,
   ``leaf_search`` or ``pagerank`` — and ``sites`` the whole open
   stack).  ``view_splice`` is one span per view's device assembly,
   with its ``path`` (``patch`` / ``concat`` / ``full``), ``n_dirty``,
   ``n_subgraphs``, ``n_patches`` and ``bytes_written`` (from shapes).
   Write spans carry the commit/view timestamp in their args — one
   write is traceable from submission to the first reader view that
   observes it.  Spans time the host: around an asynchronous device
   call they time its dispatch, and the device trace gives device time.
   :mod:`repro.obs.profile` moves the ring onto a ``jax.profiler``
   trace's clock through the ``repro.clock`` anchors each traced read
   writes, and :meth:`~repro.obs.trace.Tracer.clear` stamps
   ``epoch_ns``, the clock's zero for a measured window.
3. :mod:`repro.obs.export` — Prometheus text exposition
   (:func:`~repro.obs.export.prometheus_text`), Chrome trace-event JSON
   loadable in Perfetto (:func:`~repro.obs.export.chrome_trace` /
   ``write_chrome_trace``), and the human-readable
   ``RapidStore.telemetry_report()``.

Metric naming scheme
--------------------
``<subsystem>_<what>[_<unit>]`` with the subsystem one of ``store``,
``pipeline``, ``wal``, ``reader``, ``assembler``, ``device_cache``,
``compactor`` — e.g. ``store_commits``, ``pipeline_queue_depth`` (with a
``shard`` label), ``wal_backlog_bytes``, ``device_cache_hit_ratio``,
``store_memory_bytes`` (with a ``component`` label), and the latency
histograms ``read_latency_seconds`` / ``commit_visibility_seconds`` /
``wal_sync_seconds``.  Exporters prepend the ``rapidstore_`` namespace
(and a ``_total`` suffix for counters) so the exposition follows
Prometheus conventions while in-process names stay short.  Store-scoped
metrics live on the per-store ``store.registry``; process-wide surfaces
(the device cache, the view assembler, reader-slot exhaustion) live on
the module-global :data:`repro.obs.metrics.REGISTRY`.

Overhead contract
-----------------
Counters that back the legacy stat surfaces are **always live** — they
cost what the old locked dicts cost (one uncontended lock per
increment) and tests rely on them unconditionally.  Everything *added*
by this plane — spans, compile records, clock anchors and
latency-histogram observation — is **off by default** and gated behind
``REPRO_TELEMETRY=1`` (or :func:`repro.obs.trace.enable`).  Off, an
instrumentation site costs one attribute check (``TRACER.enabled``): no
clock read, no ``args`` dict built, no profiler annotation, a shared
no-op ``site`` context, and a compile listener that returns at once —
``tests/test_trace_sites.py::test_tracing_off_costs_nothing`` asserts
it on a write-pipeline, compactor and search run.  On, a span costs two
``perf_counter_ns`` calls, one tuple build and one striped ring slot
write; ``benchmarks/bench_concurrent.py`` (a benchmark, not a tier-1
test) holds reader p99 with telemetry on within 1.1x of off when run.
No span ever waits on or reads from the device.  The span ring is
fixed-size (``REPRO_TELEMETRY_RING``, default 32768 spans): saturation
overwrites the oldest spans per stripe and never blocks or allocates
unboundedly.
"""

from .metrics import REGISTRY, Counter, Gauge, Histogram, MetricsRegistry
from .trace import TRACER, SpanRing, Tracer, enable, enabled
from .export import chrome_trace, prometheus_text, telemetry_report, write_chrome_trace
from .profile import busiest_span, clock_offsets, host_events, on_profiler_clock

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TRACER",
    "SpanRing",
    "Tracer",
    "enable",
    "enabled",
    "chrome_trace",
    "prometheus_text",
    "telemetry_report",
    "write_chrome_trace",
    "busiest_span",
    "clock_offsets",
    "host_events",
    "on_profiler_clock",
]

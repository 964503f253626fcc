"""The tracer's instrumentation sites: free when off (no clock read, no
profiler annotation, no site pushed, no listener record), the compile
listener's sites, the view_splice and quiesce spans, and the clock anchors
that put the ring on a profile's clock."""

import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RapidStore
from repro.core.analytics import pagerank_view
from repro.kernels.leaf_search import edge_search_view
from repro.obs import profile
from repro.obs import trace as trace_mod
from repro.obs.trace import TRACER, SpanRing


@pytest.fixture
def tracer():
    """The process tracer with a fresh ring, restored afterwards."""
    saved = TRACER.enabled, TRACER.ring
    TRACER.ring = SpanRing(1 << 14)
    TRACER.clear()
    try:
        yield TRACER
    finally:
        TRACER.enabled, TRACER.ring = saved
        TRACER.clear()


EMPTY = np.empty((0, 2), np.int64)
_COMPILES = [0]  # backend compiles seen by this module's own listener


def _count_compiles(event, seconds, **_):
    if event == trace_mod.COMPILE_EVENT:
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


def _graph(n=256, m=1200, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, (m, 2), dtype=np.int64)
    return e[e[:, 0] != e[:, 1]]


def _rehearse(store, rng, n):
    """Writes through the pipeline, a compactor fold, searches and a
    PageRank on pinned views."""
    for _ in range(6):
        ins = rng.integers(0, n, (8, 2), dtype=np.int64)
        store.apply_async(ins[ins[:, 0] != ins[:, 1]], EMPTY)
    store.flush()
    store.compactor.compact_once()
    for _ in range(2):
        h = store.begin_read()
        try:
            us = rng.integers(0, n, 32)
            vs = rng.integers(0, n, 32)
            edge_search_view(h.view, us, vs)
            jax.block_until_ready(pagerank_view(h.view, iters=2))
        finally:
            store.end_read(h)
        store.apply_async(np.array([[1, 2]], np.int64), EMPTY).wait()


def test_tracing_off_costs_nothing(tracer, tmp_path, monkeypatch):
    tracer.enabled = False
    calls = {"clock": 0, "annotation": 0, "site": 0}

    def clock():
        calls["clock"] += 1
        return time.perf_counter_ns()

    def annotation(*a, **k):
        calls["annotation"] += 1
        return jax.profiler.TraceAnnotation(*a, **k)

    class Site(trace_mod._Site):
        def __enter__(self):
            calls["site"] += 1
            return super().__enter__()

    monkeypatch.setattr(trace_mod, "time", types.SimpleNamespace(perf_counter_ns=clock))
    monkeypatch.setattr(trace_mod, "TraceAnnotation", annotation)
    monkeypatch.setattr(trace_mod, "_Site", Site)

    compiles_before = _COMPILES[0]
    n = 200
    store = RapidStore.from_edges(n, _graph(n, 900, 1), partition_size=16, B=32,
                                  high_threshold=8)
    store.attach_write_pipeline()
    store.attach_wal(tmp_path / "w.wal")
    store.attach_compactor()
    try:
        jax.clear_caches()  # so the run compiles, and the listener is called
        _rehearse(store, np.random.default_rng(2), n)
    finally:
        store.detach_compactor()
        store.detach_write_pipeline()
        store.detach_wal()
    assert _COMPILES[0] > compiles_before
    assert (calls["clock"], calls["annotation"], calls["site"]) == (0, 0, 0), calls
    assert tracer.ring.recorded() == 0 and tracer.counts() == {}


def test_off_site_is_one_shared_context(tracer):
    tracer.enabled = False
    assert tracer.site("a") is tracer.site("b") is trace_mod._NO_SITE
    with tracer.site("a"):
        pass
    assert getattr(tracer._local, "sites", None) in (None, [])


def test_compile_span_takes_innermost_site_of_its_thread(tracer):
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    x37, x41, x43, x47 = (jnp.ones(k) for k in (37, 41, 43, 47))
    tracer.enabled = True
    with tracer.site("outer"):
        with tracer.site("inner"):
            f(x37).block_until_ready()
        f(x41).block_until_ready()
    other = threading.Thread(target=lambda: f(x43).block_until_ready())
    with tracer.site("held by the main thread"):
        other.start()
        other.join()
    f(x47).block_until_ready()
    compiles = [s.args for s in tracer.spans() if s.name == "compile"]
    assert [a["site"] for a in compiles[:4]] == ["inner", "outer", None, None]
    assert [a["sites"] for a in compiles[:4]] == [("outer", "inner"), ("outer",), (), ()]
    assert all(s.dur_ns > 0 for s in tracer.spans() if s.name == "compile")


def test_view_splice_span_and_its_compiles(tracer):
    """A splice of a few dirty subgraphs: one view_splice span inside its
    read span, and the splice's own compiles charged to view_splice."""
    n = 512
    store = RapidStore.from_edges(n, _graph(n, 3000, 4), partition_size=16, B=32,
                                  high_threshold=64)
    tracer.enabled = True
    with store.read_view() as v:
        v.to_leaf_blocks_device()
        v.to_coo_device()
    store.insert_edge(3, 7)
    store.insert_edge(40, 9)
    jax.clear_caches()
    with store.read_view() as v:
        v.to_leaf_blocks_device()
        v.to_coo_device()
        ts = v.ts
    spans = tracer.spans()
    splices = [s for s in spans if s.name == "view_splice"]
    assert [s.args["path"] for s in splices[:2]] == ["full", "full"]
    assert len(splices) == 4
    assert {s.args["path"] for s in splices[2:]} <= {"patch", "concat"}
    for s in splices[2:]:
        assert s.ts == ts and s.args["n_dirty"] == 2
        assert s.args["n_subgraphs"] == n // 16
        assert s.args["n_patches"] > 0 and s.args["bytes_written"] > 0
    reads = [s for s in spans if s.name == "read"]
    for s in splices:
        assert any(r.ts == s.ts and r.tid == s.tid and r.start_ns <= s.start_ns
                   and s.start_ns + s.dur_ns <= r.start_ns + r.dur_ns for r in reads)
    last = splices[2]
    inside = [c for c in spans if c.name == "compile" and c.start_ns >= last.start_ns
              and c.start_ns + c.dur_ns <= splices[3].start_ns + splices[3].dur_ns]
    assert "view_splice" in {c.args["site"] for c in inside}


def test_patch_path_counts_whole_column_writes(tracer):
    """Equal-sized dirty segments are patched: each eager update writes a
    whole new column, and bytes_written says so."""
    n = 256
    store = RapidStore.from_edges(n, _graph(n, 1500, 5), partition_size=16, B=32,
                                  high_threshold=64)
    tracer.enabled = True
    with store.read_view() as v:
        src, dst = v.to_coo()
        v.to_coo_device()
    have = set(dst[src == 5].tolist())
    new = next(w for w in range(n) if w != 5 and w not in have)
    # one commit: an insert and a delete in one subgraph, so its length holds
    store.apply(np.array([[5, new]], np.int64), np.array([[5, min(have)]], np.int64))
    with store.read_view() as v:
        src, dst = v.to_coo_device()
        col_bytes = int(src.nbytes) + int(dst.nbytes)
    s = [s for s in tracer.spans() if s.name == "view_splice"][-1]
    assert s.args["path"] == "patch"
    assert s.args["n_patches"] == 2  # one dirty subgraph, two columns
    assert s.args["bytes_written"] == col_bytes


def test_quiesce_span_splits_drain_from_fold(tracer, tmp_path):
    n = 128
    store = RapidStore.from_edges(n, _graph(n, 500, 6), partition_size=16, B=32)
    store.attach_write_pipeline()
    store.attach_compactor()
    tracer.enabled = True
    try:
        for i in range(20):
            store.apply_async(np.array([[i, (i + 1) % n]], np.int64), EMPTY)
        store.compactor.compact_once()
    finally:
        store.detach_compactor()
        store.detach_write_pipeline()
    q = [s for s in tracer.spans() if s.name == "quiesce"]
    fold = [s for s in tracer.spans() if s.name == "compactor_fold"]
    assert len(q) == len(fold) == 1
    assert 0 <= q[0].args["drain_s"] * 1e9 <= q[0].dur_ns <= fold[0].dur_ns
    assert q[0].start_ns == fold[0].start_ns


def test_clock_anchors_put_the_ring_on_the_profile_clock(tracer, tmp_path):
    store = RapidStore.from_edges(64, _graph(64, 200, 7), partition_size=16, B=32)
    tracer.enabled = True
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            with jax.profiler.TraceAnnotation("test.read"):
                with store.read_view():
                    time.sleep(0.003)
            time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = profile.host_events(ProfileData.from_file(str(path)).planes)
    offsets = profile.clock_offsets(host, tracer.spans())
    assert len(offsets) == 4
    assert max(offsets) - min(offsets) < 50e-6
    moved = profile.on_profiler_clock(host, tracer.spans())
    reads = sorted(s for s in moved if s[0] == "read")
    outer = sorted((t0, t1) for name, t0, t1, _ in host if name == "test.read")
    assert len(reads) == len(outer) == 4
    for (_, r0, r1, *_), (a, b) in zip(reads, outer):
        assert a - 50e-6 <= r0 <= r1 <= b + 50e-6


def test_on_profiler_clock_needs_an_anchor(tracer):
    with pytest.raises(ValueError):
        profile.on_profiler_clock([("x", 0.0, 1.0, {})], [])


def test_busiest_span_counts_self_time():
    # one thread: a read holding a splice; another: a long enqueue
    spans = [("read", 0.0, 10.0, 1, 5, None), ("view_splice", 1.0, 7.0, 1, 5, None),
             ("enqueue", 0.0, 5.0, 2, -1, None)]
    assert profile.busiest_span((0.0, 10.0), spans) == "view_splice"
    assert profile.busiest_span((0.0, 1.0), spans) == "enqueue"  # tie: shorter
    assert profile.busiest_span((7.0, 10.0), spans) == "read"
    assert profile.busiest_span((11.0, 12.0), spans) is None

"""One run of one cell: set-up, the measured window, and the checks.

The cell's configuration file says what graph to make and how the store is
deployed; its traffic file says who reads and who writes, and at what rate.
Nothing here is specific to one cell: the three mixes differ only in their
data.  Times in a :class:`Run` are seconds from the window's start.
"""

from __future__ import annotations

import shutil
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from . import gen, reference
from .device import CompileCounter, memory_peak, persistent_cache_off

# how long requests due in the window may still be waited for once it closes
GRACE_S = 60.0


@dataclass
class Run:
    """What a run measured; the metric readers read this."""

    cell: str
    config: dict
    traffic: dict
    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0
    # searches: one entry per request due in the window
    due: np.ndarray = None
    answered: np.ndarray = None  # seconds, nan when never answered
    req_ts: np.ndarray = None  # the view timestamp that answered
    found: np.ndarray = None
    # one per pinned view of a reader: start, view ready, done, extra
    cycles: list = field(default_factory=list)
    # writes: submit, ack (nan: not acknowledged), stamp, ops
    w_submit: np.ndarray = None
    w_ack: np.ndarray = None
    w_stamp: np.ndarray = None
    ops_per_write: int = 0
    compiles: int = 0
    compile_s: float = 0.0
    memory_peak: int | None = None
    live_edges: int = 0
    wal_syncs: int = 0
    wal_spans: list = field(default_factory=list)  # (start, dur) seconds
    results: list = field(default_factory=list)  # (view ts, ranks)
    wal_span_count: int = 0
    stream_exhausted: bool = False
    trace: dict | None = None  # the reduced device trace
    B: int = 0
    lateness: dict = field(default_factory=dict)


class Clock:
    def __init__(self):
        self.t0 = time.perf_counter()

    def __call__(self) -> float:
        return time.perf_counter() - self.t0


def annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def _ready(x):
    import jax

    return jax.block_until_ready(x)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------
class Deployment:
    """The generated graph, its write stream and requests, and the store
    attached as the configuration says.  The write pipeline is always
    attached: the writers submit through ``apply_async``."""

    def __init__(self, config, traffic, seed, seconds, work_dir: Path):
        from repro.core import RapidStore

        self.phases = {}
        tick = time.perf_counter()

        def phase(name):
            nonlocal tick
            now = time.perf_counter()
            self.phases[name] = now - tick
            tick = now

        self.phase = phase
        g = config["graph"]
        self.n = 1 << int(g["scale"])
        self.base = gen.base_edges(g, seed)
        phase("generate")
        writes = traffic["writes"]
        self.ins_per, self.del_per = int(writes["inserts"]), int(writes["deletes"])
        if writes["loop"] == "open":
            horizon = seconds + float(writes.get("overrun_s", 0.0))
            n_window = int(np.ceil(horizon / float(writes["interval_s"])))
        else:
            n_window = int(np.ceil(float(writes["max_ops_per_s"]) * seconds
                                   / (self.ins_per + self.del_per)))
        self.stream = gen.WriteStream(g, seed, self.base, n_window, self.ins_per, self.del_per)
        reads = traffic["reads"]
        self.req_due = self.req_keys = None
        if reads["op"] == "search":
            if writes["loop"] == "open":
                step = float(writes["interval_s"])
            else:
                step = (self.ins_per + self.del_per) / float(writes["expect_ops_per_s"])
            self.req_due, self.req_keys = gen.search_requests(
                seed, self.n, self.base, self.stream, np.arange(n_window) * step, reads,
                seconds)
        phase("stream_and_requests")
        s = config["store"]
        self.store = RapidStore.from_edges(
            self.n, gen.pairs(self.base), partition_size=int(s["partition_size"]),
            B=int(s["leaf_width"]), high_threshold=int(s["high_degree_threshold"]),
            tracer_k=int(s["tracer_k"]))
        phase("bulk_load")
        a = config["attach"]
        self.store.attach_write_pipeline()
        wal_dir = work_dir / "wal"
        shutil.rmtree(wal_dir, ignore_errors=True)
        wal_dir.mkdir(parents=True)
        self.store.attach_wal(wal_dir / "rapidstore.wal", fsync=bool(a["wal_fsync"]))
        self.store.attach_compactor()
        self.compactor_interval = float(a["compactor_interval_s"])
        self.stamps = np.zeros(self.stream.n_writes, np.int64)
        phase("attach")

    def close(self):
        st = self.store
        try:
            st.detach_compactor()
        finally:
            try:
                st.detach_write_pipeline()
            finally:
                st.detach_wal()


def warm_up(dep: Deployment, reads: dict, search_fn, analytics_fn) -> None:
    """Assemble the first view on the device (tiles for searches, the COO
    for PageRank) and run the reader's program on it once: one batch of
    searches, or one PageRank.  Later views splice subgraphs, and hold an
    edge count, whose shapes depend on the writes; a program that has to
    compile for them compiles in the window, where it is counted."""
    st = dep.store
    with st.read_view() as v:
        if reads["op"] == "search":
            _ready(v.to_leaf_blocks_device().rows)
            dep.phase("first_view")
            us, vs = gen.split_keys(dep.base[:: max(1, len(dep.base) // 64)][:64])
            search_fn(v, us, vs)
            dep.phase("warm_search")
        else:
            _ready(v.to_coo_device())
            dep.phase("first_view")
            _ready(analytics_fn(v, reads))
            dep.phase("warm_analytics")


# ---------------------------------------------------------------------------
# Writers
# ---------------------------------------------------------------------------
class Writer:
    """Open loop (one write every ``interval_s``) or closed loop (up to
    ``outstanding`` writes in flight), from the stream after the warm-up
    writes.  Records submit and acknowledgement times and stamps."""

    def __init__(self, dep: Deployment, writes: dict, clock: Clock, fault=None):
        self.dep, self.w, self.clock, self.fault = dep, writes, clock, fault
        n = dep.stream.n_writes
        self.submit = np.full(n, np.nan)
        self.ack = np.full(n, np.nan)
        self.tickets = [None] * n
        self.n_sub = 0
        self.stop = threading.Event()
        self.error = None
        self.exhausted = False
        self._q = deque()
        self._cv = threading.Condition()
        self._threads = []
        self.late = []

    def _submit(self, j):
        st = self.dep.store
        ins, dels = self.dep.stream.write(j)
        if self.fault is not None:
            ins, dels = self.fault.write(j, ins, dels)
        with annotate("bench.apply_async"):
            self.submit[j] = self.clock()
            t = st.apply_async(ins, dels)
        self.tickets[j] = t
        self.n_sub = j + 1
        return t

    def _open(self):
        interval = float(self.w["interval_s"])
        n = len(self.submit)
        for j in range(n):
            due = j * interval
            while not self.stop.is_set():
                d = due - self.clock()
                if d <= 0:
                    break
                self.stop.wait(min(d, 0.05))
            if self.stop.is_set():
                return
            self.late.append(self.clock() - due)
            t = self._submit(j)
            with self._cv:
                self._q.append((j, t))
                self._cv.notify()
        self.exhausted = True

    def _acker(self):
        while True:
            with self._cv:
                while not self._q:
                    if self._done:
                        return
                    self._cv.wait(0.05)
                j, t = self._q.popleft()
            with annotate("bench.wait"):
                self.dep.stamps[j] = t.wait()
            self.ack[j] = self.clock()

    def _closed(self):
        depth = int(self.w["outstanding"])
        n = len(self.submit)
        inflight = deque()
        j = 0
        while not self.stop.is_set():
            while len(inflight) < depth and j < n and not self.stop.is_set():
                inflight.append((j, self._submit(j)))
                j += 1
            if not inflight:
                break
            k, t = inflight.popleft()
            with annotate("bench.wait"):
                self.dep.stamps[k] = t.wait()
            self.ack[k] = self.clock()
        self.exhausted = j >= n
        for k, t in inflight:
            self.dep.stamps[k] = t.wait()
            self.ack[k] = self.clock()

    def _guard(self, fn):
        def run():
            try:
                fn()
            except BaseException as e:  # surfaced by join()
                self.error = e
        return run

    def start(self):
        self._done = False
        fns = [self._open, self._acker] if self.w["loop"] == "open" else [self._closed]
        for fn in fns:
            th = threading.Thread(target=self._guard(fn), daemon=True)
            th.start()
            self._threads.append(th)

    def acked(self) -> int:
        return int(np.count_nonzero(~np.isnan(self.ack)))

    def halt(self):
        """Submit no more writes."""
        self.stop.set()

    def join(self):
        self._threads[0].join()
        self.dep.store.flush()
        with self._cv:
            self._done = True
            self._cv.notify_all()
        for th in self._threads[1:]:
            th.join()
        if self.error is not None:
            raise self.error
        # every submitted write resolves once the pipeline is flushed
        for j in range(self.n_sub):
            stamp = self.tickets[j].wait()
            self.dep.stamps[j] = stamp
            if np.isnan(self.ack[j]):
                self.ack[j] = self.clock()


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------
class Tracing:
    """Profiles one bounded slice of the window: from the first read cycle
    begun after ``after_s`` to the end of the first one that ends after
    ``after_s + seconds``."""

    def __init__(self, spec, out_dir: Path):
        self.after = float(spec["after_s"])
        self.until = self.after + float(spec["seconds"])
        self.dir = out_dir
        self.state = "idle"
        self._ann = None

    def before_cycle(self, now):
        if self.state == "idle" and now >= self.after:
            from . import trace

            trace.start(self.dir)
            self._ann = annotate("bench.trace_window")
            self._ann.__enter__()
            self.state = "on"

    def after_cycle(self, now):
        if self.state == "on" and now >= self.until:
            self.finish()

    def finish(self):
        if self.state == "on":
            from . import trace

            self._ann.__exit__(None, None, None)
            trace.stop()
            self.state = "done"


def serve_searches(dep, clock, seconds, search_fn, tracing, fault=None):
    """Open-loop point searches: each pass takes every request that is
    due, pins the newest snapshot, answers them with one batched search
    and unpins.  Requests due in the window are waited for up to
    ``GRACE_S`` past its close."""
    st = dep.store
    due = dep.req_due
    n = len(due)
    us, vs = gen.split_keys(dep.req_keys)
    answered = np.full(n, np.nan)
    req_ts = np.zeros(n, np.int64)
    found = np.zeros(n, bool)
    cycles = []
    i = 0
    while i < n:
        now = clock()
        if now > seconds + GRACE_S:
            break
        if due[i] > now:
            time.sleep(min(due[i] - now, 0.05))
            continue
        j = int(np.searchsorted(due, now, "right"))
        if tracing is not None:
            tracing.before_cycle(now)
        traced = tracing is not None and tracing.state == "on"
        t0 = clock()
        with annotate("bench.begin_read"):
            h = st.begin_read()
        try:
            view = h.view if fault is None else fault.view(st, h)
            with annotate("bench.view"):
                _ready(view.to_leaf_blocks_device().rows)
            t1 = clock()
            with annotate("bench.edge_search"):
                got = search_fn(view, us[i:j], vs[i:j])
            t2 = clock()
        finally:
            st.end_read(h)
        if fault is not None:
            got = fault.answers(i, got)
        answered[i:j] = t2
        req_ts[i:j] = h.ts
        found[i:j] = got
        cycles.append((t0, t1, t2, {"n": j - i, "traced": traced}))
        if tracing is not None:
            tracing.after_cycle(t2)
        i = j
    if tracing is not None:
        tracing.finish()
    return answered, req_ts, found, cycles


def run_analytics(dep, clock, seconds, reads, analytics_fn, tracing, writer, fault=None):
    """One closed-loop analytics client: once ``pin_after_writes`` writes
    of the window are acknowledged, pin the newest snapshot, run the
    program, wait for its result, unpin; start again while the window is
    open.  So every result is of a snapshot that writes have changed.  The
    window closes when the last result begun in it is done."""
    st = dep.store
    cycles, results = [], []
    need = int(reads["pin_after_writes"])
    while writer.acked() < need and writer.error is None and clock() < seconds:
        time.sleep(0.002)
    while True:
        if tracing is not None:
            tracing.before_cycle(clock())
        traced = tracing is not None and tracing.state == "on"
        t0 = clock()
        with annotate("bench.begin_read"):
            h = st.begin_read()
        try:
            view = h.view if fault is None else fault.view(st, h)
            with annotate("bench.view"):
                _ready(view.to_coo_device())
            t1 = clock()
            with annotate("bench.pagerank"):
                out = _ready(analytics_fn(view, reads))
            t2 = clock()
            n_edges = view.n_edges
        finally:
            st.end_read(h)
        if tracing is not None:
            tracing.after_cycle(t2)
        res = np.asarray(out)
        if fault is not None:
            res = fault.ranks(len(results), res)
        results.append((h.ts, res))
        cycles.append((t0, t1, t2, {"edges": n_edges, "traced": traced}))
        if clock() >= seconds:
            break
    if tracing is not None:
        tracing.finish()
    return cycles, results


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------
def window(dep: Deployment, traffic: dict, seconds: float, trace_dir, fault=None,
           search_fn=None, analytics_fn=None) -> Run:
    st = dep.store
    reads, writes = traffic["reads"], traffic["writes"]
    counter = CompileCounter.install()
    tracing = Tracing(traffic["trace"], trace_dir) if trace_dir is not None else None
    persistent_cache_off()
    wal0 = st.wal.syncs
    from repro.obs.trace import TRACER

    TRACER.clear()
    clock = Clock()
    counter.on = True
    writer = Writer(dep, writes, clock, fault)
    if fault is not None:
        fault.start(st)
    st.compactor.start(interval=dep.compactor_interval)
    writer.start()
    run = Run(cell="", config={}, traffic=traffic, seconds=seconds)
    try:
        if reads["op"] == "search":
            stopper = threading.Timer(seconds, writer.halt)
            stopper.daemon = True
            stopper.start()
            (run.answered, run.req_ts, run.found, run.cycles) = serve_searches(
                dep, clock, seconds, search_fn, tracing, fault)
            run.due = dep.req_due
            run.window_s = float(seconds)
            stopper.join()
        else:
            run.cycles, run.results = run_analytics(
                dep, clock, seconds, reads, analytics_fn, tracing, writer, fault)
            writer.halt()
            run.window_s = run.cycles[-1][2]
        counter.on = False
        run.compiles, run.compile_s = counter.count, counter.seconds
        run.memory_peak = memory_peak()
        writer.halt()
        writer.join()
    finally:
        counter.on = False
        writer.halt()
        if fault is not None:
            fault.close(st)
        st.compactor.stop()  # re-raises a background failure
    run.w_submit, run.w_ack = writer.submit[: writer.n_sub], writer.ack[: writer.n_sub]
    run.w_stamp = dep.stamps[: writer.n_sub]
    run.ops_per_write = dep.ins_per + dep.del_per
    run.stream_exhausted = writer.exhausted and writes["loop"] == "closed"
    run.wal_syncs = st.wal.syncs - wal0
    run.wal_spans = [((s.start_ns / 1e9) - (clock.t0), s.dur_ns / 1e9)
                     for s in TRACER.spans() if s.name == "wal_sync"]
    run.wal_span_count = TRACER.count("wal_sync")
    run.lateness = {"writer_max_s": max(writer.late, default=0.0)}
    return run


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------
def check(dep: Deployment, run: Run, limits: dict, reads: dict) -> dict:
    """Compare what the window produced with the plain reference.  Returns
    ``{name: [number, limit]}``; the run is correct when no number is above
    its limit."""
    hist = reference.EdgeHistory(dep.base, dep.stream.ins, dep.stream.dels, dep.stamps)
    out = {}
    if run.due is not None:
        ok = ~np.isnan(run.answered)
        want = hist.live(dep.req_keys[ok], run.req_ts[ok])
        out["wrong_answers"] = [int((want != run.found[ok]).sum()), limits["wrong_answers"]]
        out["unanswered"] = [int((~ok).sum()), limits["unanswered"]]
    if reads["op"] == "pagerank":
        errs = []
        for ts, ranks in run.results:
            want = reference.pagerank(hist.edges_at(ts), dep.n, int(reads["iters"]),
                                      float(reads["damping"]))
            errs.append(reference.max_rel_err(ranks, want))
        out["pagerank_max_rel_err"] = [max(errs), limits["pagerank_max_rel_err"]]
    if "readback_missing" in limits:
        out.update(readback(dep, hist, limits))
    return out


def readback(dep: Deployment, hist, limits) -> dict:
    """Every acknowledged insert is found and every acknowledged delete is
    absent in a view pinned after ``flush()``; the view holds exactly the
    reference's number of edges."""
    from repro.kernels.leaf_search import edge_search_view

    st = dep.store
    st.flush()
    acked = dep.stamps > 0
    ins = dep.stream.ins[acked].reshape(-1)
    dels = dep.stream.dels[acked].reshape(-1)
    keys = np.concatenate([ins, dels])
    with st.read_view() as v:
        us, vs = gen.split_keys(keys)
        got = np.asarray(edge_search_view(v, us, vs))
        n_edges = v.n_edges
        ts = v.ts
    want_n = len(dep.base) - len(dels) + len(ins)
    missing = int((~got[: len(ins)]).sum())
    present = int(got[len(ins):].sum())
    return {
        "readback_view_behind": [int(ts < dep.stamps.max()), 0],
        "readback_missing": [missing, limits["readback_missing"]],
        "readback_deleted_present": [present, limits["readback_deleted_present"]],
        "readback_edge_count_gap": [abs(int(n_edges) - want_n),
                                    limits["readback_edge_count_gap"]],
    }


def log(*a):
    print(*a, file=sys.stderr, flush=True)

#!/usr/bin/env python3
"""Smoke run of RapidStore's read and write path on a TPU.

    python chip_smoke.py              # one chip: the Graph500 deployment
    python chip_smoke.py --chips 4    # only the shard plane over 4 chips

One chip: a Graph500 Kronecker graph (scale 22, edgefactor 16, initiator
0.57/0.19/0.19) is bulk-loaded into a store with the paper's defaults
(|P|=64, B=512) and what a deployment attaches: the write pipeline, a WAL
with fsync on, and the compactor.  A stream of mixed insert/delete batches
goes through ``apply_async`` + ``flush``; a pinned view then runs PageRank,
BFS, SSSP, WCC and a batched edge search on the device, SpMM (d=128) on a
smaller R-MAT (the per-tile kernel does work proportional to tiles x
vertices, so scale 22 is out of reach), and triangle counting on an
undirected R-MAT of scale 18.  Every result is checked against an
independent numpy/scipy reference computed from the generated edges.

``--chips 4`` runs only the shard-plane phase: the collectives over
``jax.devices()[:4]`` are compared with the one-device results of the same
process, and every device is checked to hold its placed tiles.

Each phase prints one line; the last line is the JSON verdict.  The script
fails (non-zero exit, no verdict) when JAX finds no TPU, when the kernels
would be interpreted, when a path-choosing ``REPRO_*`` variable is set, or
when any check fails.  The compile cache goes to ``JAX_COMPILATION_CACHE_DIR``
when set, else to ``.jax_cache/`` at the checkout root.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

# environment variables that send reads down another path than a deployment's
PATH_VARS = (
    "REPRO_DISABLE_DEVICE_CACHE",
    "REPRO_DISABLE_SHARD_PLANE",
    "REPRO_DISABLE_DELTA_SPLICE",
    "REPRO_SPLICE_MAX_DIRTY_FRAC",
    "REPRO_LEAF_TIERS",
)

EDGEFACTOR = 16  # Graph500: edges = edgefactor * 2**scale
INITIATOR = (0.57, 0.19, 0.19)  # Graph500 Kronecker initiator (a, b, c)
SCALE = 22  # the deployment's graph
SPMM_SCALE = 16  # leaf_spmm's work grows with tiles x vertices
TC_SCALE = 18  # undirected graph for triangle counting
PLANE_SCALE = 18  # four-chip phase: faults across chips, at 4x the chip cost
INGEST_BATCHES, INGEST_BATCH = 32, 4096  # ops per batch: 3/4 insert, 1/4 delete
PROBES = 1 << 16  # edge-search probes, half of them present
SPMM_D = 128

# PageRank runs in float32 on the device against a float64 reference: each
# iteration sums a vertex's in-edge contributions in the scatter's order, so
# a vertex of in-degree k carries a rounding error of order sqrt(k) * 2^-24
# relative to its rank, damped by 0.85 per iteration.  rtol=1e-4 leaves
# margin above the ~2e-5 such sums reach at the hub in-degrees of these
# graphs, and is below the relative change one missing in-edge makes to a
# rank.  The plane and one-device programs fold the same float32 sums in
# different orders: they are held to the same bound.
PR_RTOL = 1e-4
# SSSP: the device sums float32 weights along a path of at most a few dozen
# hops; each addition rounds by at most 2^-24 of the running distance, so
# 1e-5 relative bounds a path of up to ~160 hops.
SSSP_RTOL = 1e-5


class SmokeFailure(RuntimeError):
    """A result that disagrees with its reference."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def report(phase: str, **kv) -> None:
    parts = [f"{k}={v}" for k, v in kv.items()]
    print(f"[{phase}] " + " ".join(parts), flush=True)


class Clock:
    """Wall time of a block; the block ends in ``block_until_ready``."""

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        return False


def ready(x):
    import jax

    return jax.block_until_ready(x)


def device_bytes() -> object:
    """Bytes in use on the local devices, or "not reported"."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None or "bytes_in_use" not in s for s in stats):
        return "not reported"
    return sum(int(s["bytes_in_use"]) for s in stats)


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------
def graph500_edges(scale: int, seed: int) -> np.ndarray:
    from repro.graph.generators import rmat_edges

    a, b, c = INITIATOR
    return rmat_edges(scale, EDGEFACTOR << scale, seed=seed, a=a, b=b, c=c)


def keys_of(u, v) -> np.ndarray:
    return (np.asarray(u, np.int64) << 32) | np.asarray(v, np.int64)


def split_keys(keys: np.ndarray):
    return keys >> 32, keys & 0xFFFFFFFF


def store_config() -> dict:
    from repro.configs.rapidstore import CONFIG

    return dict(
        partition_size=CONFIG.partition_size,
        B=CONFIG.leaf_width,
        high_threshold=CONFIG.high_degree_threshold,
        tracer_k=CONFIG.tracer_k,
    )


def ingest_stream(base_keys, n, seed, n_batches, batch):
    """Mixed batches: 3/4 inserts of fresh Kronecker edges absent from the
    base graph, 1/4 deletes of base edges; no edge is touched twice, so the
    final edge set is fixed by the stream alone."""
    from repro.graph.generators import rmat_edges

    rng = np.random.default_rng(seed)
    n_ins = n_batches * (batch - batch // 4)
    n_del = n_batches * (batch // 4)
    a, b, c = INITIATOR
    fresh = rmat_edges(n.bit_length() - 1, 4 * n_ins, seed=seed + 1, a=a, b=b, c=c)
    cand = np.unique(keys_of(fresh[:, 0], fresh[:, 1]))
    cand = cand[~np.isin(cand, base_keys, assume_unique=True)]
    ins = rng.permutation(cand)[:n_ins]
    check(len(ins) == n_ins, "ingest: not enough fresh edges to insert")
    dels = rng.choice(base_keys, size=n_del, replace=False)
    ins_b = np.array_split(ins, n_batches)
    del_b = np.array_split(dels, n_batches)
    return ins, dels, list(zip(ins_b, del_b))


def pairs(keys: np.ndarray) -> np.ndarray:
    u, v = split_keys(keys)
    return np.stack([u, v], 1)


# ---------------------------------------------------------------------------
# References (numpy / scipy, independent of the store and of JAX)
# ---------------------------------------------------------------------------
def csr_matrix(src, dst, n, data=None):
    """CSR over (src, dst) already sorted by (src, dst)."""
    import scipy.sparse as sp

    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
    if data is None:
        data = np.ones(len(dst), np.float64)
    return sp.csr_matrix((data, np.asarray(dst, np.int64), indptr), shape=(n, n))


def ref_pagerank(src, dst, n, iters=10, damping=0.85) -> np.ndarray:
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    p = np.full(n, 1.0 / n)
    for _ in range(iters):
        agg = np.bincount(dst, weights=(p * inv)[src], minlength=n)
        dangling = p[deg == 0].sum()
        p = (1.0 - damping) / n + damping * (agg + dangling / n)
    return p


def ref_levels(csr, root) -> np.ndarray:
    from scipy.sparse.csgraph import shortest_path

    d = shortest_path(csr, method="D", directed=True, unweighted=True, indices=root)
    out = np.full(csr.shape[0], -1, np.int64)
    hit = np.isfinite(d)
    out[hit] = d[hit].astype(np.int64)
    return out


def ref_sssp(csr_w, root) -> np.ndarray:
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(csr_w, directed=True, indices=root)


def ref_wcc(csr) -> np.ndarray:
    """Per-vertex label = smallest vertex id of its weak component."""
    from scipy.sparse.csgraph import connected_components

    _, comp = connected_components(csr, directed=True, connection="weak")
    n = csr.shape[0]
    low = np.full(comp.max() + 1, n, np.int64)
    np.minimum.at(low, comp, np.arange(n))
    return low[comp]


def ref_triangles(src, dst, n) -> int:
    """Triangles of a symmetric simple graph: orient every edge from lower
    to higher (degree, id) rank, then count closed wedges with a sparse
    product — each triangle once."""
    deg = np.bincount(src, minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    fwd = rank[src] < rank[dst]
    ru, rv = rank[src[fwd]], rank[dst[fwd]]
    order = np.lexsort((rv, ru))
    U = csr_matrix(ru[order], rv[order], n)
    return int((U @ U).multiply(U).sum())


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def build_deployment(scale, seed, out_dir: Path):
    """Generate and bulk-load the graph; attach pipeline, WAL, compactor."""
    from repro.core import RapidStore

    n = 1 << scale
    with Clock() as c_gen:
        edges = graph500_edges(scale, seed)
        base_keys = np.unique(keys_of(edges[:, 0], edges[:, 1]))
    report("generate", seconds=f"{c_gen.seconds:.3f}", scale=scale,
           vertices=n, generated=len(edges), distinct_edges=len(base_keys))
    with Clock() as c_load:
        store = RapidStore.from_edges(n, edges, **store_config())
        del edges
        wal_dir = out_dir / "wal"
        shutil.rmtree(wal_dir, ignore_errors=True)
        wal_dir.mkdir(parents=True)
        store.attach_write_pipeline()
        store.attach_wal(wal_dir / "rapidstore.wal", fsync=True)
        store.attach_compactor()
    report("load", seconds=f"{c_load.seconds:.3f}", kind="set-up",
           subgraphs=store.n_subgraphs, memory_bytes=store.memory_bytes(),
           device_bytes=device_bytes())
    return store, base_keys


def run_ingest(store, base_keys, seed, n_batches, batch):
    """apply_async every batch while the compactor folds in the background;
    flush, then check every ticket was acknowledged.  Returns the expected
    final edge keys and the acknowledged inserts and deletes."""
    n = store.n_vertices
    ins, dels, stream = ingest_stream(base_keys, n, seed, n_batches, batch)
    with Clock() as c:
        store.compactor.start(interval=0.5)
        try:
            tickets = [store.apply_async(pairs(i), pairs(d)) for i, d in stream]
            store.flush()
            stamps = [t.wait() for t in tickets]
        finally:
            store.compactor.stop()  # re-raises a background failure
        store.compactor.compact_once()
    check(all(ts > 0 for ts in stamps), "ingest: a batch was not acknowledged")
    expected = np.union1d(np.setdiff1d(base_keys, dels, assume_unique=True), ins)
    ops = len(ins) + len(dels)
    report("ingest", seconds=f"{c.seconds:.3f}", batches=n_batches, ops=ops,
           ops_per_s=f"{ops / c.seconds:.1f}", wal_syncs=store.wal.syncs,
           memory_bytes=store.memory_bytes())
    return expected, ins, dels


def check_capacity_coo(cols, src, dst) -> None:
    """The device COO in capacity form: ``coo_capacity(E)`` entries per
    column, the host COO as their prefix, SENTINEL after it."""
    from repro.core.leaf_pool import SENTINEL
    from repro.core.view_assembler import coo_capacity

    m = len(src)
    for name, col, want in (("src", cols[0], src), ("dst", cols[1], dst)):
        got = np.asarray(col)
        check(got.shape == (coo_capacity(m),),
              f"device COO {name}: {got.shape[0]} entries for {m} edges, "
              f"not the capacity {coo_capacity(m)}")
        check(np.array_equal(got[:m], want) and bool(np.all(got[m:] == SENTINEL)),
              f"device COO {name}: the prefix is not the host COO or the tail "
              "is not SENTINEL")


def pin_and_check(store, expected):
    """Pin a view, read its edges back, assemble its device arrays."""
    with Clock() as c_pin:
        handle = store.begin_read()
    view = handle.view
    src, dst = view.to_coo()
    check(np.array_equal(keys_of(src, dst), expected),
          "read-back: the pinned view's edges differ from the acknowledged "
          "inserts and deletes")
    report("pin", seconds=f"{c_pin.seconds:.6f}", ts=view.ts,
           edges=view.n_edges, readback="ok")
    with Clock() as c_up:
        cols = ready(view.to_coo_device())
        ready(view.to_leaf_blocks_device().rows)
    report("assemble", seconds=f"{c_up.seconds:.3f}", kind="set-up",
           tiles=int(view.to_leaf_blocks_device().rows.shape[0]),
           coo_capacity=int(cols[0].shape[0]), device_bytes=device_bytes())
    check_capacity_coo(cols, src, dst)
    return handle


def timed(phase, fn, *args):
    """Run ``fn`` twice: the first call includes compiling, the second is
    warm.  Returns the warm result as numpy."""
    with Clock() as c1:
        ready(fn(*args))
    with Clock() as c2:
        out = ready(fn(*args))
    report(phase, compile_and_run_s=f"{c1.seconds:.3f}", warm_s=f"{c2.seconds:.6f}")
    return np.asarray(out)


def run_analytics(view, expected, ins, dels, seed):
    """PageRank, BFS, SSSP, WCC and edge search on the device vs references."""
    from repro.core.analytics import bfs_view, pagerank_view, sssp_view, wcc_view
    from repro.kernels.leaf_search import edge_search_view

    n = view.n_vertices
    src, dst = split_keys(expected)
    rng = np.random.default_rng(seed + 1)
    root = int(np.argmax(np.bincount(src, minlength=n)))
    w = (1.0 - rng.random(len(expected), dtype=np.float32)).astype(np.float32)
    csr = csr_matrix(src, dst, n)

    pr = timed("pagerank", pagerank_view, view)
    want = ref_pagerank(src, dst, n)
    err = float(np.max(np.abs(pr - want) / want))
    check(err <= PR_RTOL, f"pagerank: max relative error {err} > {PR_RTOL}")
    report("pagerank", check="ok", max_rel_err=err)

    lv = timed("bfs", bfs_view, view, root)
    check(np.array_equal(lv.astype(np.int64), ref_levels(csr, root)),
          "bfs: levels differ from the reference")
    report("bfs", check="ok", root=root, reached=int((lv >= 0).sum()))

    dist = timed("sssp", sssp_view, view, w, root)
    want = ref_sssp(csr_matrix(src, dst, n, data=w.astype(np.float64)), root)
    fin = np.isfinite(want)
    check(np.array_equal(np.isfinite(dist), fin), "sssp: reachability differs")
    err = float(np.max(np.abs(dist[fin] - want[fin]) / np.maximum(want[fin], 1e-30)))
    check(err <= SSSP_RTOL, f"sssp: max relative error {err} > {SSSP_RTOL}")
    report("sssp", check="ok", max_rel_err=err)

    labels = timed("wcc", wcc_view, view)
    want = ref_wcc(csr)
    check(np.array_equal(labels.astype(np.int64), want), "wcc: labels differ")
    report("wcc", check="ok", components=len(np.unique(want)))

    # half present (acknowledged inserts first), half absent (acknowledged
    # deletes first, then random pairs that are no edge)
    half = PROBES // 2
    present = np.concatenate([ins[:half], rng.choice(expected, half)])[:half]
    absent = dels[:half]
    while len(absent) < half:
        cand = keys_of(rng.integers(0, n, half), rng.integers(0, n, half))
        cand = cand[~np.isin(cand, expected)]
        absent = np.concatenate([absent, cand])[:half]
    probe = np.concatenate([present, absent])
    pu, pv = split_keys(probe)
    found = timed("edge_search", edge_search_view, view, pu, pv)
    want = np.isin(probe, expected)
    check(want[:half].all() and not want[half:].any(), "edge search: bad probes")
    check(np.array_equal(found, want), "edge search: hits differ")
    report("edge_search", check="ok", probes=len(probe), hits=int(found.sum()))


def run_spmm(scale, seed):
    """spmm_view (d=128) over a bulk-loaded R-MAT of ``scale``.  H holds
    small integers, so every float32 sum is exact whatever the matmul
    precision: the result must equal the reference exactly."""
    from repro.core import RapidStore
    from repro.kernels.spmm import spmm_view

    n = 1 << scale
    edges = graph500_edges(scale, seed + 2)
    store = RapidStore.from_edges(n, edges, **store_config())
    keys = np.unique(keys_of(edges[:, 0], edges[:, 1]))
    src, dst = split_keys(keys)
    h = np.random.default_rng(seed + 3).integers(-8, 9, (n, SPMM_D)).astype(np.float32)
    with store.read_view() as view:
        y = timed("spmm", spmm_view, view, h)
    want = csr_matrix(src, dst, n) @ h.astype(np.float64)
    check(np.array_equal(y.astype(np.float64), want), "spmm: result differs")
    report("spmm", check="ok", scale=scale, d=SPMM_D, edges=len(keys))


def run_triangles(scale, seed):
    """Device triangle count over an undirected R-MAT of ``scale``."""
    from repro.core import RapidStore
    from repro.core.analytics import triangle_count_view

    n = 1 << scale
    e = graph500_edges(scale, seed + 4)
    store = RapidStore.from_edges(n, e, undirected=True, **store_config())
    with store.read_view() as view:
        src, dst = view.to_coo()
        with Clock() as c1:
            tc = triangle_count_view(view)
        with Clock() as c2:
            tc2 = triangle_count_view(view)
    sym = keys_of(np.concatenate([e[:, 0], e[:, 1]]), np.concatenate([e[:, 1], e[:, 0]]))
    check(np.array_equal(keys_of(src, dst), np.unique(sym)),
          "triangles: symmetric load differs from the generated edges")
    want = ref_triangles(src.astype(np.int64), dst.astype(np.int64), n)
    check(tc == want and tc2 == want, f"triangles: {tc}/{tc2} != reference {want}")
    report("triangle_count", compile_and_run_s=f"{c1.seconds:.3f}",
           warm_s=f"{c2.seconds:.3f}", check="ok", scale=scale, triangles=tc)


def run_one_chip(scale, spmm_scale, tc_scale, seed, n_batches, batch, out_dir):
    store, base_keys = build_deployment(scale, seed, Path(out_dir))
    try:
        expected, ins, dels = run_ingest(store, base_keys, seed, n_batches, batch)
        handle = pin_and_check(store, expected)
        try:
            run_analytics(handle.view, expected, ins, dels, seed)
        finally:
            store.end_read(handle)
    finally:
        store.detach_compactor()
        store.detach_write_pipeline()
        store.detach_wal()
    run_spmm(spmm_scale, seed)
    run_triangles(tc_scale, seed)


def check_plane_residency(plane, view, kinds):
    """Every shard's arrays sit on the device its placement names, and
    every device received an upload."""
    placement = plane.placement_at(view.ts, len(view.snaps))
    check(len({d.id for d in plane.devices}) == plane.n_shards,
          "plane: devices are not distinct")
    for kind in kinds:
        sharded = getattr(view.assembly.sharded, kind)
        for k, sb in enumerate(sharded.shards):
            check(sb.device == plane.devices[k], f"plane {kind}: shard {k} device")
            check(all(c.devices() == {plane.devices[k]} for c in sb.cols),
                  f"plane {kind}: shard {k} arrays are not on device {k}")
            check(np.array_equal(sb.sids, np.nonzero(placement == k)[0]),
                  f"plane {kind}: shard {k} holds other subgraphs than placed")
    check(all(u > 0 for u in plane.stats.uploads),
          f"plane: a device got no tiles (uploads {plane.stats.uploads})")


def run_plane(scale, spmm_scale, seed, n_devices):
    """Shard-plane collectives over ``n_devices`` vs one device."""
    from repro.core import RapidStore
    from repro.core.analytics import bfs_view, pagerank_view, sssp_view, wcc_view
    from repro.kernels.spmm import spmm_view

    results = {}
    for label, sc in (("analytics", scale), ("spmm", spmm_scale)):
        n = 1 << sc
        edges = graph500_edges(sc, seed + (0 if label == "analytics" else 2))
        store = RapidStore.from_edges(n, edges, **store_config())
        del edges
        rng = np.random.default_rng(seed + 5)
        with store.read_view() as v:
            m = v.n_edges
            src, _ = v.to_coo()
            root = int(np.argmax(np.bincount(src, minlength=n)))
        w = (1.0 - rng.random(m, dtype=np.float32)).astype(np.float32)
        h = rng.integers(-8, 9, (n, SPMM_D)).astype(np.float32)
        if label == "analytics":
            calls = {
                "pagerank": lambda v: pagerank_view(v),
                "bfs": lambda v: bfs_view(v, root),
                "sssp": lambda v: sssp_view(v, w, root),
                "wcc": lambda v: wcc_view(v),
            }
        else:
            calls = {"spmm": lambda v: spmm_view(v, h)}
        with store.read_view() as v:
            one = {k: timed(f"one_device_{k}", f, v) for k, f in calls.items()}
        plane = store.attach_shard_plane(n_devices=n_devices)
        with store.read_view() as v:
            many = {k: timed(f"plane_{k}", f, v) for k, f in calls.items()}
            check_plane_residency(
                plane, v, ("coo",) if label == "analytics" else ("blocks",)
            )
        report(f"plane_{label}", scale=sc, devices=n_devices,
               uploads=plane.stats.uploads, collective_calls=plane.stats.collective_calls)
        for k in calls:
            a, b = many[k], one[k]
            if k == "pagerank":
                err = float(np.max(np.abs(a - b) / np.abs(b)))
                check(err <= PR_RTOL, f"plane pagerank: max relative error {err}")
                report("plane_pagerank", check="ok", max_rel_err=err)
            else:
                check(a.dtype == b.dtype and np.array_equal(a, b),
                      f"plane {k}: differs from one device")
                report(f"plane_{k}", check="ok")
        results[label] = plane.stats.uploads
        store.detach_shard_plane()
    return results


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def require_tpu(chips: int) -> dict:
    """Refuse to run anywhere but the TPU path; returns the device record."""
    bad = [v for v in PATH_VARS if os.environ.get(v)]
    if bad:
        raise SystemExit(f"chip_smoke: path-choosing variables set: {bad}")
    import jax

    from repro.kernels.runtime import use_interpret

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX platform '{devs[0].platform}')")
    if use_interpret():
        raise SystemExit("chip_smoke: Pallas kernels would run interpreted")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips asked, {len(devs)} visible")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "smoke_out"),
                    help="directory for the WAL")
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    from repro.kernels.runtime import use_compile_cache

    report("device", **device, compile_cache=use_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        run_plane(PLANE_SCALE, SPMM_SCALE, args.seed, 4)
    else:
        run_one_chip(SCALE, SPMM_SCALE, TC_SCALE, args.seed,
                     INGEST_BATCHES, INGEST_BATCH, args.out)
    report("total", seconds=f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()

"""Device-resident leaf-block tile cache (core.device_cache).

Covers: bitwise parity of the device scan/intersect/spmm/analytics paths
against the kept ``*_uncached`` host oracles, cache hit/miss and upload
counters (zero host->device transfer on warm repeats; O(dirty) uploads
after a write), ``memory_bytes()`` accounting of resident device tiles,
and the release/GC invalidation contract (a recycled LeafPool row can
never serve a stale tile).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _parity import assert_capacity_cols, make_store, rand_edges
from repro.core import RapidStore, device_cache
from repro.core.analytics import (
    bfs_coo, bfs_view, pagerank_coo, pagerank_view, sssp_coo, sssp_view,
    wcc_coo, wcc_view,
)
from repro.core.leaf_pool import SENTINEL
from repro.kernels.intersect import intersect_tiles_view
from repro.kernels.intersect.ref import intersect_count_ref
from repro.kernels.leaf_search import edge_search_view
from repro.kernels.spmm import (
    leaf_scan_reduce, leaf_scan_reduce_view, leaf_spmm, leaf_spmm_view, spmm_view,
)


@pytest.fixture(autouse=True)
def _fresh_stats():
    device_cache.stats.reset()
    yield


# -- device layout parity vs host oracles -------------------------------------------
@pytest.mark.parametrize("p,B,ht", [(16, 16, 8), (64, 32, 16), (8, 8, 4)])
def test_device_blocks_bitmatch_host_oracle(p, B, ht):
    n = 96
    store = make_store(n=n, p=p, B=B, ht=ht)
    with store.read_view() as view:
        dev = view.to_leaf_blocks_device()
        host = view.to_leaf_blocks_uncached()
        assert np.array_equal(np.asarray(dev.src), host.src)
        assert np.array_equal(np.asarray(dev.rows), host.rows)
        assert np.array_equal(np.asarray(dev.length), host.length)
        # device COO/CSR match the uncached host materialization
        assert_capacity_cols(view.to_coo_device(), view.to_coo_uncached())
        csr_d = view.to_csr_device()
        csr_h = view.to_csr()
        assert np.array_equal(np.asarray(csr_d.offsets), csr_h.offsets)
        assert_capacity_cols((csr_d.indices,), (csr_h.indices,))
        # the tiles are genuine jax.Arrays with SENTINEL padding intact
        assert isinstance(dev.rows, jax.Array)
        rows = np.asarray(dev.rows)
        for row, ln in zip(rows, np.asarray(dev.length)):
            assert np.all(row[ln:] == SENTINEL)


def test_device_scan_intersect_spmm_bitmatch_host_oracle():
    n = 96
    store = make_store(n=n)
    rng = np.random.default_rng(3)
    with store.read_view() as view:
        oracle = view.to_leaf_blocks_uncached()
        x = rng.normal(size=n).astype(np.float32)
        got = np.asarray(leaf_scan_reduce_view(view, jnp.asarray(x)))
        want = np.asarray(leaf_scan_reduce(oracle.rows, x))
        assert np.array_equal(got, want)

        H = rng.normal(size=(n, 24)).astype(np.float32)
        got = np.asarray(leaf_spmm_view(view, jnp.asarray(H)))
        want = np.asarray(leaf_spmm(oracle.rows, H))
        assert np.array_equal(got, want)

        agg = np.asarray(spmm_view(view, jnp.asarray(H)))
        want_agg = np.zeros((n, 24), np.float32)
        np.add.at(want_agg, oracle.src, want)
        np.testing.assert_allclose(agg, want_agg, rtol=1e-6, atol=1e-6)

        nb = len(oracle.src)
        ia = rng.integers(0, nb, 32)
        ib = rng.integers(0, nb, 32)
        got = np.asarray(intersect_tiles_view(view, ia, ib))
        want = np.asarray(
            intersect_count_ref(jnp.asarray(oracle.rows[ia]), jnp.asarray(oracle.rows[ib]))
        )
        assert np.array_equal(got, want)


def test_device_edge_search_matches_point_reads():
    n = 96
    e = rand_edges(n, 700, seed=5)
    store = RapidStore.from_edges(n, e, partition_size=16, B=8, high_threshold=4)
    with store.read_view() as view:
        present = e[:60]
        absent = np.stack([present[:, 0], (present[:, 1] + 1) % n], 1)
        qs = np.concatenate([present, absent])
        got = edge_search_view(view, qs[:, 0], qs[:, 1])
        want = np.array([view.search(int(u), int(v)) for u, v in qs])
        assert np.array_equal(got, want)


def test_device_analytics_bitmatch_host_oracle():
    n = 96
    store = make_store(n=n, seed=7)
    rng = np.random.default_rng(7)
    with store.read_view() as view:
        src_o, dst_o = view.to_coo_uncached()
        # identical call conventions on both sides: jit caches by convention,
        # and positional-vs-keyword damping compiles to 1-ULP-different HLO
        pr_d = np.asarray(pagerank_view(view, device=True))
        pr_h = np.asarray(pagerank_coo(src_o, dst_o, n, iters=10, damping=0.85))
        assert np.array_equal(pr_d, pr_h)

        assert np.array_equal(
            np.asarray(bfs_view(view, 0, device=True)),
            np.asarray(bfs_coo(src_o, dst_o, n, 0)),
        )

        w = rng.uniform(0.1, 1.0, len(src_o)).astype(np.float32)
        assert np.array_equal(
            np.asarray(sssp_view(view, w, 0, device=True)),
            np.asarray(sssp_coo(src_o, dst_o, jnp.asarray(w), n, 0)),
        )

        assert np.array_equal(
            np.asarray(wcc_view(view, device=True)),
            np.asarray(
                wcc_coo(
                    jnp.concatenate([jnp.asarray(src_o, jnp.int32), jnp.asarray(dst_o)]),
                    jnp.concatenate([jnp.asarray(dst_o), jnp.asarray(src_o, jnp.int32)]),
                    n,
                )
            ),
        )
        # the host-routed view path agrees too (device=False)
        np.testing.assert_allclose(
            np.asarray(pagerank_view(view, device=False)), pr_d, rtol=1e-6
        )


# -- transfer accounting -------------------------------------------------------------
def test_warm_repeat_zero_uploads():
    n = 96
    store = make_store(n=n)
    with store.read_view() as view:
        x = jnp.asarray(np.random.default_rng(0).normal(size=n).astype(np.float32))
        pagerank_view(view, device=True)
        leaf_scan_reduce_view(view, x)
        view.to_csr_device()
        cold = device_cache.stats.snapshot()
        assert device_cache.stats.uploads > 0
        # warm repeats: identical results, ZERO further host->device uploads
        a = view.to_leaf_blocks_device()
        b = view.to_leaf_blocks_device()
        assert a is b
        assert view.to_csr_device() is view.to_csr_device()
        pagerank_view(view, device=True)
        leaf_scan_reduce_view(view, x)
        assert device_cache.stats.uploads == cold[2]
        assert device_cache.stats.bytes_uploaded == cold[3]

    # a brand-new view over the unchanged store reuses the retired
    # predecessor's assembled device arrays wholesale (delta plane, empty
    # dirty set): no uploads, no misses — and no per-snapshot touches at all
    from repro.core import view_assembler

    with store.read_view() as v2:
        before = device_cache.stats.snapshot()
        view_assembler.stats.reset()
        v2.to_leaf_blocks_device()
        v2.to_coo_device()
        after = device_cache.stats.snapshot()
        assert after[2] == before[2]  # uploads flat
        assert after[1] == before[1]  # no misses
        assert after[0] == before[0]  # not even per-snapshot cache hits
        assert view_assembler.stats.reuses == 2
        assert view_assembler.stats.snapshot_touches == 0


def test_write_uploads_only_dirty_subgraphs():
    from repro.core import view_assembler

    n = 128
    # pin the plain pool: this test asserts the device predecessor-splice
    # zero-touch contract, which only the single-B layout provides (tiered
    # assembly is a memoized per-tier concat that *hits* clean snap caches)
    store = make_store(n=n, m=800, seed=11, leaf_tiers=(16,))
    with store.read_view() as v1:
        v1.to_leaf_blocks_device()
        absent = next(v for v in range(2, n) if not v1.search(1, v))
    assert store.insert_edge(1, absent) > 0  # dirties subgraph 0 only
    before = device_cache.stats.snapshot()
    view_assembler.stats.reset()
    with store.read_view() as v2:
        v2.to_leaf_blocks_device()
        after = device_cache.stats.snapshot()
        # exactly one snapshot (3 arrays) re-uploaded and spliced into the
        # predecessor's device arrays; clean subgraphs are never touched
        # (delta plane — not even a per-snapshot cache hit)
        assert after[1] - before[1] == 1  # misses
        assert after[2] - before[2] == 3  # uploads
        assert after[0] - before[0] == 0  # hits: clean snaps untouched
        assert view_assembler.stats.splices == 1
        assert view_assembler.stats.snapshot_touches == 1
        assert view_assembler.stats.full_concats == 0
        # and the fresh tile stream is correct
        host = v2.to_leaf_blocks_uncached()
        assert np.array_equal(np.asarray(v2.to_leaf_blocks_device().rows), host.rows)


def _splice_one_subgraph(store, u):
    """Write one edge out of ``u`` and assemble a fresh view's device tiles.

    A full build uploads the view's concatenated arrays in one go; only a
    splice gives the dirty snapshot tiles of its own.  Returns the dirty
    (now tile-holding) snapshot.
    """
    with store.read_view() as v:
        v.to_leaf_blocks_device()
        v.to_coo_device()
        absent = next(w for w in range(store.n_vertices)
                      if w != u and not v.search(u, w))
    store.insert_edge(u, absent)
    with store.read_view() as v:
        v.to_leaf_blocks_device()
        v.to_coo_device()
        return v.snaps[u // store.p]


def test_memory_bytes_accounts_for_device_tiles():
    n = 64
    # pin the plain pool: tiered pools assemble from per-snapshot tiles
    store = make_store(n=n, m=400, seed=13, leaf_tiers=(16,))
    base = store.memory_bytes()
    with store.read_view() as view:
        view.to_leaf_blocks_device()
        view.to_coo_device()
        a = view.assembly
    # the retired bundle keeps the full build's device arrays accounted
    host_bytes = sum(s.cache_bytes() for c in store.chains for s in c._versions)
    assert a.device_bytes() > 0
    assert store.memory_bytes() == (
        base + host_bytes + a.host_bytes() + a.device_bytes()
    )
    # a splice's per-snapshot tiles are charged to their snapshot
    snap = _splice_one_subgraph(store, 1)
    assert snap.device_cache_bytes() > 0
    breakdown = store.memory_breakdown()
    assert breakdown["versions"] == sum(
        s.cache_bytes() + s.device_cache_bytes()
        + s.ci.values.nbytes + s.ci.offsets.nbytes + s.active.nbytes
        + sum(d.leaf_ids.nbytes + d.leaf_min.nbytes for d in s.dirs.values())
        for c in store.chains for s in c._versions
    )


# -- release / GC invalidation -------------------------------------------------------
def test_gc_release_drops_device_tiles_and_refuses_stale_materialization():
    n = 64
    store = RapidStore.from_edges(
        n, rand_edges(n, 700, seed=17), partition_size=16, B=8, high_threshold=4
    )
    _splice_one_subgraph(store, 3)
    with store.read_view() as v:
        old_snaps = v.snaps
        assert any(s.device_cache_bytes() for s in old_snaps)
        assert all(device_cache.tiles_fresh(s) for s in old_snaps)
    rel0 = device_cache.stats.releases
    # no pinned readers: each commit reclaims predecessor versions
    for i in range(4):
        store.insert_edges(rand_edges(n, 50, seed=300 + i))
        store.delete_edges(rand_edges(n, 30, seed=400 + i))
    assert store.stats["versions_reclaimed"] > 0
    live = {id(s) for c in store.chains for s in c._versions}
    reclaimed = [s for s in old_snaps if id(s) not in live]
    assert reclaimed, "expected at least one reclaimed version"
    for s in reclaimed:
        assert s.device_cache_bytes() == 0
        assert s._dev_blocks_cache is None and s._dev_coo_cache is None
        assert s._dev_gen_stamp is None
        # a released snapshot refuses to rebuild from (possibly recycled) rows
        with pytest.raises(RuntimeError, match="released"):
            s.to_leaf_blocks_global()
        with pytest.raises(RuntimeError, match="released"):
            s.to_coo_global()
        with pytest.raises(RuntimeError, match="released"):
            device_cache.leaf_block_tiles(s)
    assert device_cache.stats.releases > rel0
    # live snapshots' device tiles are provably fresh after the GC churn
    with store.read_view() as v2:
        v2.to_leaf_blocks_device()
        assert all(device_cache.tiles_fresh(s) for s in v2.snaps)
        host = v2.to_leaf_blocks_uncached()
        assert np.array_equal(np.asarray(v2.to_leaf_blocks_device().rows), host.rows)


def test_recycled_pool_row_never_serves_stale_tile():
    """End-to-end recycle: free rows via deletes, force re-allocation, and
    check that the generation stamp detects the recycle while every live
    view's device tiles keep bit-matching the host oracle."""
    n = 64
    store = RapidStore.from_edges(
        n, rand_edges(n, 900, seed=19), partition_size=8, B=8, high_threshold=4
    )
    _splice_one_subgraph(store, 5)
    with store.read_view() as v:
        old_snaps = v.snaps
        stamps = {s.sid: s._dev_gen_stamp for s in old_snaps if s._dev_gen_stamp}
        assert stamps
    frees0 = store.pool.n_frees
    for i in range(6):  # churn: deletes free rows, inserts recycle them
        store.delete_edges(rand_edges(n, 60, seed=500 + i))
        store.insert_edges(rand_edges(n, 60, seed=600 + i))
    assert store.pool.n_frees > frees0, "churn must actually free pool rows"
    # at least one of the stamped rows was freed (generation advanced) —
    # proving the detector trips exactly when a tile would have gone stale
    advanced = any(
        not np.array_equal(store.pool.generation[ids], gens)
        for ids, gens in stamps.values()
    )
    assert advanced, "expected some captured row generation to advance"
    # reclaimed old snapshots dropped their tiles before any recycle
    live = {id(s) for c in store.chains for s in c._versions}
    for s in old_snaps:
        if id(s) not in live:
            assert s._dev_blocks_cache is None
    # and the current view's device tiles match the oracle bit-for-bit
    with store.read_view() as v2:
        assert all(device_cache.tiles_fresh(s) for s in v2.snaps)
        dev = v2.to_leaf_blocks_device()
        host = v2.to_leaf_blocks_uncached()
        assert np.array_equal(np.asarray(dev.src), host.src)
        assert np.array_equal(np.asarray(dev.rows), host.rows)
        assert np.array_equal(np.asarray(dev.length), host.length)


def test_pinned_view_device_tiles_survive_concurrent_commits():
    n = 96
    store = make_store(n=n, seed=23, B=8, ht=4)
    h = store.begin_read()
    dev_before = h.view.to_leaf_blocks_device()
    rows_before = np.asarray(dev_before.rows).copy()
    for i in range(12):
        store.insert_edges(rand_edges(n, 40, seed=700 + i))
        store.delete_edges(rand_edges(n, 30, seed=800 + i))
    # the pinned view's tiles are untouched by newer commits + GC
    assert h.view.to_leaf_blocks_device() is dev_before
    assert np.array_equal(np.asarray(dev_before.rows), rows_before)
    assert all(device_cache.tiles_fresh(s) for s in h.view.snaps)
    store.end_read(h)


@pytest.mark.device
def test_tiles_live_on_accelerator():
    """Only meaningful with a real accelerator: tiles must not sit on host."""
    store = make_store()
    with store.read_view() as view:
        dev = view.to_leaf_blocks_device()
        platforms = {d.platform for d in dev.rows.devices()}
        assert platforms & {"tpu", "gpu", "cuda", "rocm"}

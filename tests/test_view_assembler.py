"""Delta-plane view assembly (core.view_assembler).

Covers: the acceptance contract — after a commit dirtying 1 of >= 32
subgraphs, a fresh view materializes host COO/CSR/leaf-blocks and device
COO/leaf-blocks with per-subgraph touches <= dirty count (no O(S)
concatenation), bitwise-identical to the ``*_uncached`` oracles; commit
lineage semantics (windows, symmetry, trimming); the full-concat fallbacks
(no predecessor, predecessor GC'd mid-chain, dirty fraction above the
threshold, REPRO_DISABLE_DELTA_SPLICE); retirement handoff rules; and
equal-size device splicing via dynamic_update_slice.
"""

import functools
import gc

import numpy as np
import pytest

from _parity import assert_capacity_cols, assert_view_matches_oracles, rand_edges
from _parity import make_store as _make_store
from repro.core import CommitLineage, RapidStore, device_cache, view_assembler
from repro.core.analytics import (
    pagerank_coo, pagerank_view, triangle_count_fast, triangle_count_view,
)

# this file's default store is larger (S = 32 subgraphs) so the O(d)-vs-O(S)
# contracts have room to be observable; helpers live in tests/_parity.py
make_store = functools.partial(_make_store, n=512, m=4000)


@pytest.fixture(autouse=True)
def _fresh_stats():
    view_assembler.stats.reset()
    device_cache.stats.reset()
    yield


# -- commit lineage ----------------------------------------------------------------
def test_lineage_windows_and_symmetry():
    lin = CommitLineage()
    lin.record(1, {0})
    lin.record(2, {3, 4})
    lin.record(4, {1})
    assert lin.dirty_between(0, 4) == {0, 3, 4, 1}
    assert lin.dirty_between(1, 2) == {3, 4}
    assert lin.dirty_between(2, 2) == frozenset()
    assert lin.dirty_between(2, 3) == frozenset()  # ts=4 outside (2, 3]
    # symmetric: the diff between two timestamps has no direction
    assert lin.dirty_between(4, 1) == lin.dirty_between(1, 4) == {3, 4, 1}


def test_lineage_trim_returns_unknown():
    lin = CommitLineage(max_records=4)
    for ts in range(1, 9):
        lin.record(ts, {ts % 3})
    assert len(lin) == 4  # records 5..8 survive, base_ts = 4
    assert lin.dirty_between(3, 8) is None  # window reaches trimmed region
    assert lin.dirty_between(4, 8) is not None  # exactly covered
    assert lin.dirty_between(4, 8) == {5 % 3, 6 % 3, 7 % 3, 8 % 3}


def test_store_lineage_records_dirty_sids():
    n, p = 128, 16
    store = RapidStore(n, partition_size=p, B=8, high_threshold=4)
    t1 = store.insert_edge(1, 2)  # subgraph 0
    t2 = store.insert_edges(np.array([[17, 3], [33, 5]], np.int64))  # sids 1, 2
    assert store.lineage.dirty_between(0, t1) == {0}
    assert store.lineage.dirty_between(t1, t2) == {1, 2}
    assert store.lineage.dirty_between(0, t2) == {0, 1, 2}


# -- the acceptance contract: O(d) splice, bitwise-identical -----------------------
def test_single_dirty_subgraph_splices_without_OS_concat():
    n, p = 512, 16  # S = 32 subgraphs
    # pin the plain single-B pool: this test counts device predecessor-splice
    # touches, a single-tier-layout path (multi-tier device assembly is a
    # memoized per-tier concat, covered by test_property_tiered instead)
    store = make_store(n=n, p=p, leaf_tiers=(16,))
    assert store.n_subgraphs >= 32
    with store.read_view() as v1:
        v1.to_csr()
        v1.to_leaf_blocks()
        v1.to_leaf_blocks_device()
        v1.to_coo_device()
        absent = next(w for w in range(1, n) if not v1.search(3, w))
    assert store.insert_edge(3, absent) > 0  # dirties subgraph 0 only

    view_assembler.stats.reset()
    device_cache.stats.reset()
    with store.read_view() as v2:
        # host CSR: one dirty COO segment + degree patch, no O(S) concat
        v2.to_csr()
        s = view_assembler.stats
        assert s.snapshot_touches <= 1 + 0, (
            f"host CSR touched {s.snapshot_touches} subgraph caches for 1 "
            f"dirty subgraph of {store.n_subgraphs}"
        )
        # device leaf blocks: only the dirty snapshot's tiles move
        v2.to_leaf_blocks_device()
        assert s.snapshot_touches <= 2  # one per assembled layout family
        assert device_cache.stats.uploads == 3  # (src, rows, length) once
        v2.to_leaf_blocks()
        v2.to_coo_device()
        assert s.snapshot_touches <= 4  # still <= dirty count per layout
        assert s.full_concats == 0
        assert s.splices >= 4
        assert_view_matches_oracles(v2)


def test_warm_view_chain_is_pure_reuse():
    store = make_store()
    with store.read_view() as v1:
        v1.to_coo()
        v1.to_csr()
        v1.to_leaf_blocks()
    view_assembler.stats.reset()
    with store.read_view() as v2:
        a = v2.to_coo()
        csr = v2.to_csr()
        lb = v2.to_leaf_blocks()
        s = view_assembler.stats
        assert s.snapshot_touches == 0
        # coo + csr + leaf blocks (the blocks path reuses both the compacted
        # stream and its padded twin, hence 4 reuse events for 3 calls)
        assert s.reuses == 4
        assert s.full_concats == 0
        assert_arrays = v2.to_coo()
        assert assert_arrays[0] is a[0]  # view-level memo still O(1)
    with store.read_view() as v3:  # chain continues through v2's retirement
        v3.to_coo()
        assert view_assembler.stats.snapshot_touches == 0


def test_analytics_after_small_write_use_splice():
    n = 512
    store = make_store(n=n)
    with store.read_view() as v1:
        pr1 = pagerank_view(v1, device=True)
        absent = next(w for w in range(1, n) if not v1.search(2, w))
    store.insert_edge(2, absent)
    view_assembler.stats.reset()
    with store.read_view() as v2:
        pr2 = np.asarray(pagerank_view(v2, device=True))
        assert view_assembler.stats.splices == 1
        assert view_assembler.stats.snapshot_touches == 1
        src_o, dst_o = v2.to_coo_uncached()
        want = np.asarray(pagerank_coo(src_o, dst_o, n, iters=10, damping=0.85))
        assert np.array_equal(pr2, want)


# -- fallbacks ---------------------------------------------------------------------
def test_first_view_full_concats():
    store = make_store(n=128)
    with store.read_view() as v:
        v.to_coo()
        assert view_assembler.stats.full_concats == 1
        assert view_assembler.stats.fallback_no_pred >= 1
        assert view_assembler.stats.snapshot_touches == store.n_subgraphs


def test_predecessor_gcd_mid_chain_falls_back_and_stays_correct():
    n = 256
    store = make_store(n=n)

    def warm():  # no local keeps the view (or its bundle) alive afterwards
        with store.read_view() as v1:
            v1.to_coo()
            v1.to_leaf_blocks()

    warm()
    store.insert_edge(1, 2)
    view_assembler.stats.reset()
    h = store.begin_read()  # holds only a weakref to v1's retired bundle
    # simulate GC of the predecessor mid-chain: the store lets go and the
    # bundle dies even though h's weakref was already handed out
    store._retired_assembly = None
    gc.collect()
    assert h.view._pred() is None
    v = h.view
    v.to_coo()
    assert view_assembler.stats.fallback_no_pred >= 1
    assert view_assembler.stats.full_concats == 1
    assert_view_matches_oracles(v)
    store.end_read(h)


def test_dirty_fraction_above_threshold_full_concats():
    n, p = 256, 16  # S = 16
    store = make_store(n=n, p=p)
    with store.read_view() as v1:
        v1.to_coo()
    # one batch touching every subgraph: dirty fraction 1.0 > 0.25
    ins = np.stack([np.arange(0, n, p, dtype=np.int64),
                    (np.arange(0, n, p) + 7) % n], 1)
    store.insert_edges(ins)
    view_assembler.stats.reset()
    with store.read_view() as v2:
        v2.to_coo()
        assert view_assembler.stats.fallback_dirty_frac >= 1
        assert view_assembler.stats.splices == 0
        assert view_assembler.stats.full_concats == 1
        assert_view_matches_oracles(v2)


def test_disable_env_forces_full_concat(monkeypatch):
    store = make_store(n=128)
    with store.read_view() as v1:
        v1.to_coo()
    store.insert_edge(1, 2)
    monkeypatch.setenv("REPRO_DISABLE_DELTA_SPLICE", "1")
    view_assembler.stats.reset()
    with store.read_view() as v2:
        v2.to_coo()
        assert view_assembler.stats.splices == 0
        assert view_assembler.stats.full_concats == 1
        assert_view_matches_oracles(v2)


def test_lineage_trim_forces_fallback_not_corruption():
    n = 128
    store = make_store(n=n)
    store.lineage.max_records = 2
    with store.read_view() as v1:
        v1.to_coo()
    for i in range(5):  # trims the window between v1 and the next read
        store.insert_edge(int(np.random.default_rng(i).integers(0, n)), (i + 3) % n)
    view_assembler.stats.reset()
    with store.read_view() as v2:
        v2.to_coo()
        assert view_assembler.stats.fallback_lineage >= 1
        assert_view_matches_oracles(v2)


# -- retirement handoff ------------------------------------------------------------
def test_point_read_only_view_does_not_clobber_predecessor():
    store = make_store(n=128)
    with store.read_view() as v1:
        v1.to_coo()
    bundle = store._retired_assembly
    assert bundle is not None
    with store.read_view() as v2:
        v2.search(0, 1)  # no materialization
    assert store._retired_assembly is bundle  # empty bundle was not kept
    store.insert_edge(1, 2)
    view_assembler.stats.reset()
    with store.read_view() as v3:
        v3.to_coo()
        assert view_assembler.stats.splices == 1  # spliced against v1's bundle


def test_growing_vertex_space_extends_dirty_set():
    n, p = 128, 16
    store = make_store(n=n, p=p, m=600)
    with store.read_view() as v1:
        v1.to_coo()
        v1.to_leaf_blocks()
    u = store.insert_vertex()  # may grow n_vertices (and possibly S)
    store.insert_edge(u, 0)
    with store.read_view() as v2:
        assert v2.n_vertices == store.n_vertices
        assert_view_matches_oracles(v2)
        csr = v2.to_csr()
        assert csr.n_vertices == v2.n_vertices
        assert np.array_equal(csr.neighbors(u), np.sort(v2.scan(u)))


# -- device splice mechanics -------------------------------------------------------
def test_equal_size_device_splice_dynamic_update():
    """delete+insert keeping segment sizes equal exercises the
    dynamic_update_slice patch path (same-shape splice)."""
    n, p = 256, 16
    store = make_store(n=n, p=p, m=2000)
    with store.read_view() as v1:
        v1.to_coo_device()
        v1.to_leaf_blocks_device()
        nbrs = v1.scan(3).copy()
        absent = next(w for w in range(1, n) if not v1.search(3, w))
    assert len(nbrs) > 0
    # one delete + one insert on the same vertex: same per-subgraph edge count
    store.apply(
        ins=np.array([[3, absent]], np.int64),
        dels=np.array([[3, int(nbrs[0])]], np.int64),
    )
    view_assembler.stats.reset()
    with store.read_view() as v2:
        cols = v2.to_coo_device()
        assert view_assembler.stats.splices == 1
        assert_capacity_cols(cols, v2.to_coo_uncached())
        db = v2.to_leaf_blocks_device()
        ob = v2.to_leaf_blocks_uncached()
        assert np.array_equal(np.asarray(db.rows), ob.rows)


def test_triangle_count_device_path_matches_host():
    n = 96
    e = rand_edges(n, 700, seed=9)
    store = RapidStore.from_edges(
        n, e, undirected=True, partition_size=16, B=8, high_threshold=4
    )
    with store.read_view() as v:
        want = triangle_count_fast(v.to_csr())
        assert triangle_count_view(v, device=True) == want
        assert triangle_count_view(v, device=False) == want
    # still exact after an (undirected) write
    with store.read_view() as v:
        absent = next(w for w in range(1, n) if not v.search(0, w))
    store.insert_edges(np.array([[0, absent], [absent, 0]], np.int64))
    with store.read_view() as v2:
        assert triangle_count_view(v2, device=True) == triangle_count_fast(v2.to_csr())


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_interleaving_sweep_bitmatch_oracles(seed):
    """Deterministic mirror of the hypothesis interleaving property
    (tests/test_property_delta_plane.py), so the delta plane is exercised
    even where hypothesis is unavailable: random small/wide writes, reads
    verifying every layout against the uncached oracles, and periodic
    predecessor-bundle drops (GC mid-chain)."""
    n, p = 64, 8
    rng = np.random.default_rng(seed)
    store = RapidStore(n, partition_size=p, B=8, high_threshold=4)
    oracle = set()
    for step in range(30):
        r = rng.random()
        if r < 0.45:  # small single-subgraph write (splice territory)
            sid = int(rng.integers(0, n // p))
            us = rng.integers(sid * p, (sid + 1) * p, size=int(rng.integers(1, 5)))
            vs = rng.integers(0, n, size=len(us))
            ins = np.stack([us, vs], 1).astype(np.int64)
            ins = ins[ins[:, 0] != ins[:, 1]]
            dels = np.empty((0, 2), np.int64)
            local = [e for e in oracle if e[0] // p == sid]
            if local and rng.random() < 0.5:
                dels = np.array(
                    [local[i] for i in rng.integers(0, len(local), size=2)], np.int64
                )
            store.apply(ins, dels)
            oracle |= {(int(u), int(v)) for u, v in ins}
            oracle -= {(int(u), int(v)) for u, v in dels}
        elif r < 0.6:  # wide write: dirty fraction above the splice threshold
            ins = rand_edges(n, 40, seed=int(rng.integers(1 << 30)))
            store.insert_edges(ins)
            oracle |= {(int(u), int(v)) for u, v in ins}
        elif r < 0.7:  # predecessor assembly GC'd mid-chain
            store._retired_assembly = None
            gc.collect()
        else:  # verified read
            with store.read_view() as view:
                assert_view_matches_oracles(view)
                assert view.edge_set() == oracle
    with store.read_view() as view:
        assert_view_matches_oracles(view)
        assert view.edge_set() == oracle
    assert view_assembler.stats.splices > 0  # the sweep exercised the delta path
    store.check_invariants()


def test_empty_view_block_width_matches_pool_B():
    """Satellite bugfix: empty views must emit the store's configured B, not
    a hardcoded 8 — device padding disagrees otherwise."""
    # single-element tier spec pins B=32 even under a REPRO_LEAF_TIERS env
    store = RapidStore(40, partition_size=8, leaf_tiers=(32,))
    with store.read_view() as v:
        assert v.B == 32
        assert v.to_leaf_blocks().rows.shape == (0, 32)
        assert v.to_leaf_blocks_uncached().rows.shape == (0, 32)
        assert v.to_leaf_blocks_device().rows.shape == (0, 32)

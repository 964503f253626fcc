"""The capacity-form device COO (``view_assembler.coo_capacity``).

Covers: the eighth-octave ladder (monotone, every value a rung, at most
12.5% waste above its floor); the ``to_coo_device`` contract on each
assembly path (full, patch, concat): columns of length
``coo_capacity(n_edges)``, the prefix bitwise equal to the host COO, a
SENTINEL tail; the analytics programs on a view below its capacity,
bitwise equal to the exact COO; and PageRank on a fresh view reusing its
compiled program while the edge count keeps its rung, compiling once where
a commit crosses one.
"""

import jax
import numpy as np
import pytest

from _parity import ENTRY_CASES, assert_capacity_cols, assert_view_matches_oracles, bits
from _parity import make_entry_ctx, rand_edges
from repro.core import RapidStore, view_assembler
from repro.core.analytics import pagerank_coo, pagerank_view
from repro.core.view_assembler import COO_MIN_CAPACITY, coo_capacity
from repro.obs import trace as trace_mod
from repro.obs.trace import TRACER, SpanRing

_COMPILES = [0]  # backend compiles seen by this module's own listener


def _count_compiles(event, seconds, **_):
    if event == trace_mod.COMPILE_EVENT:
        _COMPILES[0] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)


@pytest.fixture(autouse=True)
def _fresh_stats():
    view_assembler.stats.reset()
    yield


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


def _unique_edges(n, m, seed):
    """Exactly ``m`` distinct directed edges, no self-loops."""
    rng = np.random.default_rng(seed)
    keys = rng.choice(n * (n - 1), size=m, replace=False)
    u, r = keys // (n - 1), keys % (n - 1)
    return np.stack([u, r + (r >= u)], 1).astype(np.int64)


def _store(n=512, m=3000, seed=4, p=16):
    return RapidStore.from_edges(n, rand_edges(n, m, seed), partition_size=p, B=16,
                                 high_threshold=8)


# -- the ladder ----------------------------------------------------------------------
def test_capacity_ladder_is_monotone_and_wastes_at_most_an_eighth():
    ms = np.unique(np.concatenate([
        np.arange(0, 5000),
        np.geomspace(5000, 2**31 - 1, 3000).astype(np.int64),
    ]))
    caps = np.array([coo_capacity(int(m)) for m in ms])
    assert np.all(caps >= ms)
    assert np.all(np.diff(caps) >= 0)
    assert np.all(caps[ms <= COO_MIN_CAPACITY] == COO_MIN_CAPACITY)
    big = ms > COO_MIN_CAPACITY
    assert np.all((caps[big] - ms[big]) / ms[big] <= 0.125)
    for c in set(caps.tolist()):
        # each a rung (8 + j) * 2**(e - 3), and its own capacity
        e = c.bit_length() - 1
        assert c % (1 << (e - 3)) == 0
        assert coo_capacity(c) == c
    # the Graph500 scale-22 deployment: 65.25M edges on the 2**26 rung
    assert coo_capacity(65_250_000) == 1 << 26
    assert coo_capacity((1 << 26) + 1) == 9 << 23


# -- the to_coo_device contract on each assembly path --------------------------------
@pytest.mark.parametrize("path", ["full", "patch", "concat"])
def test_device_coo_is_capacity_form_on_every_path(path, tracer):
    n = 512
    store = _store(n)
    if path != "full":  # a warm predecessor, then a commit to splice
        with store.read_view() as v:
            v.to_coo_device()
            src, dst = v.to_coo()
        have = set(dst[src == 5].tolist())
        new = next(w for w in range(n) if w != 5 and w not in have)
        ins = np.array([[5, new]], np.int64)
        if path == "patch":  # an insert and a delete: the segment keeps its length
            store.apply(ins, np.array([[5, min(have)]], np.int64))
        else:
            store.insert_edges(ins)
    tracer.enabled = True
    with store.read_view() as v:
        m = v.n_edges
        cols = v.to_coo_device()
        assert int(cols[0].shape[0]) == coo_capacity(m) > m
        assert_capacity_cols(cols, v.to_coo_uncached())
        assert_view_matches_oracles(v)
    s, = [s for s in tracer.spans() if s.name == "view_splice" and "cap" in s.args]
    assert s.args["path"] == path
    assert s.args["cap"] == coo_capacity(m) and s.args["n_edges"] == m


# -- analytics below capacity --------------------------------------------------------
@pytest.mark.parametrize("case", ["pagerank_view", "bfs_view", "sssp_view", "wcc_view"])
def test_analytics_below_capacity_match_the_exact_coo(case):
    n = 512
    store = _store(n, seed=6)
    with store.read_view() as v:
        v.to_coo_device()
    store.insert_edge(9, 10)  # the spliced view's concatenate writes the tail
    with store.read_view() as v:
        m = v.n_edges
        assert int(v.to_coo_device()[0].shape[0]) == coo_capacity(m) > m
        assert view_assembler.stats.splices >= 1
        assert ENTRY_CASES[case](v, make_entry_ctx(v))


# -- no recompile inside a rung ------------------------------------------------------
def test_fresh_view_reuses_pagerank_until_a_rung_is_crossed():
    # an n no other test runs PageRank on, so its programs are this test's
    n, p = 320, 16
    S = n // p
    store = RapidStore.from_edges(n, _unique_edges(n, 2400, 8), partition_size=p,
                                  B=16, high_threshold=8)
    with store.read_view() as v1:
        m1 = v1.n_edges
        pagerank_view(v1).block_until_ready()
        src1, dst1 = v1.to_coo()
    cap = coo_capacity(m1)
    assert m1 == 2400 and cap == 2560
    # one insert into every subgraph and a few deletes: more than a quarter
    # of the subgraphs dirty, so the view is a full build (no compile of
    # its own), and 2400 + S - S // 4 edges keep the rung
    have = set(zip(src1.tolist(), dst1.tolist()))
    ins = [(s * p, next(w for w in range(n) if w != s * p and (s * p, w) not in have))
           for s in range(S)]
    dels = np.stack([src1[: S // 4], dst1[: S // 4]], 1).astype(np.int64)
    store.apply(np.array(ins, np.int64), dels)
    with store.read_view() as v2:
        assert coo_capacity(v2.n_edges) == cap and v2.n_edges != m1
        before = _COMPILES[0]
        got = np.asarray(pagerank_view(v2))
        assert _COMPILES[0] == before  # the view and the program: no compile
        assert view_assembler.stats.full_concats >= 2
        src_o, dst_o = v2.to_coo_uncached()
    want = np.asarray(pagerank_coo(src_o, dst_o, n, iters=10, damping=0.85))
    assert np.array_equal(bits(got), bits(want))
    assert view_assembler.stats.coo_capacity_grows == 0

    # a commit across the rung: one compile, one capacity change
    fresh = _unique_edges(n, 600, 9)
    store.insert_edges(fresh)
    with store.read_view() as v3:
        assert coo_capacity(v3.n_edges) > cap
        before = _COMPILES[0]
        got = np.asarray(pagerank_view(v3))
        assert _COMPILES[0] == before + 1
        src_o, dst_o = v3.to_coo_uncached()
    assert view_assembler.stats.coo_capacity_grows == 1
    want = np.asarray(pagerank_coo(src_o, dst_o, n, iters=10, damping=0.85))
    assert np.array_equal(bits(got), bits(want))

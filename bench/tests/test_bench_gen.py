"""The generators and the reference: deterministic per seed, the Graph500
relabelling a bijection, a write stream that touches no edge twice, and
the reference's edge set at a timestamp equal to a store driven through
the same writes."""

import numpy as np

from bench.harness import gen, reference

KRON = {"generator": "kronecker", "scale": 10, "edgefactor": 16,
        "initiator": [0.57, 0.19, 0.19], "permute": True}
UNIF = {"generator": "uniform", "scale": 10, "edgefactor": 16, "permute": False}
SEED = 2**33 + 7


def test_same_seed_same_graph():
    for g in (KRON, UNIF):
        a, b = gen.base_edges(g, SEED), gen.base_edges(g, SEED)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, gen.base_edges(g, SEED + 1))
        # a seed above 32 bits is not cut to its low bits
        assert not np.array_equal(a, gen.base_edges(g, SEED & 0xFFFFFFFF))
        u, v = gen.split_keys(a)
        assert (u != v).all() and np.array_equal(a, np.unique(a))
        assert u.max() < 1 << g["scale"] and v.max() < 1 << g["scale"]


def test_graph500_permutation_is_a_bijection():
    p = gen.vertex_permutation(SEED, 12)
    assert np.array_equal(np.sort(p), np.arange(1 << 12))
    assert not np.array_equal(p, np.arange(1 << 12))


def test_kronecker_is_skewed_and_uniform_is_not():
    ku, _ = gen.split_keys(gen.base_edges(KRON, SEED))
    uu, _ = gen.split_keys(gen.base_edges(UNIF, SEED))
    assert np.bincount(ku).max() > 8 * np.bincount(uu).max() / 2


def test_write_stream_touches_no_edge_twice():
    base = gen.base_edges(KRON, SEED)
    s = gen.WriteStream(KRON, SEED, base, 50, 48, 16)
    ins, dels = s.ins.reshape(-1), s.dels.reshape(-1)
    assert len(np.unique(ins)) == len(ins) and len(np.unique(dels)) == len(dels)
    assert not gen.in_sorted(ins, base).any()
    assert gen.in_sorted(dels, base).all()
    again = gen.WriteStream(KRON, SEED, base, 50, 48, 16)
    assert np.array_equal(again.ins, s.ins) and np.array_equal(again.dels, s.dels)


def test_search_requests_same_count_every_seed():
    base = gen.base_edges(KRON, SEED)
    s = gen.WriteStream(KRON, SEED, base, 40, 48, 16)
    reads = {"rate_per_s": 50.0, "hit_share": 0.4, "recent_insert_share": 0.2,
             "recent_delete_share": 0.2, "recent_s": 0.5}
    due_w = np.arange(40) * 0.125
    n = [len(gen.search_requests(sd, 1 << 10, base, s, due_w, reads, 4.0)[0])
         for sd in (1, 2, SEED)]
    assert n == [200, 200, 200]
    due, keys = gen.search_requests(SEED, 1 << 10, base, s, due_w, reads, 4.0)
    assert (np.diff(due) >= 0).all()
    dels = np.sort(s.dels.reshape(-1))
    hits = gen.in_sorted(keys, base) & ~gen.in_sorted(keys, dels)
    assert hits.sum() == 80
    # recent keys: inserted or deleted by a write due within recent_s of
    # the request, so their answer changes inside the window
    for table in (s.ins, s.dels):
        write_of = {int(k): w for w in range(table.shape[0]) for k in table[w]}
        recent = [(d, write_of[int(k)]) for d, k in zip(due, keys) if int(k) in write_of]
        assert len(recent) == 40
        assert all(abs(due_w[w] - d) <= 0.5 + 1e-9 for d, w in recent)
    others = ~(gen.in_sorted(keys, base) | gen.in_sorted(keys, np.sort(s.ins.reshape(-1))))
    assert others.sum() == 40


def test_reference_matches_store_at_each_stamp():
    from repro.core import RapidStore

    base = gen.base_edges(KRON, SEED)
    s = gen.WriteStream(KRON, SEED, base, 12, 48, 16)
    store = RapidStore.from_edges(1 << 10, gen.pairs(base), partition_size=64, B=512,
                                  high_threshold=256)
    stamps, seen = [], {}
    for i in range(s.n_writes):
        stamps.append(store.apply(*s.write(i)))
        if i % 3 == 0:
            with store.read_view() as v:
                src, dst = v.to_coo()
                seen[v.ts] = gen.keys_of(src, dst)
    hist = reference.EdgeHistory(base, s.ins, s.dels, stamps)
    assert len(seen) == 4
    for ts, keys in seen.items():
        assert np.array_equal(hist.edges_at(ts), keys)
        assert hist.live(keys, ts).all()
    assert not hist.live(s.dels.reshape(-1), max(stamps)).any()
    assert hist.live(s.dels.reshape(-1), 0).all()
    # the float64 PageRank sums to one
    p = reference.pagerank(hist.edges_at(max(stamps)), 1 << 10)
    assert abs(p.sum() - 1.0) < 1e-9

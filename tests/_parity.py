"""Shared parity fixtures: ``*_view`` entry points vs ``*_uncached`` oracles.

One canonical copy of the helpers that used to be duplicated across
``test_device_cache.py``, ``test_view_assembler.py``, ``test_shard_plane.py``
and the property suites:

- :func:`rand_edges` / :func:`make_store` — the standard small random store;
- :func:`bits` — float32 -> uint32 view for *bitwise* comparisons;
- :func:`pack_padded` — padded ``LeafBlockView`` -> compacted-stream tuple,
  the independent oracle for the compacted layout;
- :func:`assert_capacity_cols` — a capacity-form device COO column
  against its exact host twin (length, prefix, SENTINEL tail);
- :func:`assert_view_matches_oracles` — every materialization layout (host
  COO/CSR/padded blocks/compacted stream, device COO/CSR/blocks) asserted
  bitwise against the per-vertex-loop ``*_uncached`` oracles;
- :func:`make_entry_ctx` / :data:`ENTRY_CASES` — the cross-layout fixture
  matrix: each ``*_view`` entry point (kernels + analytics) paired with an
  oracle computed from the uncached arrays, over identical operands.

``tests/test_parity_matrix.py`` runs the matrix across the host / device /
sharded routes and both ``REPRO_DISABLE_DELTA_SPLICE`` legs.
"""

import os

import numpy as np


def hypothesis_examples(default: int) -> int:
    """Example budget for the hypothesis suites: the nightly/``--full`` CI
    leg raises it via ``REPRO_HYPOTHESIS_MAX_EXAMPLES`` (see tier1.yml)."""
    budget = int(os.environ.get("REPRO_HYPOTHESIS_MAX_EXAMPLES", "0"))
    return budget if budget > 0 else default


def rand_edges(n, m, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2), dtype=np.int64)
    return e[e[:, 0] != e[:, 1]]


def make_store(n=96, m=900, seed=1, p=16, B=16, ht=8, undirected=False,
               leaf_tiers=None):
    from repro.core import RapidStore

    return RapidStore.from_edges(
        n, rand_edges(n, m, seed), undirected=undirected,
        partition_size=p, B=B, high_threshold=ht, leaf_tiers=leaf_tiers,
    )


def bits(a):
    """Reinterpret float32 as uint32 so equality asserts are bitwise."""
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


def pack_padded(lb):
    """Pack a padded ``LeafBlockView`` into the compacted-stream tuple
    ``(data, leaf_offsets, leaf_lens, leaf_keys)`` — the independent oracle
    for ``to_leaf_stream`` (never touches the stream code path)."""
    lens = lb.length.astype(np.int64)
    B = lb.rows.shape[1]
    mask = np.arange(B)[None, :] < lens[:, None]
    offsets = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=offsets[1:])
    return lb.rows[mask], offsets, lb.length, lb.src


def assert_capacity_cols(cols, exact):
    """Capacity-form device columns against exact host columns: each has
    length ``coo_capacity(len(exact))``, its prefix equals the host column
    bitwise, and its tail is all SENTINEL."""
    from repro.core.leaf_pool import SENTINEL
    from repro.core.view_assembler import coo_capacity

    for got, want in zip(cols, exact, strict=True):
        got, m = np.asarray(got), len(want)
        assert got.shape == (coo_capacity(m),)
        assert np.array_equal(got[:m], want)
        assert np.all(got[m:] == SENTINEL)


def assert_view_matches_oracles(view):
    """Bitwise parity of every materialization layout vs the uncached
    oracles: host COO/CSR/blocks/stream and device COO/CSR/blocks."""
    src, dst = view.to_coo()
    osrc, odst = view.to_coo_uncached()
    assert np.array_equal(src, osrc) and np.array_equal(dst, odst)
    csr = view.to_csr()
    degs = np.bincount(osrc, minlength=view.n_vertices)
    off = np.zeros(view.n_vertices + 1, np.int64)
    np.cumsum(degs, out=off[1:])
    assert np.array_equal(csr.offsets, off)
    assert np.array_equal(csr.indices, odst)
    ob = view.to_leaf_blocks_uncached()
    lb = view.to_leaf_blocks()
    assert np.array_equal(lb.src, ob.src)
    assert np.array_equal(lb.rows, ob.rows)
    assert np.array_equal(lb.length, ob.length)
    # the compacted stream vs the packed padded oracle
    st = view.to_leaf_stream()
    odata, ooffsets, olens, okeys = pack_padded(ob)
    assert np.array_equal(st.data, odata)
    assert np.array_equal(st.leaf_offsets, ooffsets)
    assert np.array_equal(st.leaf_lens, olens)
    assert np.array_equal(st.leaf_keys, okeys)
    db = view.to_leaf_blocks_device()
    assert np.array_equal(np.asarray(db.src), ob.src)
    assert np.array_equal(np.asarray(db.rows), ob.rows)
    assert np.array_equal(np.asarray(db.length), ob.length)
    assert_capacity_cols(view.to_coo_device(), (osrc, odst))
    dcsr = view.to_csr_device()
    assert np.array_equal(np.asarray(dcsr.offsets), off)
    assert_capacity_cols((dcsr.indices,), (odst,))


# ---------------------------------------------------------------------------
# The *_view entry-point matrix
# ---------------------------------------------------------------------------
def make_entry_ctx(view, seed=0):
    """Shared operands + uncached-oracle arrays for the entry-point matrix.

    Everything downstream oracles need is derived from the ``*_uncached``
    materializers here, once, so every case compares the live entry point
    against the same independent inputs.
    """
    rng = np.random.default_rng(seed)
    n = view.n_vertices
    ob = view.to_leaf_blocks_uncached()
    src_o, dst_o = view.to_coo_uncached()
    nb = max(1, len(ob.src))
    present = np.stack([src_o, dst_o.astype(np.int64)], 1)[:40] if len(src_o) \
        else np.empty((0, 2), np.int64)
    qs = np.concatenate([
        present,
        np.stack([present[:, 0], (present[:, 1] + 1) % n], 1),
    ]) if len(present) else np.empty((0, 2), np.int64)
    return dict(
        n=n,
        blocks=ob,
        src_o=src_o,
        dst_o=dst_o,
        x=rng.normal(size=n).astype(np.float32),
        H=rng.normal(size=(n, 12)).astype(np.float32),
        w=(rng.random(len(src_o)) + 0.1).astype(np.float32),
        ia=rng.integers(0, nb, 24),
        ib=rng.integers(0, nb, 24),
        queries=qs,
    )


def _case_edge_search(view, ctx):
    from repro.kernels.leaf_search import edge_search_view

    qs = ctx["queries"]
    if not len(qs):
        return True
    got = edge_search_view(view, qs[:, 0], qs[:, 1])
    want = np.array([view.search(int(u), int(v)) for u, v in qs])
    return np.array_equal(got, want)


def _case_intersect(view, ctx):
    import jax.numpy as jnp

    from repro.kernels.intersect import intersect_tiles_view
    from repro.kernels.intersect.ref import intersect_count_ref

    ob, ia, ib = ctx["blocks"], ctx["ia"], ctx["ib"]
    if not len(ob.src):
        return True
    got = np.asarray(intersect_tiles_view(view, ia, ib))
    want = np.asarray(
        intersect_count_ref(jnp.asarray(ob.rows[ia]), jnp.asarray(ob.rows[ib]))
    )
    return np.array_equal(got, want)


def _case_sum_intersect(view, ctx):
    import jax.numpy as jnp

    from repro.kernels.intersect import sum_intersect_tiles_view
    from repro.kernels.intersect.ref import intersect_count_ref

    ob, ia, ib = ctx["blocks"], ctx["ia"], ctx["ib"]
    if not len(ob.src):
        return True
    got = sum_intersect_tiles_view(view, ia, ib, batch=16)
    want = int(np.asarray(
        intersect_count_ref(jnp.asarray(ob.rows[ia]), jnp.asarray(ob.rows[ib])),
        np.int64,
    ).sum())
    return got == want


def _case_scan_reduce(view, ctx):
    import jax.numpy as jnp

    from repro.kernels.spmm import leaf_scan_reduce, leaf_scan_reduce_view

    got = np.asarray(leaf_scan_reduce_view(view, jnp.asarray(ctx["x"])))
    want = np.asarray(leaf_scan_reduce(ctx["blocks"].rows, ctx["x"]))
    return np.array_equal(bits(got), bits(want))


def _case_leaf_spmm(view, ctx):
    import jax.numpy as jnp

    from repro.kernels.spmm import leaf_spmm, leaf_spmm_view

    got = np.asarray(leaf_spmm_view(view, jnp.asarray(ctx["H"])))
    want = np.asarray(leaf_spmm(ctx["blocks"].rows, ctx["H"]))
    return np.array_equal(bits(got), bits(want))


def _case_spmm(view, ctx):
    import jax
    import jax.numpy as jnp

    from repro.kernels.spmm import leaf_spmm, spmm_view

    got = np.asarray(spmm_view(view, jnp.asarray(ctx["H"])))
    per_tile = leaf_spmm(ctx["blocks"].rows, ctx["H"])
    want = np.asarray(jax.ops.segment_sum(
        per_tile, jnp.asarray(ctx["blocks"].src), num_segments=ctx["n"]
    ))
    return np.array_equal(bits(got), bits(want))


# Plane PageRank folds each vertex's float32 sum in a different program from
# the single-device oracle, so XLA may order or contract it differently: the
# two agree to a few ulps, not bitwise.  rtol=1e-5 (~80 ulps) bounds ten
# iterations of such sums.  The single-device route stays bitwise.
PLANE_PR_RTOL = 1e-5


def _case_pagerank(view, ctx):
    from repro.core.analytics import pagerank_coo, pagerank_view
    from repro.core.shard_plane import active_plane

    got = np.asarray(pagerank_view(view))
    want = np.asarray(
        pagerank_coo(ctx["src_o"], ctx["dst_o"], ctx["n"], iters=10, damping=0.85)
    )
    if active_plane(view) is not None:
        return np.allclose(got, want, rtol=PLANE_PR_RTOL, atol=0)
    return np.array_equal(bits(got), bits(want))


def _case_bfs(view, ctx):
    from repro.core.analytics import bfs_coo, bfs_view

    got = np.asarray(bfs_view(view, 0))
    want = np.asarray(bfs_coo(ctx["src_o"], ctx["dst_o"], ctx["n"], 0))
    return np.array_equal(got, want)


def _case_sssp(view, ctx):
    import jax.numpy as jnp

    from repro.core.analytics import sssp_coo, sssp_view

    got = np.asarray(sssp_view(view, ctx["w"], 0))
    want = np.asarray(
        sssp_coo(ctx["src_o"], ctx["dst_o"], jnp.asarray(ctx["w"]), ctx["n"], 0)
    )
    return np.array_equal(bits(got), bits(want))


def _case_wcc(view, ctx):
    import jax.numpy as jnp

    from repro.core.analytics import wcc_coo, wcc_view

    src32 = jnp.asarray(ctx["src_o"], jnp.int32)
    dst32 = jnp.asarray(ctx["dst_o"])
    got = np.asarray(wcc_view(view))
    want = np.asarray(wcc_coo(
        jnp.concatenate([src32, dst32]), jnp.concatenate([dst32, src32]), ctx["n"]
    ))
    return np.array_equal(got, want)


def _case_triangle_count(view, ctx):
    from repro.core.analytics import triangle_count_fast, triangle_count_view
    from repro.core.snapshot import CSRView

    degs = np.bincount(ctx["src_o"], minlength=ctx["n"])
    off = np.zeros(ctx["n"] + 1, np.int64)
    np.cumsum(degs, out=off[1:])
    want = triangle_count_fast(CSRView(off, ctx["dst_o"]))
    return triangle_count_view(view) == want


# name -> case(view, ctx) -> bool; the *_view entry-point fixture matrix
ENTRY_CASES = {
    "edge_search_view": _case_edge_search,
    "intersect_tiles_view": _case_intersect,
    "sum_intersect_tiles_view": _case_sum_intersect,
    "leaf_scan_reduce_view": _case_scan_reduce,
    "leaf_spmm_view": _case_leaf_spmm,
    "spmm_view": _case_spmm,
    "pagerank_view": _case_pagerank,
    "bfs_view": _case_bfs,
    "sssp_view": _case_sssp,
    "wcc_view": _case_wcc,
    "triangle_count_view": _case_triangle_count,
}
